"""Where the benchmark's files are, for the tests beside this file."""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, 'benchmark')


def names(kind, ext):
    """Sorted base names of ``benchmark/<kind>/*<ext>``."""
    return sorted(os.path.basename(p)[:-len(ext)]
                  for p in glob.glob(os.path.join(BENCH, kind, '*' + ext))
                  if not os.path.basename(p).startswith('__'))


def benchmark_json():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def tiny_config(causal, dtype='float32', positions=32):
    """``TransformerConfig.tiny`` widths as a configuration file's dict."""
    return dict(name='tiny', family='transformer', num_hidden_layers=2,
                hidden_size=64, num_attention_heads=4,
                intermediate_size=256, vocab_size=256,
                max_position_embeddings=positions, causal=causal,
                tied_embeddings=True, dtype=dtype, remat=True,
                scan_layers=True, loss_chunk=0,
                task='causal_lm' if causal else 'masked_lm',
                mask_token_id=3, mask_rate=0.15)
