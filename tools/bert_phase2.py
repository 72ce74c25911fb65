"""BERT-large phase-2 (seq 512) sweep on the chip.

Sweeps per-chip batch and the flash-attention kernel (force-on vs the
auto XLA path — seq 512 sits at the kernel's measured 1.0x crossover)
at bert_large's own example default sequence length. Reports tokens/s
per chip + analytic MFU per config, median of 3 fenced blocks.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bench as B


def main():
    from autodist_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    dev = jax.devices()[0]
    peak = B.peak_flops_for(dev)
    seq = 512
    cfg = TransformerConfig.bert_large(dtype=jnp.bfloat16, remat=True)
    rng = np.random.RandomState(0)
    flops_tok = B.bert_train_flops_per_token(cfg, seq)
    auto_min = fa.MIN_KERNEL_SEQ

    batches = [int(b) for b in
               (sys.argv[1:] or ['64', '96', '128'])]
    force_off = 10 ** 9   # the xla-attn arm must DISABLE the kernel
                          # regardless of the adopted default threshold
    for batch_size in batches:
        batch = {'tokens': rng.randint(0, cfg.vocab, (batch_size, seq),
                                       dtype=np.int32),
                 'targets': rng.randint(0, cfg.vocab, (batch_size, seq),
                                        dtype=np.int32)}
        for flash in (False, True):
            fa.MIN_KERNEL_SEQ = 512 if flash else force_off
            label = 'B%d_%s' % (batch_size,
                                'flash' if flash else 'xla-attn')
            try:
                stats = {}
                dt, _ = B.run_workload(TransformerLM(cfg), batch,
                                       steps=8, stats_out=stats)
                tps = batch_size * seq * 8 / dt
                print(label, json.dumps(
                    {'tokens_per_s_chip': round(tps, 1),
                     'mfu_pct': B.mfu_pct(tps * flops_tok, peak),
                     'dispersion_pct': stats['dispersion_pct']}),
                    flush=True)
            except Exception as e:   # noqa: BLE001 - OOM rows recorded
                print(label, json.dumps({'error': str(e)[:200]}),
                      flush=True)
    fa.MIN_KERNEL_SEQ = auto_min


if __name__ == '__main__':
    main()
