"""BERT-large phase-1 remat-policy sweep (round 5 frontier probe).

The 47.5%-MFU point uses FULL per-block remat; round 4's per-op
profile attributed ~9% of the step to scan-stacking bookkeeping plus
the full recompute. This sweeps the selective policies ('dots' keeps
every matmul output — recompute only elementwise work) against full
remat and no remat at phase-1 and phase-2 shapes. OOM rows are
recorded as such.
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

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    peak = B.peak_flops_for(jax.devices()[0])
    rng = np.random.RandomState(0)
    steps = 8
    cases = [(128, 512), (128, 384), (512, 96)]
    if len(sys.argv) == 3:        # usage: bert_remat_sweep.py SEQ BATCH
        cases = [(int(sys.argv[1]), int(sys.argv[2]))]
    elif len(sys.argv) != 1:
        sys.exit('usage: bert_remat_sweep.py [SEQ BATCH]')
    for seq, bs in cases:
        for remat in (True, 'dots', False):
            cfg = TransformerConfig.bert_large(dtype=jnp.bfloat16,
                                               remat=remat)
            batch = {'tokens': rng.randint(0, cfg.vocab, (bs, seq),
                                           dtype=np.int32),
                     'targets': rng.randint(0, cfg.vocab, (bs, seq),
                                            dtype=np.int32)}
            label = 's%d_B%d_remat-%s' % (seq, bs, remat)
            try:
                stats = {}
                dt, _ = B.run_workload(TransformerLM(cfg), batch,
                                       steps=steps, stats_out=stats)
                tps = bs * seq * steps / dt
                print(label, json.dumps(
                    {'tokens_per_s_chip': round(tps, 1),
                     'mfu_pct': B.mfu_pct(
                         tps * B.bert_train_flops_per_token(cfg, seq),
                         peak),
                     'dispersion_pct': stats['dispersion_pct']}),
                    flush=True)
            except Exception as e:   # noqa: BLE001 - OOM rows recorded
                print(label, json.dumps({'error': str(e)[:160]}),
                      flush=True)


if __name__ == '__main__':
    main()
