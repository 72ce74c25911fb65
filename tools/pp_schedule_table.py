"""Measure the pipeline-schedule trade table.

For pp in {2, 4}: GPipe vs fused-1F1B(remat) vs fused-1F1B(stash), all
through the same Trainer/TransformerLM path on the 8-device virtual CPU
mesh. Reported per config:

- compiled FLOPs (``compiled.cost_analysis()['flops']``) — recorded
  but NOT comparable across these three programs (while-loop bodies
  count once and the schedules have different loop structures — see
  the BASELINE.md round-5 caveats),
- temp memory (``memory_analysis().temp_size_in_bytes``) — the
  activation working set,
- wall step time on the CPU mesh (1 host core, so wall ≈ serialized
  total compute) — the compute evidence, with that caveat stated.

Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/pp_schedule_table.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from autodist_tpu.utils.jax_env import setup_compile_cache

setup_compile_cache()

import dataclasses

import numpy as np

import jax
import optax

from autodist_tpu.api import Trainer
from autodist_tpu.models.transformer import (TransformerConfig,
                                             TransformerLM)
from autodist_tpu.parallel.axes import ParallelSpec


def measure(model, batch, pp, schedule, variant, microbatches, steps=3):
    tr = Trainer(model, optax.sgd(0.1),
                 spec=ParallelSpec(pp=pp, dp=1,
                                   microbatches=microbatches,
                                   pp_schedule=schedule,
                                   pp_variant=variant))
    state = tr.init(jax.random.PRNGKey(0))
    compiled = tr.compile_step(state, batch)
    mem = compiled.memory_analysis().temp_size_in_bytes
    cost = compiled.cost_analysis()
    flops = cost.get('flops', float('nan')) if cost else float('nan')
    sharded = tr.shard_batch(batch)
    state, m = compiled(state, sharded)   # warmup
    loss = float(m['loss'])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, sharded)
    float(m['loss'])
    dt = (time.perf_counter() - t0) / steps
    return {'temp_mb': mem / 1e6, 'gflops': flops / 1e9,
            'step_s': dt, 'loss': loss}


def main():
    cfg = dataclasses.replace(
        TransformerConfig.tiny(dtype=np.float32, n_layers=8,
                               max_len=128), vocab=4096)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 4096, (32, 128)),
             'targets': rng.randint(0, 4096, (32, 128))}
    M = 16
    rows = []
    for pp in (2, 4):
        for label, schedule, variant in (
                ('gpipe', 'gpipe', 'auto'),
                ('fused-remat', '1f1b', 'remat'),
                ('fused-stash', '1f1b', 'stash')):
            r = measure(model, batch, pp, schedule, variant, M)
            r.update(pp=pp, config=label)
            rows.append(r)
            print(json.dumps(r), flush=True)
    # quick consistency: every config trains the same loss
    losses = {round(r['loss'], 3) for r in rows}
    print('# distinct warmup losses (expect 1):', losses)


if __name__ == '__main__':
    main()
