"""Shared example bootstrap: repo-root import path + compile cache.

The examples run on whatever backend JAX finds. For the CPU test mesh
pass plain environment variables: ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from autodist_tpu.utils.jax_env import setup_compile_cache  # noqa: E402

setup_compile_cache()


def timed_steps(trainer, state, batch, steps):
    """Shared benchmark harness: AOT-compile the step once, place the
    sharded batch on device once, warm up, then time ``steps`` calls of
    the compiled executable.

    Returns ``(state, last_loss, elapsed_s)``. The host readback
    (``float``) of the last loss fences the timed window.
    """
    import time

    compiled = trainer.compile_step(state, batch)
    batch = trainer.shard_batch(batch)
    state, metrics = compiled(state, batch)   # warmup
    float(metrics['loss'])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = compiled(state, batch)
    loss = float(metrics['loss'])
    return state, loss, time.perf_counter() - t0
