"""JAX process-environment helpers for entry points.

Nothing here runs at package import: entry points (``chip_smoke.py``,
``benchmark/harness.py``, ``examples/_common.py``,
``tools/pp_schedule_table.py``) call :func:`setup_compile_cache`
themselves, and
the session arms the XLA overlap flags when its plan needs them.
"""
import os

#: ``<checkout>/.jax_cache`` — fixed, absolute, derived from this file's
#: location. The path is part of JAX's cache key, so it must not move
#: between runs (no temporary name, pid, run id or clock value).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')


def setup_compile_cache():
    """Point JAX's persistent compilation cache at a placeable
    directory and return it.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache is
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Call before the first compile.
    """
    placed = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if placed:
        return placed
    import jax
    jax.config.update('jax_compilation_cache_dir',
                      DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


# XLA flags that let bucketed gradient collectives actually overlap the
# backward pass: the latency-hiding scheduler reorders independent
# collectives ahead of compute, and async collective fusion turns each
# bucket's all-reduce into a start/done pair compute can run between.
# LIBTPU_INIT_ARGS is read once at libtpu initialization and ignored by
# CPU/GPU backends, so setting it is safe on any host.
OVERLAP_FLAGS = ('--xla_tpu_enable_latency_hiding_scheduler=true '
                 '--xla_tpu_enable_async_collective_fusion=true')


def setup_overlap_flags():
    """Arm the XLA overlap flags for bucketed gradient synchronization.

    Called at session setup when the execution plan has fused-AllReduce
    (bucketed) variables; ``AUTODIST_XLA_OVERLAP=0`` opts out. The flags
    are appended to ``LIBTPU_INIT_ARGS`` only if absent. libtpu reads
    the variable once at backend init, so when the backend is already
    up the setting reaches only processes launched after this point
    (the coordinator forwards the variable to workers); returns the
    flag string applied, or '' when opted out / already present.
    """
    from autodist_tpu.const import ENV
    if not ENV.AUTODIST_XLA_OVERLAP.val:
        return ''
    cur = os.environ.get('LIBTPU_INIT_ARGS', '')
    missing = [f for f in OVERLAP_FLAGS.split()
               if f.split('=')[0] not in cur]
    if not missing:
        return ''
    os.environ['LIBTPU_INIT_ARGS'] = \
        (cur + ' ' + ' '.join(missing)).strip()
    return ' '.join(missing)
