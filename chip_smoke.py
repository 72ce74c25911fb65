"""Quickest proof that the program still starts on the chip.

    python3 chip_smoke.py              # on a machine with a TPU
    python3 chip_smoke.py --cpu-dry-run   # debug the command on the CPU

Drives both engines once, in ONE process (a chip belongs to one process
at a time), through the entry points a user calls:

1. builds the native coord service from ``autodist_tpu/native/*.cc``;
2. compiles the flash attention kernels, non-interpreted, at the
   shapes the models use, and compares each with plain jnp;
3. trains BERT-large at its published widths (24 x 1024 x 16, vocab
   30522, bf16, remat) at seq 512 through ``Trainer`` for a few steps —
   at dp=1, and on four or more devices also at dp=4 and dp=2 x tp=2;
4. runs the reference-shaped session (``AutoDist.scope()`` ->
   ``create_distributed_session()`` -> ``sess.run``): the c0
   linear-regression ground truth under ``AllReduce`` and a dense model
   under ``AllReduce`` / ``Parallax`` / ``PartitionedPS``;
5. runs one loose-mode leg (native coord service + PS push/pull around
   a device step).

Every phase checks its result and raises; nothing here catches a
phase's failure, so any failed phase is a non-zero exit. The last line
of stdout is one JSON object. Times, rates and byte counts printed on
the way are labelled smoke observations of this one run, not benchmark
results. Without a TPU backend the script refuses to run; the only
other mode is ``--cpu-dry-run`` (tiny widths, interpreted kernels),
which says that it is one and prints no device figure.
"""
import argparse
import json
import os
import re
import shutil
import socket
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

SEQ = 512                  # flash kernel on the path (MIN_KERNEL_SEQ)
BERT_STEPS = 5             # timed steps after the warm-up step
# Per-chip batch: 96 is the repo's own seq-512 record; the smaller ones
# are tried, in order, only when XLA reports the chip's HBM exhausted at
# compile time — the run says which one it used.
BERT_PER_CHIP_BATCHES = (96, 64, 48, 32)
C0_EXPECTED_B = 0.01 * 4.17503   # reference c0 ground truth, one SGD step

# Kernel-vs-reference tolerance: max |kernel - ref| <= KERNEL_RTOL x
# max |ref|, the reference being plain jnp in float32 at 'highest'
# matmul precision. The kernels take bf16 operands, accumulate in f32 on
# the MXU, round the probabilities (and, backward, dS) to bf16 before
# the second matmul, and round the result to bf16: a few bf16 roundings
# (2^-9 relative each) against the tensor's scale. 2^-6 is four bf16
# ulps — about 2.5x the worst ratio seen on a v5e (0.6%, dq at seq
# 4096) — while a wrong mask, block index or scale is off by O(1).
KERNEL_RTOL = 2.0 ** -6

# an HLO instruction reads "... <shape> all-reduce(<operands>)"; operand
# references are "%all-reduce.7" and never match
_COLLECTIVE_RE = re.compile(
    r' (all-reduce|all-gather|reduce-scatter|collective-permute|'
    r'all-to-all)(?:-start)?\(')


def check(cond, msg):
    """A check that survives ``python -O`` (unlike ``assert``)."""
    if not cond:
        raise AssertionError(msg)


def say(msg):
    print(msg, flush=True)


class CompileCounter:
    """Counts backend compile requests and persistent-cache traffic from
    JAX's own monitoring events (a cache hit still counts as a compile
    request; it is just a short one)."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.requests += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.cache_writes += 1


# ---------------------------------------------------------------------------
# phase 1: native build
# ---------------------------------------------------------------------------

def phase_native():
    """The loose-mode leg needs the coord service built from the tracked
    sources on this machine; a missing compiler is a failure here, not a
    degraded run later."""
    from autodist_tpu import native_build
    check(shutil.which('g++'), 'g++ not found: cannot build the native '
          'coord service from autodist_tpu/native/coord_service.cc')
    t0 = time.perf_counter()
    binary = native_build.build('coord_service.cc')
    check(os.access(binary, os.X_OK), 'no executable at %s' % binary)
    say('native: coord_service at %s (%.1f s; a fraction of a second '
        'means the source-hash cache already held it)'
        % (binary, time.perf_counter() - t0))


# ---------------------------------------------------------------------------
# phase 2: kernels vs plain jnp
# ---------------------------------------------------------------------------

def _max_abs(x):
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(x.astype(jnp.float32))))


def _check_close(name, got, ref, rtol):
    scale = _max_abs(ref)
    err = _max_abs(got.astype('float32') - ref.astype('float32'))
    check(err == err and err <= rtol * scale,
          '%s: max abs error %.4g exceeds %.4g x scale %.4g'
          % (name, err, rtol, scale))
    return err / scale


def _reference_attention(q, k, v, causal):
    import jax
    import jax.numpy as jnp
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   precision='highest') * q.shape[-1] ** -0.5
    if causal:
        n = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, axis=-1), v,
                      precision='highest')


def _check_flash(shape, causal, interpret):
    """Forward and backward of the flash kernel at ``shape`` against the
    plain attention; returns the compile+run seconds of both."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import flash_attention as fa

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk in keys)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal,
                                  interpret=interpret)

    def reference(q, k, v):
        return _reference_attention(q, k, v, causal)

    def grads_of(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) *
                                    do.astype(jnp.float32)),
            argnums=(0, 1, 2)))

    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(kernel)(q, k, v))
    grads = jax.block_until_ready(grads_of(kernel)(q, k, v))
    elapsed = time.perf_counter() - t0
    ratios = [_check_close('flash fwd %s' % (shape,), out,
                           jax.jit(reference)(q, k, v), KERNEL_RTOL)]
    for name, g, r in zip(('dq', 'dk', 'dv'), grads,
                          grads_of(reference)(q, k, v)):
        ratios.append(_check_close('flash %s %s' % (name, shape), g, r,
                                   KERNEL_RTOL))
    say('kernel flash %s causal=%s %s: fwd+bwd match plain jnp '
        '(worst error/scale %.2g, tolerance %.2g)'
        % (shape, causal, fa._plan(shape, causal), max(ratios),
           KERNEL_RTOL))
    return elapsed


def phase_kernels(dry_run):
    """The flash attention kernels, compiled by Mosaic (not
    interpreted) at the shapes the models use: BERT-large attention
    (16 heads x 64, seq 512, blocks 256/512) and the long-context LM's
    (12 heads x 64, seq 4096 causal, blocks 512/1024)."""
    if dry_run:
        # same code, interpreted, at sizes the CPU can chew
        flash_shapes = [((1, 2, 512, 16), False)]
    else:
        flash_shapes = [((4, 16, 512, 64), False),
                        ((2, 12, 4096, 64), True)]
    interpret = dry_run   # explicit: never whatever a default picks
    elapsed = sum(_check_flash(shape, causal, interpret)
                  for shape, causal in flash_shapes)
    if not dry_run:
        say('observation: kernel phase compile+run %.1f s' % elapsed)


# ---------------------------------------------------------------------------
# phase 3: BERT-large through the Trainer
# ---------------------------------------------------------------------------

def _collectives(hlo):
    return sorted(set(_COLLECTIVE_RE.findall(hlo)))


def _check_placement(trainer, state):
    """Every parameter leaf is laid out as the trainer's sharding tree
    says, and the state really lives on every device of the mesh — not
    all on device 0."""
    import jax
    mesh_devices = set(trainer.mesh.devices.flat)
    params = jax.tree.leaves(state.params)
    expected = jax.tree.leaves(
        trainer._param_sharding_tree(state.params),
        is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    check(len(params) == len(expected), 'sharding tree mismatch')
    split = 0
    for leaf, want in zip(params, expected):
        check(leaf.sharding.is_equivalent_to(want, leaf.ndim),
              'param %s placed as %s, expected %s'
              % (leaf.shape, leaf.sharding, want))
        check(set(leaf.sharding.device_set) == mesh_devices,
              'param %s spans %d of %d mesh devices'
              % (leaf.shape, len(leaf.sharding.device_set),
                 len(mesh_devices)))
        split += leaf.sharding.shard_shape(leaf.shape) != leaf.shape
    holders = {s.device for leaf in jax.tree.leaves(state)
               for s in leaf.addressable_shards}
    check(holders == mesh_devices,
          'state shards sit on %d devices, mesh has %d'
          % (len(holders), len(mesh_devices)))
    if trainer.spec.tp > 1:
        check(split > 0, 'tp=%d but no parameter is partitioned'
              % trainer.spec.tp)
    return split, len(params)


def phase_bert(dp, tp, dry_run, compiles):
    """A few optimizer steps of BERT-large at seq 512 through
    ``Trainer``, built exactly as ``examples/bert.py`` builds it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    name = 'bert dp=%d tp=%d' % (dp, tp)
    if dry_run:
        # tiny widths; max_len must cover SEQ or the position lookup
        # reads out of range and the loss goes nan
        cfg = TransformerConfig.tiny(dtype=jnp.float32, max_len=SEQ)
        per_chip_batches = (2,)
    else:
        cfg = TransformerConfig.bert_large(dtype=jnp.bfloat16, remat=True)
        per_chip_batches = BERT_PER_CHIP_BATCHES
    trainer = Trainer(TransformerLM(cfg), optax.adamw(1e-4),
                      spec=ParallelSpec(dp=dp, tp=tp))
    check(trainer.mesh.size == dp * tp, '%s: mesh %s' % (name,
                                                         trainer.mesh))
    state = trainer.init(jax.random.PRNGKey(0))
    split, leaves = _check_placement(trainer, state)

    rng = np.random.RandomState(0)
    compiled = None
    for per_chip in per_chip_batches:
        batch_size = per_chip * dp
        batch = {'tokens': rng.randint(0, cfg.vocab, (batch_size, SEQ),
                                       dtype=np.int32),
                 'targets': rng.randint(0, cfg.vocab, (batch_size, SEQ),
                                        dtype=np.int32)}
        hits_before = compiles.cache_hits
        t0 = time.perf_counter()
        try:
            compiled = trainer.compile_step(state, batch)   # ONE compile
        except jax.errors.JaxRuntimeError as e:
            # the one failure this phase steps around, and reports:
            # the batch not fitting the chip's HBM
            if 'RESOURCE_EXHAUSTED' not in str(e):
                raise
            say('%s: per-chip batch %d does not fit HBM at compile '
                'time; trying a smaller one' % (name, per_chip))
            continue
        compile_s = time.perf_counter() - t0
        break
    check(compiled is not None, '%s: no per-chip batch of %s fits'
          % (name, per_chip_batches))

    hlo = compiled.as_text()
    kernel_calls = hlo.count('tpu_custom_call')
    if not dry_run:
        # the kernel was dispatched: attention did not drop to the jnp
        # path (models/attention.py) and Mosaic compiled it
        check(kernel_calls > 0, '%s: no Pallas custom call in the '
              'compiled step' % name)
    found = _collectives(hlo)
    if dp * tp == 1:
        check(not found, '%s: one-device step has collectives %s'
              % (name, found))
    else:
        check('all-reduce' in found, '%s: no all-reduce in a %d-device '
              'step (found %s)' % (name, dp * tp, found))

    placed = trainer.shard_batch(batch)
    state, metrics = compiled(state, placed)            # warm-up
    losses = [float(metrics['loss'])]
    requests_before = compiles.requests
    t0 = time.perf_counter()
    for _ in range(BERT_STEPS):
        state, metrics = compiled(state, placed)
        losses.append(float(metrics['loss']))           # fences the step
    step_s = (time.perf_counter() - t0) / BERT_STEPS
    check(compiles.requests == requests_before,
          '%s: %d compilation(s) after warm-up'
          % (name, compiles.requests - requests_before))
    check(all(np.isfinite(losses)), '%s: non-finite loss %s'
          % (name, losses))
    check(losses[-1] < losses[0], '%s: loss did not fall on the fixed '
          'batch: %s' % (name, losses))
    check(int(state.step) == BERT_STEPS + 1, '%s: step counter %d'
          % (name, int(state.step)))

    say('%s: %d layers x %d wide x %d heads, vocab %d, seq %d, per-chip '
        'batch %d (global %d), %s; %d/%d parameter leaves partitioned; '
        'collectives %s; loss %.4f -> %.4f over %d steps'
        % (name, cfg.n_layers, cfg.dim, cfg.n_heads, cfg.vocab, SEQ,
           per_chip, batch_size, jnp.dtype(cfg.dtype).name, split, leaves,
           found or 'none', losses[0], losses[-1], BERT_STEPS + 1))
    if not dry_run:
        peaks = [d.memory_stats()['peak_bytes_in_use']
                 for d in trainer.mesh.devices.flat]
        say('observation %s on %s: step compile %.1f s (%s), step '
            '%.1f ms, %.0f tokens/s (%.0f per chip), %d Pallas custom '
            'calls in the step, peak_bytes_in_use per device since '
            'process start %s'
            % (name, jax.devices()[0].device_kind, compile_s,
               'served by the persistent cache'
               if compiles.cache_hits > hits_before else 'cold',
               step_s * 1e3, batch_size * SEQ / step_s,
               batch_size * SEQ / step_s / (dp * tp), kernel_calls,
               peaks))


# ---------------------------------------------------------------------------
# phase 4: the reference-shaped session
# ---------------------------------------------------------------------------

def _fresh_autodist(strategy_builder, ps_cpus=1):
    """A new ``AutoDist`` over all local devices. One instance per
    process is the reference's rule; consecutive legs clear the slot."""
    import autodist_tpu as ad
    from autodist_tpu import autodist as ad_mod
    ad_mod._DEFAULT_AUTODIST.clear()
    info = ad_mod._default_resource_info()
    # PS builders place (and partition over) host CPU devices
    info['nodes'][0]['cpus'] = list(range(ps_cpus))
    return ad.AutoDist(resource_info=info,
                       strategy_builder=strategy_builder)


def _check_session_placement(sess, n):
    import jax
    devices = set(jax.devices())
    for name, arr in sess._var_state.items():
        check(set(arr.sharding.device_set) == devices,
              'session variable %s spans %d of %d devices'
              % (name, len(arr.sharding.device_set), n))


def _session_c0(n):
    """Reference case c0: seeded linear regression, one SGD step."""
    import numpy as np

    import autodist_tpu as ad

    autodist = _fresh_autodist(ad.AllReduce(chunk_size=128))
    np.random.seed(123)
    inputs = np.random.randn(1000)
    outputs = inputs * 3.0 + 2.0 + np.random.randn(1000)
    with autodist.scope():
        x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        W = ad.Variable(5.0, name='W')
        b = ad.Variable(0.0, name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
        train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
        sess = autodist.create_distributed_session()
        sess.run([loss, train_op], {x: inputs, y: outputs})
        b_val = float(np.ravel(sess.run([b])[0])[0])
    check(abs(b_val - C0_EXPECTED_B) < 1e-5,
          'c0: b=%r after one step, expected %r' % (b_val, C0_EXPECTED_B))
    check(bool(sess._plan.last_bucket_stats) == (n > 1),
          'c0: bucketed collectives %s on %d device(s)'
          % (sess._plan.last_bucket_stats, n))
    _check_session_placement(sess, n)
    sess.close()
    say('session c0 (AllReduce, %d device(s)): b == 0.01*4.17503 after '
        'one step' % n)


def _session_dense(builder, n, ps_cpus=1, width=512, steps=4):
    """A dense three-matmul model a few MB wide; returns the losses,
    the emitted collectives and the per-variable shard shapes."""
    import numpy as np

    import autodist_tpu as ad

    autodist = _fresh_autodist(builder, ps_cpus)
    rng = np.random.RandomState(0)
    feed_x = rng.randn(8 * n, width).astype(np.float32)
    feed_y = rng.randn(8 * n, 1).astype(np.float32)

    def weights(*shape):
        return (rng.randn(*shape) * 0.05).astype(np.float32)

    with autodist.scope():
        x = ad.placeholder(shape=[None, width], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None, 1], dtype=np.float32, name='y')
        w1 = ad.Variable(weights(width, 2 * width), name='w1')
        w2 = ad.Variable(weights(2 * width, width), name='w2')
        w3 = ad.Variable(weights(width, 1), name='w3')
        h = ad.ops.relu(ad.ops.matmul(x, w1))
        h = ad.ops.relu(ad.ops.matmul(h, w2))
        loss = ad.ops.reduce_mean(
            ad.ops.square(ad.ops.matmul(h, w3) - y))
        train_op = ad.optimizers.SGD(0.002).minimize(loss)
        sess = autodist.create_distributed_session()
        losses = [float(sess.run([loss, train_op],
                                 {x: feed_x, y: feed_y})[0])
                  for _ in range(steps)]
    _check_session_placement(sess, n)
    buckets = list(sess._plan.last_bucket_stats)
    shards = {name: (arr.shape, arr.sharding.shard_shape(arr.shape))
              for name, arr in sess._var_state.items()}
    sess.close()
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          '%s: losses %s' % (type(builder).__name__, losses))
    return losses, buckets, shards


def phase_session():
    """The session engine on every local device: c0 ground truth, then
    the same dense model under three builders, which in sync mode must
    train the same numbers while emitting different collectives."""
    import jax
    import numpy as np

    import autodist_tpu as ad

    n = jax.device_count()
    _session_c0(n)
    base, buckets, _ = _session_dense(ad.AllReduce(chunk_size=128), n)
    check(bool(buckets) == (n > 1), 'AllReduce buckets %s' % buckets)
    say('session dense (AllReduce, %d device(s)): losses %s'
        % (n, [round(v, 5) for v in base]))
    for builder, ps_cpus in ((ad.Parallax(), 1),
                             (ad.PartitionedPS(), 2)):
        name = type(builder).__name__
        losses, buckets, shards = _session_dense(builder, n, ps_cpus)
        check(np.allclose(losses, base, rtol=1e-4, atol=1e-6),
              '%s trained %s, AllReduce trained %s' % (name, losses, base))
        check(bool(buckets) == (n > 1),
              '%s: emitted collectives %s on %d device(s)'
              % (name, buckets, n))
        kinds = sorted({b['kind'] for b in buckets})
        if name == 'PartitionedPS' and n > 1:
            # a sharded variable was really emitted: every device holds
            # a strict slice of each weight
            check(all(shard != full for full, shard in shards.values()),
                  'PartitionedPS left a variable whole: %s' % shards)
        say('session dense (%s, %d device(s)): losses %s match '
            'AllReduce; collectives %s; shard shapes %s'
            % (name, n, [round(v, 5) for v in losses], kinds or 'none',
               {k: v[1] for k, v in shards.items()}))


# ---------------------------------------------------------------------------
# phase 5: loose mode in one process
# ---------------------------------------------------------------------------

def phase_loose(steps=4, dim=640):
    """Relaxed-consistency PS through the native coord service: pull,
    device step, push — the multi-process data plane driven from this
    one process (``utils/loose_harness``)."""
    import numpy as np

    import autodist_tpu as ad
    from autodist_tpu import autodist as ad_mod
    from autodist_tpu.runtime.coord_client import (CoordClient,
                                                   ensure_service)
    from autodist_tpu.utils.loose_harness import single_process_loose_env

    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    service = ensure_service(port=port)
    check(service is not None, 'a service already listens on %d' % port)
    try:
        with single_process_loose_env(port, depth=2) as session_sees_one:
            autodist = ad.AutoDist(
                resource_info=ad_mod._default_resource_info(),
                strategy_builder=ad.strategy.PS(staleness=2))
            rng = np.random.RandomState(0)
            feed = rng.randn(8, dim).astype(np.float32)
            with autodist.scope():
                x = ad.placeholder(shape=[None, dim], dtype=np.float32,
                                   name='x')
                W = ad.Variable(rng.randn(dim, dim).astype(np.float32),
                                name='W')
                loss = ad.ops.reduce_mean(
                    ad.ops.square(ad.ops.matmul(x, W)))
                train_op = ad.optimizers.SGD(0.01).minimize(loss, [W])
                autodist._build()     # sees 2 processes -> loose mode
                session_sees_one()
                sess = autodist.create_distributed_session()
                losses = [float(sess.run([loss, train_op], {x: feed})[0])
                          for _ in range(steps)]
                sess.get_variable_value('W')   # drains the push pipeline
                stats = dict(sess.ps_stats)
                sess.close()
    finally:
        # stop the one process this script started
        CoordClient(('127.0.0.1', port)).shutdown()
        service.wait(timeout=10)
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          'loose mode losses %s' % losses)
    check(stats['push_bytes'] > 0 and stats['pull_bytes'] > 0,
          'loose mode moved no PS bytes: %s' % stats)
    say('loose mode (PS staleness=2, native coord service): loss %.4f '
        '-> %.4f, pushed %d B, pulled %d B'
        % (losses[0], losses[-1], stats['push_bytes'],
           stats['pull_bytes']))


# ---------------------------------------------------------------------------

def main():
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--cpu-dry-run', action='store_true',
                        help='tiny widths and interpreted kernels on the '
                             'CPU, to debug this command; proves nothing '
                             'about a chip')
    args = parser.parse_args()
    dry_run = args.cpu_dry_run
    if dry_run:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    # a user-like flow: the test-only behaviour switch must be off
    os.environ.pop('AUTODIST_IS_TESTING', None)

    sys.path.insert(0, REPO)
    # before anything touches the backend: alone in a directory, without
    # the program, this import is what fails
    import autodist_tpu  # noqa: F401
    from autodist_tpu.utils.jax_env import setup_compile_cache
    cache_dir = setup_compile_cache()

    import jax
    import jaxlib
    backend = jax.default_backend()
    if dry_run:
        say('CPU DRY RUN: tiny widths, interpreted kernels, %d virtual '
            'CPU device(s). It checks that this command runs; it says '
            'nothing about a chip and prints no device figure.'
            % jax.device_count())
    elif backend != 'tpu':
        sys.exit("chip_smoke.py: needs a TPU, but jax.default_backend() "
                 "is %r (JAX_PLATFORMS=%r). Refusing to run; "
                 "--cpu-dry-run only debugs the command."
                 % (backend, os.environ.get('JAX_PLATFORMS')))
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': jax.device_count()}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    say('device: platform=%(platform)s device_kind=%(kind)s '
        'count=%(count)d' % device)
    say('versions: python %s, jax %s, jaxlib %s, libtpu %s'
        % (sys.version.split()[0], jax.__version__, jaxlib.__version__,
           libtpu_version))
    say('compile cache: %s (%s)'
        % (cache_dir, 'from JAX_COMPILATION_CACHE_DIR'
           if os.environ.get('JAX_COMPILATION_CACHE_DIR')
           else 'default inside the checkout'))
    libtpu_args_at_start = os.environ.get('LIBTPU_INIT_ARGS', '')
    say('LIBTPU_INIT_ARGS at backend start: %r' % libtpu_args_at_start)

    compiles = CompileCounter()
    phase_native()
    phase_kernels(dry_run)
    phase_bert(1, 1, dry_run, compiles)
    if jax.device_count() >= 4:
        phase_bert(4, 1, dry_run, compiles)
        phase_bert(2, 2, dry_run, compiles)
    phase_session()
    phase_loose()

    if os.environ.get('LIBTPU_INIT_ARGS', '') != libtpu_args_at_start:
        say('note: the session appended %r to LIBTPU_INIT_ARGS after the '
            'backend was up, so in this one-process run those XLA '
            'overlap flags were NOT applied'
            % os.environ['LIBTPU_INIT_ARGS'])
    say('compiles: %d backend compile requests, %d served by the '
        'persistent cache, %d entries written to it'
        % (compiles.requests, compiles.cache_hits, compiles.cache_writes))
    if not dry_run:
        say('observation: %.1f s in compile requests, %.1f s wall for '
            'the whole run' % (compiles.seconds,
                               time.perf_counter() - t_start))
    print(json.dumps({'ok': True, 'cpu_dry_run': True} if dry_run else
                     {'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
