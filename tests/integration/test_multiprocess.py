"""Multi-process tier: real 2-process runs on localhost.

Mirrors the reference's 2-machine distributed tests
(``tests/integration/test_dist.py``, ``Jenkinsfile:96-140``) on one host:

- sync tier: two processes form a global SPMD mesh via ``jax.distributed``
  (gloo CPU collectives), the chief builds + publishes the strategy over
  the native coord service, both train one c0 step on role-seeded data and
  must land on the reference's 2-worker ground truth
  ``b == 0.01*(4.17503+4.05530)/2`` (cases/c0.py:92-120).
- staleness tier (c9 parity, cases/c9.py:14-21,92-125): relaxed PS runs in
  loose mode (independent local programs + coord-service PS); a fast chief
  must never run more than ``staleness`` steps ahead of a slow worker, and
  must actually hit that bound.
- async tier: ``sync=False`` never blocks the fast worker.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import free_port, shutdown_service

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# reference c0 per-role gradient ground truth (cases/c0.py:92-120)
GRAD_CHIEF, GRAD_WORKER = 4.17503, 4.05530


COMMON_PRELUDE = textwrap.dedent("""
    import json, os, sys, time
    import numpy as np
    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', 1)
    sys.path.insert(0, %(repo)r)
    import autodist_tpu as ad

    ROLE = 'worker' if os.environ.get('AUTODIST_WORKER') else 'chief'
    RESOURCE_INFO = {'nodes': [
        {'address': 'localhost', 'gpus': [0], 'chief': True,
         'network_bandwidth': 100},
        {'address': '127.0.0.1', 'gpus': [0], 'network_bandwidth': 100},
    ]}

    def make_data(seed):
        np.random.seed(seed)
        inputs = np.random.randn(1000)
        noises = np.random.randn(1000)
        outputs = inputs * 3.0 + 2.0 + noises
        return inputs.astype(np.float32), outputs.astype(np.float32)
""")


def launch_procs(tmp_path, script_body, nprocs, timeout=300,
                 extra_env=None, require_result=None,
                 worker_addrs=None):
    """Write the script, run it as N launch_cli-style local processes.

    ``require_result[i]``: process i must exit 0 and print a RESULT
    line; False = any exit code, RESULT optional (crash-test workers).
    """
    if require_result is None:
        require_result = (True,) * nprocs
    script = tmp_path / 'prog.py'
    script.write_text(COMMON_PRELUDE % {'repo': REPO} + script_body)
    coord_service = '127.0.0.1:%d' % free_port()
    jax_coord = '127.0.0.1:%d' % free_port()
    procs = []
    for pid in range(nprocs):
        env = dict(os.environ)
        env.pop('AUTODIST_IS_TESTING', None)
        env.update({
            'AUTODIST_PROCESS_ID': str(pid),
            'AUTODIST_NUM_PROCESSES': str(nprocs),
            'AUTODIST_COORDINATOR_ADDR': jax_coord,
            'AUTODIST_COORD_SERVICE_ADDR': coord_service,
        })
        env.update(extra_env or {})
        if pid > 0:
            env['AUTODIST_WORKER'] = (
                worker_addrs[pid - 1] if worker_addrs else '127.0.0.1')
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append((p.returncode, out, err))
    finally:
        shutdown_service(coord_service)
    results = []
    for required, (rc, out, err) in zip(require_result, outs):
        if required:
            assert rc == 0, \
                'process failed (rc=%s)\nstdout:\n%s\nstderr:\n%s' \
                % (rc, out, err[-4000:])
        line = [ln for ln in out.splitlines() if ln.startswith('RESULT ')]
        if required:
            assert line, 'no RESULT line in output:\n%s' % out
        results.append(json.loads(line[-1][len('RESULT '):])
                       if line else None)
    return results


def launch_pair(tmp_path, script_body, timeout=300, extra_env=None,
                require_result=(True, True)):
    return launch_procs(tmp_path, script_body, 2, timeout=timeout,
                        extra_env=extra_env,
                        require_result=require_result)


@pytest.mark.integration
def test_two_process_sync_c0_parity(tmp_path):
    """Global-mesh SPMD across 2 processes: reference 2-worker c0 value."""
    body = textwrap.dedent("""
        autodist = ad.AutoDist(resource_info=RESOURCE_INFO,
                               strategy_builder=ad.strategy.AllReduce())
        inputs, outputs = make_data(123 if ROLE == 'chief' else 456)
        with autodist.scope():
            x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
            y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
            W = ad.Variable(5.0, name='W')
            b = ad.Variable(0.0, name='b')
            loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            sess.run([loss, train_op], {x: inputs, y: outputs})
            b_val = float(np.ravel(sess.get_variable_value('b'))[0])
            W_val = float(np.ravel(sess.get_variable_value('W'))[0])
        print('RESULT ' + json.dumps({'role': ROLE, 'b': b_val,
                                      'W': W_val}), flush=True)
        autodist._coord.barrier('test/done', 2, timeout_s=60.0)
    """)
    results = launch_pair(tmp_path, body)
    expected_b = 0.01 * (GRAD_CHIEF + GRAD_WORKER) / 2.0
    assert {r['role'] for r in results} == {'chief', 'worker'}
    for r in results:
        assert np.isclose(r['b'], expected_b, atol=1e-4), r
    # both processes must agree bit-for-bit on the trained state
    assert results[0]['b'] == results[1]['b']
    assert results[0]['W'] == results[1]['W']


STALENESS_BODY = textwrap.dedent("""
    STALENESS = 3
    TOTAL_STEPS = 8
    SLEEP_S = 1.0
    autodist = ad.AutoDist(
        resource_info=RESOURCE_INFO,
        strategy_builder=ad.strategy.PS(%(builder_kwargs)s))
    inputs, outputs = make_data(123 if ROLE == 'chief' else 456)
    with autodist.scope():
        x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        W = ad.Variable(5.0, name='W')
        b = ad.Variable(0.0, name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
        train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
        sess = autodist.create_distributed_session()
        lead = []   # chief: how far ahead of the slow worker each step ran
        for step in range(1, TOTAL_STEPS + 1):
            sess.run(train_op, {x: inputs, y: outputs})
            if ROLE == 'chief':
                lead.append(step - sess.peer_step(1))
            else:
                time.sleep(SLEEP_S)
        b_final = float(np.ravel(sess.get_variable_value('b'))[0])
    print('RESULT ' + json.dumps({'role': ROLE, 'lead': lead,
                                  'b': b_final}), flush=True)
    autodist._coord.barrier('test/done', 2, timeout_s=120.0)
""")


@pytest.mark.integration
def test_staleness_bounds_fast_worker(tmp_path):
    """c9 semantics: fast chief never exceeds the staleness window, and
    does run ahead (it is not lock-stepped)."""
    body = STALENESS_BODY % {'builder_kwargs': 'staleness=3'}
    results = launch_pair(tmp_path, body, timeout=420)
    chief = next(r for r in results if r['role'] == 'chief')
    lead = chief['lead']
    # never more than `staleness` completed steps ahead of the slow worker
    assert max(lead) <= 3, lead
    # actually exercised the window (ran ahead; not synchronous lockstep)
    assert max(lead) >= 2, lead
    # both workers' pushes reached the PS: the value moved
    for r in results:
        assert abs(r['b']) > 1e-4


@pytest.mark.integration
def test_proxy_variable_serves_reads_from_cache(tmp_path):
    """local_proxy_variable in loose mode: pre-step reads come from the
    worker-local proxy (refreshed post-push, reference
    proxy_variable.py:163-190); staleness semantics still hold and both
    workers' updates still reach the PS."""
    body = STALENESS_BODY % {
        'builder_kwargs': 'staleness=3, local_proxy_variable=True'}
    body = body.replace(
        "print('RESULT ' + json.dumps({'role': ROLE, 'lead': lead,",
        "proxy_hits = sess._proxy_hits\n"
        "print('RESULT ' + json.dumps({'role': ROLE, 'lead': lead,"
        " 'proxy_hits': proxy_hits,")
    results = launch_pair(tmp_path, body, timeout=420)
    chief = next(r for r in results if r['role'] == 'chief')
    assert max(chief['lead']) <= 3, chief['lead']
    for r in results:
        # 8 steps x 2 vars; all pulls after the first step hit the proxy
        assert r['proxy_hits'] >= 14, r
        assert abs(r['b']) > 1e-4


@pytest.mark.integration
def test_async_ps_never_blocks(tmp_path):
    """sync=False: unconditional no-wait — the fast chief finishes all
    steps while the slow worker lags far beyond any staleness bound."""
    body = STALENESS_BODY % {'builder_kwargs': 'sync=False'}
    results = launch_pair(tmp_path, body, timeout=420)
    chief = next(r for r in results if r['role'] == 'chief')
    # ran ahead well past what a staleness gate would permit
    assert max(chief['lead']) >= 5, chief['lead']
    for r in results:
        assert abs(r['b']) > 1e-4


SHARED_OPT_BODY = textwrap.dedent("""
    autodist = ad.AutoDist(
        resource_info=RESOURCE_INFO,
        strategy_builder=ad.strategy.PS(staleness=1, %(extra_kwargs)s))
    inputs, outputs = make_data(123)     # same data on both roles
    with autodist.scope():
        x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        W = ad.Variable(5.0, name='W')
        b = ad.Variable(0.0, name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
        train_op = ad.optimizers.Momentum(0.01, momentum=0.9) \\
            .minimize(loss, [W, b])
        sess = autodist.create_distributed_session()
        for _ in range(5):
            sess.run(train_op, {x: inputs, y: outputs})
        autodist._coord.barrier('test/trained', 2, timeout_s=120.0)
        b_final = float(np.ravel(sess.get_variable_value('b'))[0])
    print('RESULT ' + json.dumps(
        {'role': ROLE, 'b': b_final,
         'shared_pushes': sess._shared_pushes}), flush=True)
    autodist._coord.barrier('test/done', 2, timeout_s=120.0)
""")


@pytest.mark.integration
def test_shared_optimizer_state_on_ps(tmp_path):
    """shared_optimizer=True runs the momentum step ON the PS with a
    service-resident velocity shared by both workers (reference
    PS-resident optimizer, kernel/partitioner.py:570-573). The shared
    velocity integrates all 10 pushes (2 workers x 5 steps), so |b|
    travels measurably further than with worker-local velocities that
    each see only 5 pushes (theoretical ratio for interleaved equal
    gradients: ~1.58)."""
    shared = launch_pair(tmp_path, SHARED_OPT_BODY % {
        'extra_kwargs': 'shared_optimizer=True'}, timeout=420)
    local = launch_pair(tmp_path, SHARED_OPT_BODY % {
        'extra_kwargs': 'shared_optimizer=False'}, timeout=420)
    b_shared = next(r['b'] for r in shared if r['role'] == 'chief')
    b_local = next(r['b'] for r in local if r['role'] == 'chief')
    for r in shared:
        # every step pushed both vars through BSTEP
        assert r['shared_pushes'] == 10, r
    for r in local:
        assert r['shared_pushes'] == 0, r
    assert abs(b_shared) > 1e-3 and abs(b_local) > 1e-3
    assert abs(b_shared) > 1.15 * abs(b_local), (b_shared, b_local)


@pytest.mark.integration
def test_shared_adam_state_on_ps(tmp_path):
    """shared_optimizer=True with ADAM runs the user's actual optimizer
    rule on the PS: moments (m, v) and the bias-correction step t are
    service-resident and shared by both workers (reference semantics —
    the optimizer is re-created over PS-resident variables whatever it
    is, kernel/partitioner.py:570-573; round 3 supported only the SGD
    family). The divergence from worker-local moments is asserted on
    the STATE ITSELF (BSTAT): the shared trajectory integrates all 10
    pushes into ONE (m, v, t) — t ends at 10, where per-worker moments
    would each see only 5 — and worker-local mode leaves no optimizer
    state on the service at all. (A |b|-magnitude divergence, which the
    momentum test uses, cannot distinguish adam modes: adam's step size
    is ~lr regardless of gradient scale, so 10 shared steps and 2x5
    summed local steps travel the same distance.)"""
    body = SHARED_OPT_BODY.replace(
        "ad.optimizers.Momentum(0.01, momentum=0.9)",
        "ad.optimizers.Adam(0.05)")
    body = body.replace(
        "b_final = float(np.ravel(sess.get_variable_value('b'))[0])",
        "b_final = float(np.ravel(sess.get_variable_value('b'))[0])\n"
        "    stat = sess._coord.vstat(sess._key('var/b'))")
    body = body.replace(
        "'shared_pushes': sess._shared_pushes}), flush=True)",
        "'shared_pushes': sess._shared_pushes, 'stat': stat}),"
        " flush=True)")
    shared = launch_pair(tmp_path, body % {
        'extra_kwargs': 'shared_optimizer=True'}, timeout=420)
    local = launch_pair(tmp_path, body % {
        'extra_kwargs': 'shared_optimizer=False'}, timeout=420)
    for r in shared:
        # every step pushed both vars through BSTEP rule=adam
        assert r['shared_pushes'] == 10, r
        # ONE shared trajectory: t integrated every worker's push, and
        # both adam moments are service-resident
        assert r['stat']['steps'] == 10, r
        assert r['stat']['slot1'] and r['stat']['slot2'], r
        assert abs(r['b']) > 1e-2, r
    for r in local:
        assert r['shared_pushes'] == 0, r
        # worker-local mode: deltas only — no PS-resident moments
        assert r['stat']['steps'] == 0, r
        assert not r['stat']['slot1'] and not r['stat']['slot2'], r
        assert abs(r['b']) > 1e-2, r


@pytest.mark.integration
def test_partitioned_var_shards_span_endpoints(tmp_path):
    """Per-shard PS placement is REAL at runtime: ONE >=100 MB
    partitioned variable is spread across TWO endpoints — each shard
    keyed var/W/shard<i> on the endpoint its part_config destination
    names (reference places each shard of a partitioned variable on its
    own PS, partitioned_ps_strategy.py:89-96 + per-shard variables
    kernel/partitioner.py:153-173; round 3 read only syncs[0] and put
    the whole tensor on one socket). Frames ride 16 MB chunks, and the
    per-endpoint wire accounting must come out balanced."""
    body = textwrap.dedent("""
        DIM = 5120           # W alone is 5120*5120*4 B = 100 MB
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PartitionedPS(staleness=1))
        np.random.seed(0)
        W0 = (np.random.randn(DIM, DIM) / DIM).astype(np.float32)
        xs = np.random.randn(8, DIM).astype(np.float32)
        ys = np.random.randn(8, DIM).astype(np.float32)
        with autodist.scope():
            x = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='x')
            y = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='y')
            W = ad.Variable(W0, name='W')
            loss = ad.ops.reduce_mean(
                ad.ops.square(ad.ops.matmul(x, W) - y))
            train_op = ad.optimizers.SGD(0.1).minimize(loss, [W])
            sess = autodist.create_distributed_session()
            for _ in range(3):
                sess.run(train_op, {x: xs, y: ys})
            stats = sess.ps_stats
            shard_eps = sess._ps_index['W']
            W_after = sess.get_variable_value('W')
            moved = float(np.abs(W_after - W0).max())
            # both halves of the tensor moved (each lives on its own
            # endpoint; a one-endpoint regression strands one half)
            moved_lo = float(np.abs(W_after[:DIM//2] - W0[:DIM//2]).max())
            moved_hi = float(np.abs(W_after[DIM//2:] - W0[DIM//2:]).max())
        print('RESULT ' + json.dumps(
            {'role': ROLE, 'shard_eps': shard_eps, 'moved': moved,
             'moved_lo': moved_lo, 'moved_hi': moved_hi,
             'ep_bytes': stats['bytes_per_endpoint'],
             'ps_mb': stats['bytes'] / 1e6,
             'ps_mb_per_s': stats['mb_per_s']}), flush=True)
        autodist._coord.barrier('test/done', 2, timeout_s=120.0)
    """)
    ep_ports = [free_port(), free_port()]
    eps = ','.join('127.0.0.1:%d' % p for p in ep_ports)
    try:
        results = launch_pair(
            tmp_path, body, timeout=600,
            extra_env={'AUTODIST_PS_ENDPOINTS': eps,
                       'AUTODIST_PS_CHUNK_BYTES': str(16 << 20)})
    finally:
        for p in ep_ports:
            shutdown_service('127.0.0.1:%d' % p)
    for r in results:
        # ONE variable, TWO endpoints: the shards really span them
        assert sorted(r['shard_eps']) == [0, 1], r
        assert r['moved'] > 1e-5 and r['moved_lo'] > 1e-5 \
            and r['moved_hi'] > 1e-5, r
        # balanced per-endpoint wire accounting: an even axis-0 split
        # puts half the bytes on each endpoint
        total = sum(r['ep_bytes'])
        assert total > 0, r
        for b in r['ep_bytes']:
            assert 0.4 < b / total < 0.6, r
        assert r['ps_mb'] > 600, r     # 3 steps x (pull+push) x 100 MB
        assert r['ps_mb_per_s'] > 20, r


@pytest.mark.integration
@pytest.mark.slow
def test_loose_mode_carries_100mb_model_multi_endpoint(tmp_path):
    """The binary PS data plane carries a real (≥100 MB) model, spread
    over TWO PS endpoints placed by PSLoadBalancing's byte-size
    bin-packing (reference ps_lb_strategy.py:64-83 + one tf.Server per
    PS node, utils/server_starter.py:48-75). Asserts both endpoints
    actually host variables, both workers' updates land, and the wire
    sustains real-model throughput (the round-2 base64 text plane would
    take minutes per step here)."""
    body = textwrap.dedent("""
        DIM = 5120           # W alone is 5120*5120*4 B = 100 MB
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PSLoadBalancing(staleness=1))
        np.random.seed(0)
        W0 = (np.random.randn(DIM, DIM) / DIM).astype(np.float32)
        xs = np.random.randn(8, DIM).astype(np.float32)
        ys = np.random.randn(8, DIM).astype(np.float32)
        with autodist.scope():
            x = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='x')
            y = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='y')
            W = ad.Variable(W0, name='W')
            b = ad.Variable(np.zeros(DIM, np.float32), name='b')
            loss = ad.ops.reduce_mean(
                ad.ops.square(ad.ops.matmul(x, W) + b - y))
            train_op = ad.optimizers.SGD(0.1).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            t0 = time.time()
            for _ in range(3):
                sess.run(train_op, {x: xs, y: ys})
            wall = time.time() - t0
            stats = sess.ps_stats
            endpoints = sorted({i for v in sess._ps_index.values()
                                for i in v})
            W_after = sess.get_variable_value('W')
            moved = float(np.abs(W_after - W0).max())
        print('RESULT ' + json.dumps(
            {'role': ROLE, 'endpoints': endpoints, 'moved': moved,
             'wall_s': wall, 'ps_mb': stats['bytes'] / 1e6,
             'ps_s': stats['seconds'],
             'ps_mb_per_s': stats['mb_per_s']}), flush=True)
        autodist._coord.barrier('test/done', 2, timeout_s=120.0)
    """)
    ep_ports = [free_port(), free_port()]
    eps = ','.join('127.0.0.1:%d' % p for p in ep_ports)
    try:
        results = launch_pair(
            tmp_path, body, timeout=600,
            extra_env={'AUTODIST_PS_ENDPOINTS': eps})
    finally:
        for p in ep_ports:
            shutdown_service('127.0.0.1:%d' % p)
    # wire bytes halve under AUTODIST_PS_WIRE_DTYPE=bf16
    scale = 0.5 if os.environ.get('AUTODIST_PS_WIRE_DTYPE') == 'bf16' \
        else 1.0
    for r in results:
        # bin-packing spread variables over BOTH endpoints
        assert r['endpoints'] == [0, 1], r
        # this worker's pulls saw, and pushes changed, the 100 MB tensor
        assert r['moved'] > 1e-5, r
        # ~100 MB model, 3 steps of pull+push: the binary wire must
        # sustain real throughput (base64 text framing managed ~single-
        # digit MB/s with 33% inflation)
        assert r['ps_mb'] > 600 * scale, r
        assert r['ps_mb_per_s'] > 20 * scale, r
    print('\n2-worker PS (%s wire): per-worker wire %s MB/s, '
          'model-bytes %s MB/s' %
          (os.environ.get('AUTODIST_PS_WIRE_DTYPE', 'f32'),
           [round(r['ps_mb_per_s']) for r in results],
           [round(r['ps_mb_per_s'] / scale) for r in results]))


@pytest.mark.integration
def test_authenticated_loose_mode_end_to_end(tmp_path, monkeypatch):
    """AUTODIST_COORD_TOKEN through the full loose stack: the chief
    starts the service WITH the secret in its env, every process (and
    the background heartbeat threads' own connections) answers the
    nonce challenge, and training behaves identically to the open
    service — plus staleness semantics still hold."""
    # also in THIS process's env so launch_pair's teardown client can
    # authenticate its SHUTDOWN (else the service would leak)
    monkeypatch.setenv('AUTODIST_COORD_TOKEN', 'integration-secret-42')
    body = STALENESS_BODY % {'builder_kwargs': 'staleness=3'}
    results = launch_pair(
        tmp_path, body, timeout=420,
        extra_env={'AUTODIST_COORD_TOKEN': 'integration-secret-42'})
    chief = next(r for r in results if r['role'] == 'chief')
    assert max(chief['lead']) <= 3, chief['lead']
    # the authed plane must not degrade run-ahead into lock-step
    assert max(chief['lead']) >= 2, chief['lead']
    for r in results:
        assert abs(r['b']) > 1e-4


@pytest.mark.integration
def test_bf16_wire_end_to_end(tmp_path):
    """AUTODIST_PS_WIRE_DTYPE=bf16 halves the PS wire; training still
    converges through the quantized frames (values f32 at rest)."""
    body = STALENESS_BODY % {'builder_kwargs': 'staleness=3'}
    results = launch_pair(tmp_path, body, timeout=420,
                          extra_env={'AUTODIST_PS_WIRE_DTYPE': 'bf16'})
    chief = next(r for r in results if r['role'] == 'chief')
    assert max(chief['lead']) <= 3, chief['lead']
    for r in results:
        assert abs(r['b']) > 1e-4


@pytest.mark.integration
def test_clean_peer_shutdown_is_not_a_crash(tmp_path):
    """A peer that finishes its run and closes its session cleanly must
    not be reported as dead: Session.close publishes a done marker and
    advances its step counter past any gate bound, so a chief still
    training runs to completion instead of raising 'missed heartbeats'
    (ADVICE r2)."""
    body = textwrap.dedent("""
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PS(staleness=2))
        inputs, outputs = make_data(123 if ROLE == 'chief' else 456)
        with autodist.scope():
            x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
            y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
            W = ad.Variable(5.0, name='W')
            b = ad.Variable(0.0, name='b')
            loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            if ROLE == 'worker':
                for _ in range(2):
                    sess.run(train_op, {x: inputs, y: outputs})
                sess.close()   # clean finish: done marker published
                print('RESULT ' + json.dumps({'role': ROLE}), flush=True)
                sys.exit(0)
            steps, failed = 0, ''
            try:
                for _ in range(10):
                    sess.run(train_op, {x: inputs, y: outputs})
                    steps += 1
            except RuntimeError as e:
                failed = str(e)
            print('RESULT ' + json.dumps(
                {'role': ROLE, 'steps': steps, 'failed': failed}),
                flush=True)
    """)
    results = launch_pair(tmp_path, body, timeout=300,
                          extra_env={'AUTODIST_HEARTBEAT_TIMEOUT': '4'})
    chief = results[0]
    assert chief['failed'] == '', chief
    assert chief['steps'] == 10, chief


RESOURCE_INFO_4 = """{'nodes': [
    {'address': 'localhost', 'gpus': [0], 'chief': True,
     'network_bandwidth': 100},
    {'address': '127.0.0.1', 'gpus': [0], 'network_bandwidth': 100},
    {'address': '127.0.0.2', 'gpus': [0], 'network_bandwidth': 100},
    {'address': '127.0.0.3', 'gpus': [0], 'network_bandwidth': 100},
]}"""

WORKER_ADDRS_4 = ['127.0.0.1', '127.0.0.2', '127.0.0.3']


@pytest.mark.integration
def test_four_process_sync_c0_parity(tmp_path):
    """Global-mesh SPMD across FOUR processes (the loose/SPMD planes
    were only ever proven at 2): each role trains on its own seeded
    data; the allreduced step must land on the average of the four
    locally-computed reference gradients, bit-identical on every
    process."""
    body = textwrap.dedent("""
        RESOURCE_INFO = %s
        autodist = ad.AutoDist(resource_info=RESOURCE_INFO,
                               strategy_builder=ad.strategy.AllReduce())
        pid = int(os.environ['AUTODIST_PROCESS_ID'])
        seed = [123, 456, 789, 1011][pid]
        inputs, outputs = make_data(seed)
        # reference-style ground truth, computed locally: d/db of
        # mean((W*x + b - y)^2) at W=5, b=0
        my_grad_b = float(np.mean(2.0 * (5.0 * inputs - outputs)))
        with autodist.scope():
            x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
            y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
            W = ad.Variable(5.0, name='W')
            b = ad.Variable(0.0, name='b')
            loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            sess.run([loss, train_op], {x: inputs, y: outputs})
            b_val = float(np.ravel(sess.get_variable_value('b'))[0])
        print('RESULT ' + json.dumps({'pid': pid, 'b': b_val,
                                      'grad_b': my_grad_b}), flush=True)
        autodist._coord.barrier('test/done', 4, timeout_s=60.0)
    """) % RESOURCE_INFO_4
    results = launch_procs(tmp_path, body, 4, timeout=420,
                           worker_addrs=WORKER_ADDRS_4)
    expected_b = -0.01 * np.mean([r['grad_b'] for r in results])
    # seed-123 role must agree with the published c0 constant
    chief_grad = next(r['grad_b'] for r in results if r['pid'] == 0)
    assert np.isclose(-chief_grad, GRAD_CHIEF, atol=1e-4), chief_grad
    for r in results:
        assert np.isclose(r['b'], expected_b, atol=1e-4), (r, expected_b)
    assert len({r['b'] for r in results}) == 1      # bit-identical


@pytest.mark.integration
def test_four_worker_loose_staleness_and_heartbeats(tmp_path):
    """The loose tier at FOUR workers: the staleness gate bounds the
    fast chief against the MINIMUM of three slow peers, heartbeats stay
    alive, and every worker's pushes land (does the per-tensor-mutex
    design hold under 4-way concurrency?)."""
    body = textwrap.dedent("""
        RESOURCE_INFO = %s
        STALENESS = 2
        TOTAL_STEPS = 6
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PS(staleness=STALENESS))
        pid = int(os.environ['AUTODIST_PROCESS_ID'])
        inputs, outputs = make_data(123 + pid)
        with autodist.scope():
            x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
            y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
            W = ad.Variable(5.0, name='W')
            b = ad.Variable(0.0, name='b')
            loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            lead = []
            for step in range(1, TOTAL_STEPS + 1):
                sess.run(train_op, {x: inputs, y: outputs})
                if pid == 0:
                    lead.append(step - min(sess.peer_step(i)
                                           for i in (1, 2, 3)))
                else:
                    time.sleep(0.6)
            b_final = float(np.ravel(sess.get_variable_value('b'))[0])
        print('RESULT ' + json.dumps({'pid': pid, 'lead': lead,
                                      'b': b_final}), flush=True)
        autodist._coord.barrier('test/done', 4, timeout_s=120.0)
    """) % RESOURCE_INFO_4
    results = launch_procs(
        tmp_path, body, 4, timeout=600,
        worker_addrs=WORKER_ADDRS_4,
        extra_env={'AUTODIST_HEARTBEAT_TIMEOUT': '30'})
    chief = next(r for r in results if r['pid'] == 0)
    assert max(chief['lead']) <= 2, chief['lead']
    assert max(chief['lead']) >= 1, chief['lead']
    for r in results:
        assert abs(r['b']) > 1e-4


@pytest.mark.integration
@pytest.mark.slow
def test_four_worker_loose_100mb_two_endpoints(tmp_path):
    """The PS data plane at FOUR concurrent workers x 105 MB model x 2
    endpoints: every worker's pulls and pushes land and the aggregate
    wire rate is recorded (BASELINE.md scaling row). Exercises the
    per-tensor mutexes under 4-way push contention."""
    body = textwrap.dedent("""
        RESOURCE_INFO = %s
        DIM = 5120
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PSLoadBalancing(staleness=1))
        np.random.seed(0)
        W0 = (np.random.randn(DIM, DIM) / DIM).astype(np.float32)
        xs = np.random.randn(8, DIM).astype(np.float32)
        ys = np.random.randn(8, DIM).astype(np.float32)
        with autodist.scope():
            x = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='x')
            y = ad.placeholder(shape=[None, DIM], dtype=np.float32,
                               name='y')
            W = ad.Variable(W0, name='W')
            b = ad.Variable(np.zeros(DIM, np.float32), name='b')
            loss = ad.ops.reduce_mean(
                ad.ops.square(ad.ops.matmul(x, W) + b - y))
            train_op = ad.optimizers.SGD(0.1).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            t0 = time.time()
            for _ in range(2):
                sess.run(train_op, {x: xs, y: ys})
            wall = time.time() - t0
            stats = sess.ps_stats
            W_after = sess.get_variable_value('W')
            moved = float(np.abs(W_after - W0).max())
        print('RESULT ' + json.dumps(
            {'pid': int(os.environ['AUTODIST_PROCESS_ID']),
             'moved': moved, 'wall_s': wall,
             'ps_mb': stats['bytes'] / 1e6, 'ps_s': stats['seconds'],
             'ps_mb_per_s': stats['mb_per_s']}), flush=True)
        autodist._coord.barrier('test/done', 4, timeout_s=240.0)
    """) % RESOURCE_INFO_4
    ep_ports = [free_port(), free_port()]
    eps = ','.join('127.0.0.1:%d' % p for p in ep_ports)
    try:
        results = launch_procs(
            tmp_path, body, 4, timeout=900,
            worker_addrs=WORKER_ADDRS_4,
            extra_env={'AUTODIST_PS_ENDPOINTS': eps})
    finally:
        for p in ep_ports:
            shutdown_service('127.0.0.1:%d' % p)
    agg_mb = sum(r['ps_mb'] for r in results)
    agg_s = max(r['ps_s'] for r in results)
    # wire bytes halve under AUTODIST_PS_WIRE_DTYPE=bf16
    scale = 0.5 if os.environ.get('AUTODIST_PS_WIRE_DTYPE') == 'bf16' \
        else 1.0
    for r in results:
        assert r['moved'] > 1e-5, r
        # 2 steps x (pull+push) x 105 MB of wire
        assert r['ps_mb'] > 400 * scale, r
    # aggregate service throughput across 4 workers (recorded for
    # BASELINE.md): must beat a single worker's floor
    print('\n4-worker PS aggregate: %.0f MB over %.1f s -> %.0f MB/s '
          '(per-worker %s MB/s)' %
          (agg_mb, agg_s, agg_mb / agg_s,
           [round(r['ps_mb_per_s']) for r in results]))
    assert agg_mb / agg_s > 40, (agg_mb, agg_s)


@pytest.mark.integration
def test_dead_worker_fails_fast_not_hangs(tmp_path):
    """Failure detection: the worker crashes mid-run; the chief, blocked
    on the staleness gate, must surface a dead-peer error within the
    heartbeat window instead of hanging for the full gate timeout
    (reference coordinator.py:98-110 monitors, reinterpreted over
    coord-service heartbeats)."""
    body = textwrap.dedent("""
        STALENESS = 2
        autodist = ad.AutoDist(
            resource_info=RESOURCE_INFO,
            strategy_builder=ad.strategy.PS(staleness=STALENESS))
        inputs, outputs = make_data(123 if ROLE == 'chief' else 456)
        with autodist.scope():
            x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
            y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
            W = ad.Variable(5.0, name='W')
            b = ad.Variable(0.0, name='b')
            loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
            train_op = ad.optimizers.SGD(0.01).minimize(loss, [W, b])
            sess = autodist.create_distributed_session()
            if ROLE == 'worker':
                for _ in range(2):
                    sess.run(train_op, {x: inputs, y: outputs})
                os._exit(17)   # simulated crash: no cleanup, no barrier
            t0 = time.time()
            steps, failed = 0, ''
            try:
                for _ in range(20):
                    sess.run(train_op, {x: inputs, y: outputs})
                    steps += 1
            except RuntimeError as e:
                failed = str(e)
            print('RESULT ' + json.dumps(
                {'role': ROLE, 'steps': steps, 'failed': failed,
                 'wait_s': time.time() - t0}), flush=True)
    """)
    results = launch_pair(tmp_path, body, timeout=300,
                          extra_env={'AUTODIST_HEARTBEAT_TIMEOUT': '4'},
                          require_result=(True, False))
    chief = results[0]
    assert 'missed heartbeats' in chief['failed'], chief
    # ran ahead to the window edge (2 worker steps + staleness 2), then
    # detected the death — well before any 600s gate timeout
    assert chief['steps'] <= 4, chief
    assert chief['wait_s'] < 120, chief
