"""Checkpoint suite (mirrors reference tests/checkpoint/):

- saver round-trip under a partitioning strategy, restored into a
  *different* distribution setup (the single-node-compatibility contract,
  test_partitionedPS_saver.py / saver.py:50-57);
- CheckpointManager retention;
- SavedModel export;
- functional-path save/restore across different meshes.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import autodist_tpu as ad
from autodist_tpu.api import Trainer
from autodist_tpu.checkpoint.saver import (CheckpointManager, Saver,
                                           SavedModelBuilder, load_pytree,
                                           save_pytree)
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec
from autodist_tpu.strategy import AllReduce, PartitionedPS


def resource_info(n=8):
    return {'nodes': [{'address': 'localhost', 'gpus': list(range(n)),
                       'chief': True, 'network_bandwidth': 100}]}


def _build_session(strategy_builder, n=8):
    # emulate a fresh program lifecycle (reference test_all.py:55-70
    # forks per case; one AutoDist per process is a hard parity rule)
    from autodist_tpu import autodist as ad_mod
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(resource_info=resource_info(n),
                           strategy_builder=strategy_builder)
    graph = autodist.scope()
    with graph:
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        W = ad.Variable(np.arange(8, dtype=np.float32).reshape(4, 2),
                        name='W')
        b = ad.Variable(np.zeros(2, np.float32), name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(x @ W + b))
        train_op = ad.optimizers.SGD(0.1).minimize(loss)
        saver = Saver()
        sess = autodist.create_distributed_session()
    return sess, saver, (x, loss, train_op)


def test_saver_roundtrip_across_strategies(tmp_path):
    """Save under PartitionedPS, restore under AllReduce: logical layout."""
    sess, saver, (x, loss, train_op) = _build_session(PartitionedPS())
    sess.run([loss, train_op], {x: np.ones((8, 4), np.float32)})
    w_after = sess.get_variable_value('W')
    path = str(tmp_path / 'ckpt')
    saver.save(sess, path)
    sess.close()

    sess2, saver2, _ = _build_session(AllReduce())
    saver2.restore(sess2, path)
    assert np.allclose(sess2.get_variable_value('W'), w_after)
    sess2.close()


def test_saver_checkpoint_is_logical_npy(tmp_path):
    sess, saver, _ = _build_session(AllReduce())
    path = str(tmp_path / 'ckpt')
    saver.save(sess, path, global_step=7)
    tensors, step = load_pytree(path + '-7')
    assert step == 7
    assert tensors['W'].shape == (4, 2)  # original unpartitioned layout
    sess.close()


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path / 'ckpts'), max_to_keep=2)
    for s in (1, 2, 3):
        mgr.save(s, {'a': np.full((2,), s, np.float32)})
    assert mgr.all_steps() == [2, 3]
    tree, step = mgr.restore(like={'a': np.zeros((2,), np.float32)})
    assert step == 3 and np.allclose(tree['a'], 3)


def test_checkpoint_manager_orbax_backend(tmp_path):
    """Same manager contract (retention, latest-step restore) with
    tensor IO delegated to orbax/tensorstore."""
    pytest.importorskip('orbax.checkpoint')
    mgr = CheckpointManager(str(tmp_path / 'ckpts'), max_to_keep=2,
                            backend='orbax')
    for s in (1, 2, 3):
        mgr.save(s, {'a': np.full((2,), s, np.float32),
                     'nest': {'b': np.arange(3.0)}})
    assert mgr.all_steps() == [2, 3]
    like = {'a': np.zeros((2,), np.float32),
            'nest': {'b': np.zeros((3,))}}
    tree, step = mgr.restore(like=like)
    assert step == 3 and np.allclose(tree['a'], 3)
    assert np.allclose(tree['nest']['b'], [0, 1, 2])
    # sharded trainer state round-trips through orbax too
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(tp=2))
    state = tr.init(jax.random.PRNGKey(0))
    params = tr.get_params(state)
    mgr.save(4, params)
    got, _ = mgr.restore(like=params, step=4)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        assert np.allclose(a, b)


def test_full_state_resume_via_orbax_live_arrays(tmp_path):
    """save_state hands the orbax backend LIVE (sharded) arrays — the
    multi-host-safe path — and restore rebuilds the state from a
    shape/dtype skeleton, never device_get-ing the template."""
    pytest.importorskip('orbax.checkpoint')
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    tr = Trainer(TransformerLM(cfg), optax.adam(1e-2),
                 spec=ParallelSpec(tp=2))
    s = tr.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}
    s, _ = tr.step(s, batch)
    mgr = CheckpointManager(str(tmp_path / 'ock'), backend='orbax')
    tr.save_state(mgr, s)
    s2, step = tr.restore_state(mgr, tr.init(jax.random.PRNGKey(9)))
    assert step == 1
    for a, b in zip(jax.tree.leaves(s2), jax.tree.leaves(s)):
        assert np.allclose(np.asarray(a), np.asarray(b))


def test_full_state_resume_is_exact(tmp_path):
    """Interrupt-and-resume reproduces the uninterrupted run exactly:
    optimizer slots and step ride the checkpoint, and restore works onto
    a DIFFERENT mesh (tp=2 -> dp)."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}
    opt = optax.adam(1e-2)   # slot-heavy: resume must carry moments

    # uninterrupted: 4 steps
    tr = Trainer(model, opt, spec=ParallelSpec())
    s = tr.init(jax.random.PRNGKey(0))
    ref_losses = []
    for _ in range(4):
        s, m = tr.step(s, batch)
        ref_losses.append(float(m['loss']))

    # interrupted: 2 steps on tp=2, checkpoint via fit, resume on dp
    mgr = CheckpointManager(str(tmp_path / 'ck'))
    tr1 = Trainer(model, opt, spec=ParallelSpec(tp=2))
    s1 = tr1.init(jax.random.PRNGKey(0))
    s1, hist1 = tr1.fit(s1, [batch] * 2, checkpoint_manager=mgr)
    assert np.allclose(hist1['loss'], ref_losses[:2], atol=2e-4)

    tr2 = Trainer(model, opt, spec=ParallelSpec())
    template = tr2.init(jax.random.PRNGKey(1))   # different init: ignored
    s2, step = tr2.restore_state(mgr, template)
    assert step == 2 and int(s2.step) == 2
    resumed = []
    for _ in range(2):
        s2, m = tr2.step(s2, batch)
        resumed.append(float(m['loss']))
    assert np.allclose(resumed, ref_losses[2:], atol=2e-4), \
        (resumed, ref_losses[2:])

    # no checkpoint -> template unchanged
    empty = CheckpointManager(str(tmp_path / 'none'))
    s3, step3 = tr2.restore_state(empty, template)
    assert step3 is None and s3 is template


def test_saved_model_builder(tmp_path):
    sess, _, _ = _build_session(AllReduce())
    export = str(tmp_path / 'export')
    b = SavedModelBuilder(export)
    b.add_meta_graph_and_variables(sess, tags=['serve'])
    b.save()
    assert os.path.exists(os.path.join(export, 'saved_model.json'))
    tensors, _ = load_pytree(os.path.join(export, 'variables'))
    assert 'W' in tensors
    sess.close()


_FRESH_LOADER = """
import json, os, sys
import numpy as np
import jax
from jax import export as jx

d, x_path, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
meta = json.load(open(os.path.join(d, 'saved_model.json')))
sig = meta['signatures']['serving_default']
with open(os.path.join(d, sig['module_file']), 'rb') as f:
    module = jx.deserialize(f.read())
man = json.load(open(os.path.join(d, 'variables', 'manifest.json')))
params = {k: np.load(os.path.join(d, 'variables', v['file']))
          for k, v in man['tensors'].items()}
out = module.call(params, np.load(x_path))
np.save(out_path, np.asarray(out[0]))
"""


def test_saved_model_serves_in_fresh_process(tmp_path):
    """The exported bundle is genuinely servable: a FRESH python process
    that never imports the framework (only jax + numpy, reading the
    documented bundle layout) reproduces the live session's prediction
    bit-for-bit, including at a batch size never seen at export time
    (polymorphic batch dim). Reference contract:
    tests/checkpoint/test_saved_model.py:26-29."""
    import subprocess
    import sys
    from autodist_tpu import autodist as ad_mod
    ad_mod._DEFAULT_AUTODIST.clear()
    autodist = ad.AutoDist(resource_info=resource_info(2),
                           strategy_builder=AllReduce())
    rng = np.random.RandomState(0)
    with autodist.scope():
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        W = ad.Variable(rng.randn(4, 2).astype(np.float32), name='W')
        b = ad.Variable(np.zeros(2, np.float32), name='b')
        pred = x @ W + b
        loss = ad.ops.reduce_mean(ad.ops.square(pred))
        train_op = ad.optimizers.SGD(0.1).minimize(loss)
        sess = autodist.create_distributed_session()
        sess.run(train_op, {x: rng.randn(8, 4).astype(np.float32)})
        export = str(tmp_path / 'export')
        builder = SavedModelBuilder(export)
        builder.add_meta_graph_and_variables(
            sess, tags=['serve'],
            signature_def_map={'serving_default': (pred, [x])})
        builder.save()
        batches = {8: rng.randn(8, 4).astype(np.float32),
                   3: rng.randn(3, 4).astype(np.float32)}
        want = {n: np.asarray(sess.run(pred, {x: v}))
                for n, v in batches.items()}
    sess.close()

    loader = tmp_path / 'loader.py'
    loader.write_text(_FRESH_LOADER)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('PYTHONPATH', None)   # no framework import possible
    for n, batch in batches.items():
        x_path = str(tmp_path / ('x%d.npy' % n))
        out_path = str(tmp_path / ('out%d.npy' % n))
        np.save(x_path, batch)
        subprocess.run([sys.executable, str(loader), export, x_path,
                        out_path], check=True, env=env, timeout=300)
        got = np.load(out_path)
        assert got.shape == (n, 2)
        np.testing.assert_allclose(got, want[n], atol=1e-6)


def test_export_servable_roundtrip_and_multi_signature(tmp_path):
    """Functional-path exporter: load_servable reproduces fn(params, x);
    a second signature joins the same bundle without clobbering the
    first."""
    from autodist_tpu.checkpoint.export import (export_servable,
                                                load_servable)
    rng = np.random.RandomState(1)
    params = {'w': rng.randn(4, 2).astype(np.float32),
              'b': rng.randn(2).astype(np.float32)}

    def fn(p, x):
        return [x @ p['w'] + p['b']]

    def fn2(p, x):
        return [jnp.tanh(x @ p['w'])]

    path = str(tmp_path / 'bundle')
    export_servable(fn, params, [((None, 4), np.float32)], path)
    export_servable(fn2, params, [((None, 4), np.float32)], path,
                    signature='tanh')
    x = rng.randn(6, 4).astype(np.float32)
    serve = load_servable(path)
    np.testing.assert_allclose(serve(x)[0], x @ params['w'] + params['b'],
                               atol=1e-6)
    serve2 = load_servable(path, signature='tanh')
    np.testing.assert_allclose(serve2(x)[0],
                               np.tanh(x @ params['w']), atol=1e-6)
    # both signatures recorded in the metadata
    import json as _json
    meta = _json.load(open(os.path.join(path, 'saved_model.json')))
    assert set(meta['signatures']) == {'serving_default', 'tanh'}


def test_export_independent_batch_dims(tmp_path):
    """shared_batch_dim=False: two inputs with genuinely independent
    dynamic leading dims export correctly and serve with DIFFERENT
    batch sizes per input (ADVICE r3: a single shared 'b' symbol forced
    them equal)."""
    from autodist_tpu.checkpoint.export import (export_servable,
                                                load_servable)
    rng = np.random.RandomState(2)
    params = {'w': rng.randn(4, 3).astype(np.float32)}

    def fn(p, queries, keys):
        # (Q, 3) x (K, 3) -> (Q, K) similarity: Q and K are unrelated
        return [(queries @ p['w']) @ (keys @ p['w']).T]

    path = str(tmp_path / 'bundle_ind')
    export_servable(fn, params,
                    [((None, 4), np.float32), ((None, 4), np.float32)],
                    path, shared_batch_dim=False)
    q = rng.randn(5, 4).astype(np.float32)
    k = rng.randn(9, 4).astype(np.float32)   # different leading dim
    serve = load_servable(path)
    out = np.asarray(serve(q, k)[0])
    want = (q @ params['w']) @ (k @ params['w']).T
    np.testing.assert_allclose(out, want, atol=1e-5)
    import json as _json
    meta = _json.load(open(os.path.join(path, 'saved_model.json')))
    assert meta['signatures']['serving_default'][
        'shared_batch_dim'] is False


def test_functional_state_roundtrip_across_meshes(tmp_path):
    """Trainer state saved on a tp=2 mesh restores onto a dp mesh."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}

    tr1 = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(tp=2))
    s1 = tr1.init(jax.random.PRNGKey(0))
    s1, _ = tr1.step(s1, batch)
    path = str(tmp_path / 'state')
    save_pytree(path, tr1.get_params(s1), step=1)

    tr2 = Trainer(model, optax.sgd(0.1), spec=ParallelSpec())
    host_params, step = load_pytree(path,
                                    like=jax.eval_shape(
                                        model.init, jax.random.PRNGKey(0)))
    s2 = tr2.init(jax.random.PRNGKey(0), params=host_params)
    assert step == 1
    # identical forward loss from the restored params
    l1 = float(model.loss(tr1.get_params(s1),
                          {k: jnp.asarray(v) for k, v in batch.items()}))
    l2 = float(model.loss(tr2.get_params(s2),
                          {k: jnp.asarray(v) for k, v in batch.items()}))
    assert np.allclose(l1, l2, atol=1e-5)


def test_shape_mismatch_raises(tmp_path):
    path = str(tmp_path / 'ckpt')
    save_pytree(path, {'a': np.zeros((2, 3), np.float32)})
    with pytest.raises(ValueError):
        load_pytree(path, like={'a': np.zeros((3, 2), np.float32)})


@pytest.mark.parametrize('backend', ['npy', 'orbax'])
def test_async_save_roundtrip_and_retention(tmp_path, backend):
    """async_save=True: save returns immediately, values are a
    snapshot at call time (later mutation invisible), retention holds,
    and restore drains the in-flight write first."""
    if backend == 'orbax':
        pytest.importorskip('orbax.checkpoint')
    from autodist_tpu.checkpoint.saver import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / 'ck'), max_to_keep=2,
                            backend=backend, async_save=True)
    trees = {}
    try:
        for step in (1, 2, 3):
            tree = {'w': jnp.full((4,), float(step)),
                    'b': {'x': jnp.arange(3, dtype=jnp.float32) * step}}
            trees[step] = jax.tree.map(np.asarray, tree)
            mgr.save(step, tree)
        mgr.wait_until_finished()
        assert mgr.all_steps() == [2, 3]    # retention kept latest 2
        got, got_step = mgr.restore(
            like=jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                trees[3]))
        assert got_step == 3
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(trees[3])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        mgr.close()   # release the orbax async worker


def test_async_save_error_surfaces_on_wait(tmp_path):
    from autodist_tpu.checkpoint.saver import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / 'ck'), backend='npy',
                            async_save=True)
    # poison the target: a FILE where the ckpt dir rename must land
    target = mgr._ckpt_path(7)
    os.makedirs(os.path.dirname(target), exist_ok=True)
    with open(target, 'w') as f:
        f.write('in the way')
    mgr.save(7, {'w': jnp.zeros(2)})
    with pytest.raises(Exception):
        mgr.wait_until_finished()


def test_fit_with_async_checkpointing(tmp_path):
    """fit(save_every=...) with an async manager trains, saves, and the
    final drain leaves a restorable full state."""
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.checkpoint.saver import CheckpointManager
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    rng = np.random.RandomState(0)

    def batches(n):
        for _ in range(n):
            yield {'tokens': rng.randint(0, 64, (4, 8), dtype=np.int32),
                   'targets': rng.randint(0, 64, (4, 8), dtype=np.int32)}

    cfg = TransformerConfig.tiny(dtype=jnp.float32, vocab=64, max_len=8)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=2))
    mgr = CheckpointManager(str(tmp_path / 'ck'), backend='npy',
                            async_save=True)
    state = tr.init(jax.random.PRNGKey(0))
    state, hist = tr.fit(state, batches(5), checkpoint_manager=mgr,
                         save_every=2)
    assert mgr.latest_step() is not None
    restored, got = tr.restore_state(mgr, state)
    assert got == mgr.latest_step()
    np.testing.assert_allclose(
        np.asarray(restored.params['embed']['table']),
        np.asarray(state.params['embed']['table']), atol=0)


def test_async_manager_close_is_idempotent(tmp_path):
    pytest.importorskip('orbax.checkpoint')
    from autodist_tpu.checkpoint.saver import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / 'ck'), backend='orbax',
                            async_save=True)
    mgr.save(1, {'w': jnp.ones(2)})
    mgr.close()
    mgr.close()
    assert mgr.all_steps() == [1]
