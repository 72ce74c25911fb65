"""Model-zoo training smoke + parity: every model family actually trains.

The zoo was once write-only; this gives each family a
real Trainer step on the CPU mesh (loss finite and decreasing), and
shards the CNNs over data to catch sharding-hostile shapes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from autodist_tpu.api import Trainer
from autodist_tpu.parallel.axes import ParallelSpec


def _train(model, batch, spec=None, steps=3, lr=0.05,
           require_decrease=True):
    tr = Trainer(model, optax.sgd(lr), spec=spec or ParallelSpec())
    state = tr.init(jax.random.PRNGKey(0))
    losses = []
    for _ in range(steps):
        state, m = tr.step(state, batch)
        losses.append(float(m['loss']))
    assert all(np.isfinite(l) for l in losses), losses
    if require_decrease:
        assert losses[-1] < losses[0], losses
    else:   # deep BN nets are not monotonic in 2 steps; just alive
        assert losses[-1] != losses[0], losses
    return losses


def _image_batch(n=8, hw=32, classes=10):
    rng = np.random.RandomState(0)
    return {'images': rng.rand(n, hw, hw, 3).astype('f4'),
            'labels': rng.randint(0, classes, (n,), dtype=np.int32)}


@pytest.mark.parametrize('name', ['resnet', 'vgg', 'densenet',
                                  'inception'])
def test_vision_models_train_sharded(name):
    from autodist_tpu.models import vision
    # inception's grid reductions need >= 75px (it raises below)
    builders = {
        'resnet': lambda: (vision.ResNet((1, 1), num_classes=10), 32),
        'vgg': lambda: (vision.VGG((8, 'M', 16, 'M'), num_classes=10,
                                   fc_spatial=8), 32),
        'densenet': lambda: (vision.DenseNet((2, 2), num_classes=10), 32),
        'inception': lambda: (vision.InceptionV3(num_classes=10), 80),
    }
    model, hw = builders[name]()
    lr = 0.01 if name == 'vgg' else 0.05   # no-BN net: keep SGD cool
    _train(model, _image_batch(hw=hw), spec=ParallelSpec(dp=8), steps=2,
           lr=lr, require_decrease=(name != 'inception'))


def test_vgg_wrong_spatial_raises():
    from autodist_tpu.models import vision
    model = vision.VGG((8, 'M'), num_classes=5)   # fc sized for 7x7
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match='fc_spatial'):
        model.apply(params, jnp.zeros((1, 32, 32, 3), jnp.float32))


def test_inception_too_small_raises():
    from autodist_tpu.models import vision
    model = vision.InceptionV3(num_classes=5)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match='75x75'):
        model.apply(params, jnp.zeros((1, 32, 32, 3), jnp.float32))


def test_lstm_lm_trains():
    from autodist_tpu.models.rnn import LSTMLM
    rng = np.random.RandomState(1)
    batch = {'tokens': rng.randint(0, 100, (8, 16), dtype=np.int32),
             'targets': rng.randint(0, 100, (8, 16), dtype=np.int32)}
    _train(LSTMLM(vocab=100, dim=16, hidden=32, n_layers=2), batch,
           lr=0.5)


def test_ncf_trains():
    from autodist_tpu.models.ncf import NCF
    rng = np.random.RandomState(2)
    batch = {'users': rng.randint(0, 50, (32,), dtype=np.int32),
             'items': rng.randint(0, 30, (32,), dtype=np.int32),
             'labels': rng.randint(0, 2, (32,), dtype=np.int32)}
    _train(NCF(50, 30, mf_dim=4, mlp_dims=(8, 4)), batch, lr=0.5)


@pytest.mark.parametrize('name', ['resnet', 'vgg', 'densenet'])
def test_vision_output_shapes(name):
    from autodist_tpu.models import vision
    model = {
        'resnet': lambda: vision.ResNet((1, 1), num_classes=7),
        'vgg': lambda: vision.VGG((8, 'M'), num_classes=7, fc_spatial=16),
        'densenet': lambda: vision.DenseNet((2,), num_classes=7),
    }[name]()
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, jnp.zeros((2, 32, 32, 3), jnp.float32))
    assert out.shape == (2, 7)


def _tiny_lm_loss_and_grads(**kw):
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (4, 128), dtype=np.int32),
             'targets': rng.randint(0, 256, (4, 128), dtype=np.int32)}
    m = TransformerLM(TransformerConfig.tiny(dtype=jnp.float32,
                                             max_len=128, **kw))
    params = m.init(jax.random.PRNGKey(0))
    loss, grads = jax.jit(jax.value_and_grad(m.loss))(params, batch)
    return float(loss), grads


@pytest.fixture(scope='module')
def plain_lm():
    """(loss, grads) of the unchunked, non-remat forward."""
    return _tiny_lm_loss_and_grads()


@pytest.mark.parametrize('kw', [
    dict(loss_chunk=64),
    dict(remat='save_attn', loss_chunk=64),
    dict(remat=True, loss_chunk=64),
    dict(remat='dots', loss_chunk=64),
    dict(remat='dots_no_batch', loss_chunk=64),
], ids=['chunked', 'save_attn', 'full_remat', 'dots', 'dots_no_batch'])
def test_chunked_ce_and_remat_modes_match_plain(plain_lm, kw):
    """loss_chunk and remat ('save_attn'/full) must not change the math:
    same loss and same gradients as the unchunked, non-remat forward."""
    ref_loss, ref_grads = plain_lm
    loss, grads = _tiny_lm_loss_and_grads(**kw)
    assert abs(loss - ref_loss) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_chunked_ce_indivisible_rows_falls_back():
    """loss_chunk that cannot split the seq dim evenly must quietly run
    unchunked (n=1), not crash or change results."""
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    rng = np.random.RandomState(1)
    batch = {'tokens': rng.randint(0, 256, (2, 7), dtype=np.int32),
             'targets': rng.randint(0, 256, (2, 7), dtype=np.int32)}
    plain = TransformerLM(TransformerConfig.tiny(dtype=jnp.float32))
    chunked = TransformerLM(TransformerConfig.tiny(dtype=jnp.float32,
                                                   loss_chunk=4))
    params = plain.init(jax.random.PRNGKey(0))
    l0 = float(jax.jit(plain.loss)(params, batch))
    l1 = float(jax.jit(chunked.loss)(params, batch))
    assert abs(l0 - l1) < 1e-6


def test_batchnorm_running_stats_advance_and_serve_eval():
    """BN EMAs advance during Trainer.step (state channel, not the
    optimizer) and Trainer.evaluate normalizes with them."""
    from autodist_tpu.models import vision

    model = vision.ResNet((1, 1), num_classes=10)
    tr = Trainer(model, optax.adamw(0.01), spec=ParallelSpec(dp=1))
    assert tr._has_state
    batch = _image_batch(n=8, hw=32)
    state = tr.init(jax.random.PRNGKey(0))

    def stem_ema(s):
        return np.asarray(s.params['stem']['bn']['ema_mean'])

    ema0 = stem_ema(state)
    assert np.allclose(ema0, 0.0)          # fresh stats
    state, _ = tr.step(state, batch)
    ema1 = stem_ema(state)
    assert not np.allclose(ema1, 0.0)      # advanced by the step
    state, _ = tr.step(state, batch)
    ema2 = stem_ema(state)
    assert not np.allclose(ema2, ema1)

    # eval uses the running stats: loss differs from a fresh-stats model
    # evaluated on the same params ONLY through the ema leaves
    eval_loss = tr.evaluate(state, [batch])
    frozen = jax.tree.map(lambda x: x, state.params)
    frozen['stem']['bn']['ema_mean'] = jnp.ones_like(
        frozen['stem']['bn']['ema_mean']) * 5.0
    state2 = state.__class__(params=frozen, opt_state=state.opt_state,
                             step=state.step)
    eval_loss2 = tr.evaluate(state2, [batch])
    assert np.isfinite(eval_loss) and np.isfinite(eval_loss2)
    assert abs(eval_loss - eval_loss2) > 1e-6


def test_batchnorm_ema_not_touched_by_weight_decay():
    """adamw's weight decay must not decay the EMA leaves: after one
    step the EMA equals EXACTLY m*ema0 + (1-m)*batch_stat — any
    optimizer contribution (decay shifts ~3% here) would break it."""
    from autodist_tpu.models.core import Module
    from autodist_tpu.models.vision import BatchNorm

    class BnModel(Module):
        def __init__(self):
            self.bn = BatchNorm(3)

        def param_defs(self):
            return {'bn': self.bn}

        def loss(self, params, batch):
            return (self.bn.apply(params['bn'], batch['x']) ** 2).mean()

    rng = np.random.RandomState(0)
    x = rng.rand(8, 4, 4, 3).astype('f4')
    tr = Trainer(BnModel(), optax.adamw(0.05, weight_decay=0.5),
                 spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    state, _ = tr.step(state, {'x': x})
    m = 0.9
    want_mean = m * 0.0 + (1 - m) * x.mean((0, 1, 2))
    want_var = m * 1.0 + (1 - m) * x.var((0, 1, 2))
    np.testing.assert_allclose(
        np.asarray(state.params['bn']['ema_mean']), want_mean, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(state.params['bn']['ema_var']), want_var, atol=1e-6)


def test_shared_stateful_module_rejected():
    """One BatchNorm instance at two tree positions cannot carry two
    running-stat homes — Trainer construction must refuse it."""
    from autodist_tpu.models.core import Module
    from autodist_tpu.models.vision import BatchNorm

    class Shared(Module):
        def __init__(self):
            self.bn = BatchNorm(3)

        def param_defs(self):
            return {'a': self.bn, 'b': self.bn}

        def loss(self, params, batch):   # pragma: no cover
            return 0.0

    with pytest.raises(ValueError, match='multiple tree positions'):
        Trainer(Shared(), optax.sgd(0.1), spec=ParallelSpec(dp=1))


def test_apply_tree_updates_is_copy_on_write():
    from autodist_tpu.models.core import apply_tree_updates
    tree = {'a': {'b': jnp.zeros(2), 'c': jnp.ones(2)}}
    out = apply_tree_updates(tree, {('a', 'b'): jnp.full((2,), 7.0)})
    assert np.allclose(out['a']['b'], 7.0)
    assert np.allclose(tree['a']['b'], 0.0)   # input untouched
    assert out['a']['c'] is tree['a']['c']    # untouched leaves shared


def test_grad_accum_with_batchnorm_state():
    """grad_accum composes with the state channel (last-chunk EMA)."""
    from autodist_tpu.models import vision

    model = vision.ResNet((1, 1), num_classes=10)
    tr = Trainer(model, optax.sgd(0.01),
                 spec=ParallelSpec(dp=1, grad_accum=2))
    batch = _image_batch(n=8, hw=32)
    state = tr.init(jax.random.PRNGKey(0))
    ema0 = np.asarray(state.params['stem']['bn']['ema_mean'])
    state, m = tr.step(state, batch)
    ema1 = np.asarray(state.params['stem']['bn']['ema_mean'])
    assert np.isfinite(float(m['loss']))
    assert not np.allclose(ema1, ema0)
