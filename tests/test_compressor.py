"""Quantized-collective compressor: int8 ring all-reduce + error feedback.

The reference's compressor tests live inside the strategy matrix (its
tier stops at fp16 casts); the int8 tier is a TPU extension, so it gets
its own parity + convergence coverage here.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import autodist_tpu as ad
from autodist_tpu.parallel.compressor import (Int8RingCompressor,
                                              int8_ring_all_reduce)


def test_int8_ring_matches_psum():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 1000).astype('f4'))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ('data',))

    def ring(x):
        return int8_ring_all_reduce(x, 'data')

    from autodist_tpu.parallel.axes import shard_map
    got = jax.jit(shard_map(ring, mesh, P('data'),
                                   P('data')))(x)
    want = x.sum(axis=0, keepdims=True).repeat(8, 0)
    # three quantization stages, each ~|max|/127 -> few-percent tolerance
    tol = 0.05 * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < tol


def test_int8_compressor_training_converges(monkeypatch):
    """Multi-step linear regression through the DSL with the int8 wire:
    error feedback keeps SGD convergent to the true weights."""
    monkeypatch.setattr(Int8RingCompressor, 'MIN_SIZE', 1)
    autodist = ad.AutoDist(
        resource_info={'nodes': [{'address': 'localhost',
                                  'gpus': list(range(8)),
                                  'chief': True,
                                  'network_bandwidth': 100}]},
        strategy_builder=ad.AllReduce(compressor='Int8RingCompressor'))
    rng = np.random.RandomState(0)
    true_w = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
    xs = rng.randn(512, 4).astype(np.float32)
    ys = xs @ true_w

    with autodist.scope():
        W = ad.Variable(np.zeros(4, np.float32), name='W')
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        pred = ad.ops.squeeze(
            ad.ops.matmul(x, ad.ops.reshape(W, (4, 1))), axis=1)
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        train_op = ad.optimizers.SGD(0.05).minimize(loss)
        sess = autodist.create_distributed_session()

    losses = []
    for _ in range(40):
        l, _ = sess.run([loss, train_op], {x: xs, y: ys})
        losses.append(float(l))
    w_final = sess.run(W)
    assert losses[-1] < losses[0] * 0.05, losses[:3] + losses[-3:]
    assert np.allclose(w_final, true_w, atol=0.15), w_final
    # the residual state is live (per-replica error feedback)
    res = sess._aux_state['compressor/W']['residual']
    assert res.shape[-1] == 4


def test_int8_small_tensor_bypasses_quantization():
    """Below MIN_SIZE the compressor must reduce exactly (plain
    collective), preserving c0-style bit parity."""
    comp = Int8RingCompressor('v')
    grad = jnp.asarray([1.234567], jnp.float32)
    out = comp.reduce(grad, None, lambda g: g * 2.0)
    assert float(out[0]) == pytest.approx(2.469134, abs=1e-6)
    assert comp.init_state(np.zeros(3, 'f4')) == {}
