"""Rotary positions inside the flash kernels (PR 32): rotating the tile
is rotating q and k before the call, tables of another shape are
refused, and ``flash.plan`` says whether the kernels rotate.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa


# Rotary positions inside the kernels (PR 32): ([b, h, s, d], window,
# blocks or None for the plan's own, rotary base). The one-block path,
# the multi-block path (online softmax, accumulators in scratch), a
# band call on the tiled walk and one in the row form, each at 4, 2 and
# 1 heads to a lane block; two bases.
_ROTARY_CASES = {
    'one_block_d64': ((2, 2, 64, 64), None, None, 10000.0),
    'one_block_d32': ((1, 4, 64, 32), None, None, 160000.0),
    'one_block_d128': ((1, 1, 64, 128), None, None, 10000.0),
    'multi_block_d64': ((1, 4, 128, 64), None, (32, 64), 160000.0),
    'multi_block_d32': ((1, 4, 128, 32), None, (64, 32), 10000.0),
    'multi_block_d128': ((1, 2, 128, 128), None, (32, 32), 160000.0),
    'band_d64': ((1, 2, 128, 64), (16, 16), (32, 32), 10000.0),
    'band_d32': ((1, 4, 128, 32), (16, 16), (32, 64), 160000.0),
    'band_d128': ((1, 1, 128, 128), (12, 20), (64, 32), 10000.0),
    # the plan's own for a narrow band (PR 40): one pass over each row
    # block's own keys, the run's pieces rotated once each
    'row_d64': ((1, 2, 256, 64), (64, 64), None, 10000.0),
    'row_d32': ((1, 4, 256, 32), (16, 48), None, 160000.0),
    'row_d128': ((1, 1, 256, 128), (128, 128), None, 10000.0),
}


def _rotate_half(x, cos, sin, h):
    """``x cos + cat(-x2, x1) sin`` on each head of ``x [b, s, h * d]``
    with a head's tables ``[s, d]``: ``rotary()``'s arithmetic written
    with a split and a concatenate, rounded once."""
    b, s, hd = x.shape
    x4 = x.reshape(b, s, h, hd // h).astype(jnp.float32)
    x1, x2 = jnp.split(x4, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (x4 * cos[:, None] + turned * sin[:, None]).astype(
        x.dtype).reshape(b, s, hd)


@pytest.mark.parametrize('case', sorted(_ROTARY_CASES))
def test_rotary_on_the_tile_is_rotary_before_the_call(case):
    """``flash_attention_merged(qkv, h, rotary=tables)`` against the same
    call on ``(rotary(q), rotary(k), v)``: q and k are rotated on the
    tile as they are outside, dq and dk turned back inside the kernels.

    Bit for bit where the arithmetic allows it to be said: XLA's CPU
    backend contracts ``a * b + c * d`` into a fused multiply-add
    wherever it likes (here in the interpreted kernel, there in
    ``rotary``'s fusion), which moves the last bit of an f32 sum and
    now and then the bf16 it rounds to. With tables that are exact in
    bf16 every product of a bf16 operand is exact in f32, contraction
    changes nothing, and ``o`` and ``lse`` must be the same bits. With
    the real tables, against ``rotary()`` itself in f32, ``o`` and the
    gradient w.r.t. ``qkv`` agree to this file's tolerances."""
    from autodist_tpu.models.attention import rotary

    (b, h, s, d), window, blocks, theta = _ROTARY_CASES[case]
    block_q, block_k = blocks or (None, None)
    rng = np.random.RandomState(11)
    qkv = jnp.asarray(rng.randn(b, s, 3 * h * d), jnp.float32)
    w = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    pos = jnp.arange(s)
    tables = fa.rotary_tables(pos, theta, h, d)
    assert [t.shape for t in tables] == [(s, fa._lane_block(h, d))] * 2
    plan = fa._plan((b, h, s, d), False, block_q, block_k, window)
    multi = case.startswith(('multi', 'band'))
    if case.startswith('row'):
        assert all(isinstance(blocks, fa.Rows) for blocks in plan)
    else:
        assert all((s // bq > 1, s // bk > 1) == (multi, multi)
                   for bq, bk, _ in plan)

    # 1. bf16 operands and exact products: the bits of o and lse
    def forward(operands, tables):
        return fa._fwd(operands, tables, h, h, False, d ** -0.5, plan.fwd,
                       True, window)
    coarse = tuple(t.astype(jnp.bfloat16).astype(jnp.float32) for t in tables)
    head = tuple(t[:, :d] for t in coarse)
    q, k, v = jnp.split(qkv.astype(jnp.bfloat16), 3, axis=-1)
    o, lse = forward((qkv.astype(jnp.bfloat16),), coarse)
    o_out, lse_out = forward(
        (_rotate_half(q, *head, h), _rotate_half(k, *head, h), v), None)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(o.astype(jnp.float32)),
                                  np.asarray(o_out.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_out))

    # 2. f32, the real tables, rotary() before the call
    def o_and_grad(operands_of, tables):
        def loss(qkv):
            o = fa._planned(operands_of(qkv), tables, h, h, False, None,
                            block_q, block_k, True, window, named=True)
            return jnp.sum(o * w), o
        (_, o), g = jax.value_and_grad(loss, has_aux=True)(qkv)
        return o, g

    def outside(qkv):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (rotary(q, pos, theta, heads=h),
                rotary(k, pos, theta, heads=h), v)
    o, dqkv = o_and_grad(lambda qkv: (qkv,), tables)
    o_out, want = o_and_grad(outside, None)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_out),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(want),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('spoil', [
    lambda cos, sin: (cos[:64], sin[:64]),
    lambda cos, sin: (cos[:, :64], sin[:, :64]),
    lambda cos, sin: (cos.astype(jnp.bfloat16), sin),
], ids=['rows', 'lanes', 'dtype'])
def test_rotary_tables_of_another_shape_are_refused(spoil):
    qkv = jnp.zeros((1, 128, 3 * 128), jnp.bfloat16)
    bad = spoil(*fa.rotary_tables(jnp.arange(128), 1e4, 2, 64))
    with pytest.raises(ValueError, match='rotary'):
        fa.flash_attention_merged(qkv, 2, causal=False, rotary=bad)


def test_flash_plan_says_whether_the_kernels_rotate(monkeypatch):
    """Every kernel call of a ModernBERT-patterned model (rotary
    positions, window and global layers, unrolled and scanned) is given
    the tables, one pair a rotary base made once a trace; no call of
    the plain model is. ``flash.plan`` records which."""
    from autodist_tpu import telemetry
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)

    monkeypatch.setattr(fa, 'MIN_KERNEL_SEQ', 16)
    made = []
    real = fa.rotary_tables
    monkeypatch.setattr(fa, 'rotary_tables', lambda *a: (
        made.append(a[1]), real(*a))[1])
    batch = {name: jnp.zeros((2, 128), jnp.int32)
             for name in ('tokens', 'targets')}

    def plans(**kw):
        model = TransformerLM(TransformerConfig(
            vocab=64, dim=128, n_layers=7, n_heads=2, max_len=128,
            causal=False, remat=True, **kw))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        t_before = time.perf_counter()
        del made[:]
        jax.eval_shape(jax.grad(model.loss), params, batch)
        return [r['tags'] for r in telemetry.get().loop_records()
                if r['t0'] >= t_before and r['name'] == 'flash.plan']

    patterned = plans(positions='rotary', window=16, global_every=3,
                      embed_norm=True, rope_theta=160000.0,
                      window_rope_theta=10000.0)
    # layer 0 unrolled, then a period's (window, window, global) traced
    # once under the scan (the two window layers share one trace)
    assert [(p['rotary'], p['window']) for p in patterned] == [
        (True, None), (True, [16, 16]), (True, None)]
    assert sorted(made) == [10000.0, 160000.0]
    plain = plans()
    assert plain and not any(p['rotary'] for p in plain)
    assert not made
