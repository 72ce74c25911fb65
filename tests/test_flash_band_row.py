"""The band kernels' row form (PR 40): a band that reaches no further
than a lane block to either side (ModernBERT's 64 keys each side) runs
``flash_fwd_band``, ``flash_dq_band`` and ``flash_dkv_band`` as ONE pass
over each row block's own keys: the block and a corner of each
neighbour, no inner grid dimension, no online softmax. Interpret mode on
the CPU against a dense masked softmax in f32, and the plan that says
which band takes which form.

A file of its own so that ``--dist loadfile`` gives it a worker:
``tests/test_flash_attention.py`` already sets tier-1's wall time.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.models.attention import rotary

THETA = 10000.0


def _heads(x, h):
    b, s, hd = x.shape
    return jnp.transpose(x.reshape(b, s, h, hd // h), (0, 2, 1, 3))


def _dense(qkv, h, window, rotated):
    """``(o [b, s, h * d], lse [b, h, 1, s])`` of plain attention under
    an explicit band mask, rotary positions put on q and k before it."""
    q, k, v = jnp.split(qkv, 3, axis=-1)
    b, s, _ = q.shape
    if rotated:
        q, k = (rotary(x, jnp.arange(s), THETA, heads=h) for x in (q, k))
    q, k, v = (_heads(x, h) for x in (q, k, v))
    ahead = jnp.arange(s)[None, :] - jnp.arange(s)[:, None]
    keep = (ahead >= -window[0]) & (ahead <= window[1])
    scores = jnp.where(keep, jnp.einsum('bhqd,bhkd->bhqk', q, k)
                       * q.shape[-1] ** -0.5, -jnp.inf)
    o = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, axis=-1), v)
    return (jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, -1),
            jax.nn.logsumexp(scores, axis=-1)[:, :, None, :])


# ([b, h, s, d], window, rotary, packed qkv, the row form's (sub-block,
# sub-blocks a step, heads a step) or None for the plan's own (the
# module's targets give LANES a step: heads x d here). Head
# dims of 64, 32 and 128 (two, four and one head to a lane block); a
# reach of half a lane block and of a whole one, one-sided and uneven
# bands; a sequence that is one step (both corners clamped at once),
# two steps (one end each) and many; steps of one and of several
# sub-blocks, of 128 and of 256 rows.
_CASES = {
    'd64-w64-rotary-packed-many': ((2, 2, 512, 64), (64, 64), True, True,
                                   (128, 1, 2)),
    'd64-w64-rotary-packed-sub_blocks': ((1, 2, 512, 64), (64, 64), True,
                                         True, (128, 2, 2)),
    'd64-w64-plain-three-two_steps': ((1, 2, 256, 64), (64, 64), False,
                                      False, (128, 1, 2)),
    'd64-w64-rotary-three-one_step': ((1, 2, 128, 64), (64, 64), True, False,
                                      None),
    'd64-w128-rotary-packed': ((1, 2, 384, 64), (128, 128), True, True,
                               (128, 1, 2)),
    'd64-w64_0-plain-packed': ((1, 2, 256, 64), (64, 0), False, True, None),
    'd64-w16_48-rotary-packed-sub256': ((1, 2, 512, 64), (16, 48), True,
                                        True, (256, 1, 2)),
    'd32-w64-plain-packed': ((1, 4, 256, 32), (64, 64), False, True, None),
    'd32-w16_48-rotary-three': ((2, 4, 256, 32), (16, 48), True, False,
                                (128, 1, 4)),
    'd32-w128-rotary-packed-heads': ((1, 8, 256, 32), (128, 128), True, True,
                                     (128, 2, 8)),
    'd128-w64-rotary-packed': ((1, 2, 256, 128), (64, 64), True, True,
                               (128, 1, 1)),
    'd128-w128-plain-three': ((1, 1, 384, 128), (128, 128), False, False,
                              None),
    'd128-w64_0-rotary-three-two_steps': ((1, 1, 256, 128), (64, 0), True,
                                          False, (128, 1, 1)),
}


@pytest.mark.parametrize('case', sorted(_CASES))
def test_row_form_matches_a_dense_masked_softmax(case, monkeypatch):
    """``o``, ``lse`` and the gradients w.r.t. q, k and v of the three
    kernels in the row form, first and last row blocks included."""
    (b, h, s, d), window, rotated, packed, targets = _CASES[case]
    if targets:
        monkeypatch.setattr(fa, '_ROW_TARGETS', dict.fromkeys(
            ('fwd', 'dq', 'dkv'), targets[:2] + (targets[2] * d,)))
    plan = fa._plan((b, h, s, d), False, window=window)
    assert all(isinstance(blocks, fa.Rows) for blocks in plan)
    assert plan.fwd.corner == (64 if max(window) <= 64 else 128)
    if targets:
        assert plan.fwd[:2] + plan.fwd[3:] == (
            min(s, targets[0] * targets[1]), targets[0], targets[2])
    rng = np.random.RandomState(11)
    qkv = jnp.asarray(rng.randn(b, s, 3 * h * d), jnp.float32)
    w = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    tables = fa.rotary_tables(jnp.arange(s), THETA, h, d) if rotated else None

    def operands(qkv):
        return (qkv,) if packed else tuple(jnp.split(qkv, 3, axis=-1))

    def kernel(qkv):
        return fa.flash_attention_merged(operands(qkv), h, causal=False,
                                         window=window, rotary=tables)

    def o_and_grad(attend):
        def loss(qkv):
            o = attend(qkv)
            return jnp.sum(o * w), o
        (_, o), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(qkv)
        return o, g

    want_o, want_lse = _dense(qkv, h, window, rotated)
    _, lse = jax.jit(lambda qkv: fa._fwd(
        operands(qkv), tables, h, h, False, d ** -0.5, plan.fwd, True,
        window))(qkv)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)
    o, dqkv = o_and_grad(kernel)
    _, want = o_and_grad(lambda qkv: _dense(qkv, h, window, rotated)[0])
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(jnp.split(dqkv, 3, axis=-1),
                              jnp.split(want, 3, axis=-1), ('dq', 'dk', 'dv')):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize('d,h', [(64, 2), (32, 4), (128, 1)])
def test_row_form_in_bf16_is_the_tiled_walk_to_its_rounding(d, h):
    """The same mathematics at the same precision: on bf16 operands with
    rotary positions the row form and the tiled walk (asked for by block
    sizes) give ``o`` and the gradient of ``qkv`` to a bf16's rounding;
    ``p`` and ``ds`` are rounded to bf16 before their products in both,
    the rotated q and k once."""
    b, s, window = 1, 256, (64, 64)
    rng = np.random.RandomState(5)
    qkv = jnp.asarray(rng.randn(b, s, 3 * h * d), jnp.bfloat16)
    w = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    tables = fa.rotary_tables(jnp.arange(s), THETA, h, d)

    def o_and_grad(block):
        def loss(qkv):
            o = fa._planned((qkv,), tables, h, h, False, None, block, block,
                            True, window, named=False)
            return jnp.sum(o.astype(jnp.float32) * w), o
        (_, o), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(qkv)
        return o, g
    t_before = time.perf_counter()
    row, tiles = o_and_grad(None), o_and_grad(128)
    forms = [r['tags']['band_form'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'flash.plan']
    assert forms == ['row', 'tiles']
    for got, want in zip(row, tiles):
        assert got.dtype == jnp.bfloat16
        got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
        assert np.max(np.abs(got - want)) <= 2 ** -7 * np.max(np.abs(want))


@pytest.mark.parametrize('window,kv_heads,asked,seq,form', [
    (None, 16, None, 8192, None),
    ((64, 64), 16, None, 8192, 'row'),        # ModernBERT's window layers
    ((128, 128), 16, None, 8192, 'row'),
    ((64, 0), 16, None, 8192, 'row'),
    ((129, 129), 16, None, 8192, 'tiles'),    # past a lane block
    ((129, 0), 16, None, 8192, 'tiles'),
    ((64, 64), 16, 128, 8192, 'tiles'),       # block sizes asked for
    ((64, 64), 16, None, 192, 'tiles'),       # no lane-wide blocks
], ids=['none', 'w64', 'w128', 'w64_0', 'w129', 'w129_0', 'asked', 's192'])
def test_the_form_follows_the_window(window, kv_heads, asked, seq, form):
    """The form is chosen from ``window`` (and what the row form has a
    body for) alone; a band the row form does not take is planned as the
    parent planned it."""
    shape = (4, 16, seq, 64)
    plan = fa._plan(shape, False, asked, asked, window, kv_heads)
    assert all(isinstance(blocks, fa.Rows) == (form == 'row')
               for blocks in plan)
    tags = fa._plan_tags(plan, seq, False, window)
    assert tags['band_form'] == form
    if form == 'row':
        for kernel, blocks in zip(('', 'dq_', 'dkv_'), plan):
            outer, inner = ('k', 'q') if kernel == 'dkv_' else ('q', 'k')
            assert tags[kernel + 'one_pass']
            assert tags[kernel + 'block_' + outer] == blocks.rows
            assert tags[kernel + 'block_' + inner] == (
                blocks.rows + 2 * blocks.corner)
            assert tags[kernel + 'tile_' + outer] == blocks.sub
            assert tags[kernel + 'tile_' + inner] == (
                blocks.sub + 2 * blocks.corner)
            assert tags[kernel + 'tiles'] == tags[kernel + 'live_tiles'] \
                == tags[kernel + 'masked_tiles'] == seq // blocks.sub
            assert tags[kernel + 'heads_per_step'] == blocks.heads_per_step


def test_mellum2s_band_takes_the_tiled_walk():
    """A causal window of 1024 keys over grouped kv heads of 128
    (Mellum2's window layers): the tiled walk at the caps PR 33 swept,
    and so would a narrow band over grouped heads be."""
    B = fa.Blocks
    shape = (4, 32, 8192, 128)
    plan = fa._plan(shape, False, window=(1023, 0), kv_heads=4)
    assert plan == fa.Plan(B(1024, 1024, 1), B(256, 256, 8), B(256, 256, 8))
    assert fa._plan_tags(plan, 8192, False, (1023, 0))['band_form'] == 'tiles'
    narrow = fa._plan(shape, False, window=(64, 0), kv_heads=4)
    assert not any(isinstance(blocks, fa.Rows) for blocks in narrow)
    # and the call says so: flash.plan of a trace
    t_before = time.perf_counter()
    jax.eval_shape(
        lambda qkv, cos, sin: fa.flash_attention_merged(
            qkv, 32, causal=True, window=(1023, 1023), rotary=(cos, sin),
            kv_heads=4),
        jax.ShapeDtypeStruct((1, 2048, 40 * 128), jnp.bfloat16),
        *[jax.ShapeDtypeStruct((2048, 128), jnp.float32)] * 2)
    tags = [r['tags'] for r in telemetry.get().loop_records()
            if r['t0'] >= t_before and r['name'] == 'flash.plan'][-1]
    assert (tags['window'], tags['band_form'], tags['one_pass']) == (
        [1023, 0], 'tiles', False)


def test_the_run_of_a_row_statistic_is_the_neighbours_corners():
    """``_stat_run``: the last lanes of the block before, the step's
    own, the first of the block after, with no slice off the lanes'
    grid."""
    from jax.experimental import pallas as pl
    pieces = [jnp.arange(n, dtype=jnp.float32)[None, None, None] + at
              for n, at in ((128, 1000.), (256, 0.), (128, 2000.))]
    for corner in (64, 128):
        def kernel(before, own, after, out):
            out[...] = fa._stat_run((before, own, after), 0, corner)
        got = pl.pallas_call(
            kernel, interpret=True, out_shape=jax.ShapeDtypeStruct(
                (1, 256 + 2 * corner), jnp.float32))(*pieces)
        want = np.concatenate([1000. + np.arange(128 - corner, 128),
                               np.arange(256), 2000. + np.arange(corner)])
        np.testing.assert_array_equal(np.asarray(got), want[None])
