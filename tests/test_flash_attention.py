"""Pallas flash-attention kernel parity (interpret mode on the CPU mesh).

Mirrors the reference's numeric-equivalence test style (SURVEY.md §4):
the kernel must match the straightforward jnp attention — forward and
gradients — for causal/full, odd block splits, and through the
MultiHeadAttention module's dispatch.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.ring_attention import local_flash_attention


def _rand_qkv(rng, shape, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('shape', [(2, 3, 128, 64), (1, 2, 96, 32)])
def test_forward_parity(causal, shape):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, shape)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = local_flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('causal', [True, False])
def test_gradient_parity(causal):
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, (2, 2, 64, 32))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    got = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(local_flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-4, rtol=5e-4)


def test_uneven_blocks_and_scale():
    # seq 40 -> blocks of 8; custom softmax scale must thread through
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, (1, 1, 40, 16))
    got = fa.flash_attention(q, k, v, causal=True, sm_scale=0.5)
    want = local_flash_attention(q, k, v, causal=True, sm_scale=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # backward at the smallest (8-row) blocks too
    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)
    g1 = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(local_flash_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_long_seq_asymmetric_blocks():
    """The production regime: seq >= MIN_KERNEL_SEQ picks asymmetric
    default blocks (bq=512, bk=1024) — partial causal tiles span
    multiple q-blocks per kv-block, a code shape short-seq tests miss."""
    assert fa._plan((1, 1, 2048, 16), True).fwd[:2] == (1024, 1024)
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, (1, 1, 2048, 16))
    got = fa.flash_attention(q, k, v, causal=True)
    want = local_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


# Every branch the static plan can take, one case each: (heads, seq,
# head_dim, causal, block_q, block_k, heads a step). head_dim 16 and 64
# have a power-of-two softmax scale (folded into q), 32 and 128 do not
# (128 ** -0.5 is no power of two). A step holds whole lane blocks of
# ``[b, s, heads * head_dim]``: two heads at 64, four at 32, one at 128,
# and every head where they are under 128 lanes in all.
_PLAN_CASES = {
    # nk == 1, one lane block (a pair of heads) a step, folded scale
    'one_pass-g2-d64': (2, 64, 64, False, 32, 64, 2),
    # nk == 1, the whole head count a step, scale on the tile
    'one_pass-gall-d32': (2, 64, 32, False, 32, 64, 2),
    # nk > 1: online softmax, scratch accumulators, 3 heads in 48 lanes
    'online-g3-d16': (3, 96, 16, False, 32, 32, 3),
    # nq == 1: flash_dkv writes straight from the tile, fwd/dq do not
    'dkv_one_pass-g2-d32': (2, 64, 32, False, 64, 32, 2),
    # causal, nq = 3: dead, diagonal-crossed and unmasked tiles in one
    # call; four heads of 32 in the 128 lanes of one block
    'causal-kinds-g4-d32': (4, 96, 32, True, 32, 32, 4),
    # causal, kv-block wider than the q-block, 12 heads at G = 6
    'causal-wide_k-g6-d64': (12, 128, 64, True, 32, 64, 6),
    # causal, q-block taller than the kv-block
    'causal-tall_q-g2-d64': (2, 128, 64, True, 64, 32, 2),
    # causal and nk == 1: every tile is live and crossed (static mask)
    'causal-one_pass-g2-d32': (2, 128, 32, True, 32, 128, 2),
    # causal and nq == 1: flash_dkv's live row (queries after the block)
    'causal-dkv_one_pass-g2-d32': (2, 128, 32, True, 128, 32, 2),
    # causal, one tile in all
    'causal-one_tile-g1-d16': (1, 64, 16, True, 64, 64, 1),
    # two and four lane blocks a step (G = 4, 8 at head_dim 64), one
    # pass and several inner blocks
    'one_pass-g4-d64': (4, 64, 64, False, 32, 64, 4),
    'online-g8-d64': (8, 64, 64, False, 32, 32, 8),
    'causal-online-g4-d64': (8, 96, 64, True, 32, 32, 4),
    # eight heads of 32 in two lane blocks, four of them a step
    'online-g4-d32': (8, 64, 32, False, 32, 32, 4),
    'causal-one_pass-g8-d32': (8, 64, 32, True, 32, 64, 8),
    # a head is a lane block: no head shares its lanes
    'one_pass-g1-d128': (2, 64, 128, False, 32, 64, 1),
    'causal-online-g2-d128': (2, 96, 128, True, 32, 32, 2),
    # an odd head count at head_dim 64 tiles no lane block: every head a
    # step, in one block as wide as the minor dimension
    'online-g3-d64': (3, 64, 64, False, 32, 32, 3),
}


def _merge(x):
    b, h, s, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)


def _split(x, h):
    b, s, hd = x.shape
    return jnp.transpose(x.reshape(b, s, h, hd // h), (0, 2, 1, 3))


@pytest.mark.parametrize('case', sorted(_PLAN_CASES))
def test_plan_branch_parity(case):
    """Forward and all three gradients of the kernels, run with an
    explicit plan on ``[b, s, heads * head_dim]`` operands, against the
    plain f32 attention."""
    h, s, d, causal, bq, bk, g = _PLAN_CASES[case]
    rng = np.random.RandomState(7)
    q, k, v = _rand_qkv(rng, (2, h, s, d))
    w = jnp.asarray(rng.randn(2, h, s, d), jnp.float32)
    scale = d ** -0.5
    assert fa._is_pow2(scale) == (d in (16, 64))
    assert g % (fa._lane_block(h, d) // d) == 0
    blocks = fa.Blocks(bq, bk, g)
    plan = fa.Plan(blocks, blocks, blocks)

    def kernel(q, k, v):
        return _split(fa._flash((_merge(q), _merge(k), _merge(v)), None, h,
                                h, causal, scale, plan, True), h)

    def plain(q, k, v):
        return local_flash_attention(q, k, v, causal=causal)

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))

    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(plain(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    (got_l, got), (want_l, want) = grads(kernel)(q, k, v), grads(plain)(q, k, v)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('heads,want', [(16, 8), (8, 8), (12, 6), (3, 3),
                                        (2, 2), (7, 7), (11, 1)])
def test_heads_per_step_divides_the_head_count(heads, want):
    assert fa._heads_per_step(heads, 32, 32) == want
    # a step never holds more score-tile elements than the budget
    g = fa._heads_per_step(heads, 512, 1024)
    assert heads % g == 0
    assert g == 1 or g * 512 * 1024 <= fa._STEP_TILE_ELEMS


@pytest.mark.parametrize('heads,d,lanes,small,large', [
    (16, 64, 128, 8, 2),     # pairs: never half a lane block
    (12, 64, 128, 6, 2), (16, 32, 128, 8, 4), (8, 32, 128, 8, 4),
    (16, 128, 128, 8, 1), (8, 256, 256, 8, 1),
    (4, 16, 64, 4, 4),       # under 128 lanes in all: one block
    (3, 64, 192, 3, 3),      # heads that tile no lane block: one block
])
def test_a_step_holds_whole_lane_blocks(heads, d, lanes, small, large):
    assert fa._lane_block(heads, d) == lanes
    per_block = lanes // d
    assert fa._heads_per_step(heads, 32, 32, per_block) == small
    # one lane block at least, whatever the tile budget says
    assert fa._heads_per_step(heads, 1024, 1024, per_block) == large


@pytest.mark.parametrize('seq,bq,bk', [(1024, 256, 512), (1024, 256, 256),
                                       (1024, 512, 1024), (96, 32, 32),
                                       (128, 64, 32), (4096, 512, 1024)])
def test_tile_counts_match_brute_force(seq, bq, bk):
    """Live = holds a position at or below the diagonal; masked = live
    and holds one above it. Counted position by position here."""
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    allowed = cols <= rows
    live = masked = 0
    for qi in range(seq // bq):
        for ki in range(seq // bk):
            t = allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            live += bool(t.any())
            masked += bool(t.any() and not t.all())
    tiles = (seq // bq) * (seq // bk)
    assert fa._tile_counts(seq, bq, bk, True) == (tiles, live, masked)
    assert fa._tile_counts(seq, bq, bk, False) == (tiles, tiles, 0)


@pytest.mark.parametrize('causal', [True, False])
def test_default_plan_parity_at_the_crossover(causal):
    """seq 512 with the blocks ``_plan`` picks itself: one tile a head,
    but for the causal ``flash_dq``, which walks the live key ranges of
    two q-blocks."""
    plan = fa._plan((1, 2, 512, 16), causal)
    assert [b[:2] for b in plan] == (
        [(512, 512), (256, 512), (512, 512)] if causal
        else [(512, 512)] * 3)
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng, (1, 2, 512, 16))

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))

    (got_l, got), (want_l, want) = (grads(fa.flash_attention)(q, k, v),
                                    grads(local_flash_attention)(q, k, v))
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_supports_and_preferred():
    assert fa.supports((1, 1, 128, 64))
    assert fa.supports((1, 1, 40, 64))      # divisible by 8
    assert not fa.supports((1, 1, 7, 64))   # not blockable
    assert not fa.preferred((1, 1, 128, 64))   # short seq: XLA wins
    assert fa.preferred((1, 1, 2048, 64))
    assert not fa.preferred((1, 1, 520, 64))   # no lane-wide blocks
    # heads that tile the lanes of [b, s, h * d] ...
    assert fa.supports((1, 16, 512, 64)) and fa.supports((1, 16, 512, 32))
    assert fa.supports((1, 8, 512, 128)) and fa.supports((1, 4, 512, 256))
    # ... or fit in one lane block
    assert fa.supports((1, 4, 512, 16)) and fa.supports((1, 1, 512, 64))
    # an odd head count at head_dim 64, a head_dim that divides no lane
    # block: XLA's
    assert not fa.supports((1, 3, 512, 64))
    assert not fa.preferred((1, 15, 2048, 64))
    assert not fa.supports((1, 4, 512, 96))


def _dense_band(q, k, v, window):
    """Plain attention under an explicit boolean band mask."""
    s = q.shape[2]
    ahead = jnp.arange(s)[None, :] - jnp.arange(s)[:, None]
    keep = (ahead >= -window[0]) & (ahead <= window[1])
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * q.shape[-1] ** -0.5
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(
        jnp.where(keep, scores, -jnp.inf), axis=-1), v)


# Band calls: (heads, seq, head_dim, window, block_q, block_k). Several
# blocks each, and a band whose edges cross block edges.
_BAND_CASES = {
    # three 32 x 32 tiles a row, every one crossed by an edge
    'square-w20': (2, 256, 16, (20, 20), 32, 32),
    # uneven reach, q-blocks twice the kv-blocks, scale on the tile
    'tall_q-w20_40': (2, 256, 32, (20, 40), 64, 32),
    # kv-blocks twice the q-blocks; the band spans whole tiles
    'wide_k-w64': (2, 256, 16, (64, 64), 32, 64),
    # a band wider than a block: interior tiles need no mask
    'interior-w70': (3, 256, 16, (70, 70), 32, 32),
    # a causal band (nothing ahead)
    'behind_only-w100_0': (2, 256, 16, (100, 0), 32, 32),
    # the default plan, ModernBERT's 64 each side: each kernel its own blocks
    'default-w64': (2, 512, 16, (64, 64), None, None),
    # the default plan where the sequence is one block (one pass, masked)
    'one_block-w8': (3, 128, 16, (8, 8), None, None),
}


@pytest.mark.parametrize('case', sorted(_BAND_CASES))
def test_band_kernels_match_a_dense_masked_softmax(case):
    """``flash_fwd_band``, ``flash_dq_band`` and ``flash_dkv_band`` in
    interpret mode: forward and all three gradients."""
    h, s, d, window, bq, bk = _BAND_CASES[case]
    rng = np.random.RandomState(11)
    q, k, v = _rand_qkv(rng, (2, h, s, d))
    w = jnp.asarray(rng.randn(2, h, s, d), jnp.float32)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=False, window=window,
                                  block_q=bq, block_k=bk)

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))

    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v)),
        np.asarray(_dense_band(q, k, v, window)), atol=2e-5, rtol=2e-5)
    (got_l, got), (want_l, want) = grads(kernel)(q, k, v), grads(
        lambda q, k, v: _dense_band(q, k, v, window))(q, k, v)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('seq,bq,bk,window', [
    (8192, 128, 128, (64, 64)), (256, 32, 32, (70, 70)),
    (256, 64, 32, (20, 40)), (256, 32, 64, (64, 64)), (512, 128, 256, (0, 0)),
])
def test_band_tile_counts_match_brute_force(seq, bq, bk, window):
    """The grid of a band call holds every tile with a pair of the band
    (counted position by position over the whole square) and few dead
    ones; masked = live and holding a pair outside the band."""
    ahead = np.arange(seq)[None, :] - np.arange(seq)[:, None]
    allowed = (ahead >= -window[0]) & (ahead <= window[1])
    live = masked = 0
    for qi in range(seq // bq):
        for ki in range(seq // bk):
            t = allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            live += bool(t.any())
            masked += bool(t.any() and not t.all())
    for transposed in (False, True):
        tiles, got_live, got_masked = fa._tile_counts(
            seq, bq, bk, False, window, transposed)
        assert (got_live, got_masked) == (live, masked)
        outer = seq // (bk if transposed else bq)
        assert live <= tiles == outer * fa._inner_blocks(
            seq, fa.Blocks(bq, bk, 1), window, transposed)
        # dead tiles only where the band leaves the sequence or a run
        # of blocks is one short of the longest
        assert tiles - live <= 2 * outer


def test_window_none_is_todays_plan_for_the_cells():
    """``bert-large.s512.*`` and ``gpt2-medium.s1024.c1``: without a
    window the plan is what PR 25 left (PERF.md §5), and the band
    plan is sized to the band, not to the sequence."""
    B = fa.Blocks
    assert fa._plan((96, 16, 512, 64), False) == fa.Plan(
        B(512, 512, 4), B(512, 512, 4), B(512, 512, 4))
    assert fa._plan((32, 16, 1024, 64), True) == fa.Plan(
        B(512, 1024, 2), B(256, 1024, 4), B(1024, 512, 2))
    # the forward's step holds a pair of heads since PR 29: a lane block
    assert fa._plan((4, 16, 8192, 64), False) == fa.Plan(
        B(1024, 1024, 2), B(512, 512, 4), B(512, 512, 4))
    # ModernBERT's window layers: the row form since PR 40 (rows of a
    # step, of a sub-block, of a neighbour's corner, heads a step), and
    # the tiled walk's blocks where block sizes are asked for
    R = fa.Rows
    assert fa._plan((4, 16, 8192, 64), False, window=(64, 64)) == fa.Plan(
        R(512, 128, 64, 4), R(1024, 256, 64, 2), R(256, 128, 64, 8))
    assert fa._block_targets(8192, False, (64, 64)) == {
        'fwd': (128, 512), 'dq': (256, 256), 'dkv': (128, 256)}
    # a wide band's tiles stop at their kernel's cap, swept on the chip
    # at Mellum2's causal window of 1024 keys (PR 33): the forward's at
    # 1024 a side, the backward kernels' at 256
    assert fa._plan((4, 16, 8192, 64), False, window=(300, 10)).dq[:2] \
        == (256, 256)
    assert fa._plan((4, 32, 8192, 128), False, window=(1023, 0),
                    kv_heads=4) == fa.Plan(
        B(1024, 1024, 1), B(256, 256, 8), B(256, 256, 8))
    # grouped kv heads: a step's query heads divide a group of 8
    assert fa._plan((4, 32, 8192, 128), True, kv_heads=4) == fa.Plan(
        B(1024, 1024, 1), B(512, 512, 4), B(512, 512, 4))


def test_window_under_a_causal_mask_is_the_causal_band():
    """``window=(left, right)`` under ``causal=True`` is the band
    ``(left, 0)``, in the kernels and on the XLA path."""
    assert fa.check_window((8, 8), causal=True) == (8, 0)
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng, (1, 2, 64, 16))
    want = local_flash_attention(q, k, v, causal=False, window=(8, 0))
    for got in (fa.flash_attention(q, k, v, causal=True, window=(8, 8)),
                local_flash_attention(q, k, v, causal=True, window=(8, 8))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match='neither negative'):
        fa.flash_attention(q, q, q, causal=False, window=(-1, 8))
    assert fa.supports((1, 1, 128, 64), window=(8, 8))
    assert fa.preferred((4, 16, 8192, 64), window=(64, 64))
    assert not fa.preferred((1, 1, 128, 64), window=(8, 8))


# (causal, window) of the three kinds of call a cell makes
_CALL_KINDS = {'global': (False, None), 'causal': (True, None),
               'band': (False, (8, 8))}


@pytest.mark.parametrize('kind', sorted(_CALL_KINDS))
def test_merged_call_is_the_public_one_with_the_heads_merged(kind):
    """``flash_attention([b, h, s, d])`` is the merged call between two
    transposes: the same bits, forward and gradients."""
    causal, window = _CALL_KINDS[kind]
    rng = np.random.RandomState(5)
    q, k, v = _rand_qkv(rng, (2, 4, 64, 16))
    w = jnp.asarray(rng.randn(2, 64, 64), jnp.float32)

    def public(q, k, v):
        return _merge(fa.flash_attention(q, k, v, causal=causal,
                                         window=window))

    def merged(q, k, v):
        return fa.flash_attention_merged(
            (_merge(q), _merge(k), _merge(v)), 4, causal=causal,
            window=window)
    np.testing.assert_array_equal(np.asarray(merged(q, k, v)),
                                  np.asarray(public(q, k, v)))
    got = jax.grad(lambda *a: jnp.sum(merged(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(public(*a) * w), (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    assert fa.saved_bytes(q.shape, q.dtype) == 2 * 4 * 64 * (16 * 4 + 4)


# (heads, head_dim): pairs and fours of heads in a lane block, a head
# that is one; with 128 lanes or more in all, q, k and v can be read
# out of one array
_PACKED = {'d64': (4, 64), 'd32': (8, 32), 'd128': (2, 128)}


@pytest.mark.parametrize('kind', sorted(_CALL_KINDS))
@pytest.mark.parametrize('heads', sorted(_PACKED))
def test_qkv_read_from_one_array_is_three_separate_operands(heads, kind):
    """The projection's output as ONE operand, q, k and v three runs of
    its columns, against the three as arrays of their own: the same
    bits, and the cotangent is the three gradients side by side."""
    h, d = _PACKED[heads]
    causal, window = _CALL_KINDS[kind]
    rng = np.random.RandomState(9)
    qkv = jnp.asarray(rng.randn(2, 64, 3 * h * d), jnp.float32)
    w = jnp.asarray(rng.randn(2, 64, h * d), jnp.float32)

    def packed(qkv):
        return fa.flash_attention_merged(qkv, h, causal=causal,
                                         window=window)

    def separate(qkv):
        return fa.flash_attention_merged(
            tuple(jnp.split(qkv, 3, axis=-1)), h, causal=causal,
            window=window)
    np.testing.assert_array_equal(np.asarray(packed(qkv)),
                                  np.asarray(separate(qkv)))
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda x: jnp.sum(packed(x) * w))(qkv)),
        np.asarray(jax.grad(lambda x: jnp.sum(separate(x) * w))(qkv)))
    # and they are the plain attention's
    q, k, v = (_split(x, h) for x in jnp.split(qkv, 3, axis=-1))
    want = local_flash_attention(q, k, v, causal=causal, window=window) \
        if window is None else _dense_band(q, k, v, window)
    np.testing.assert_allclose(np.asarray(_split(packed(qkv), h)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


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


def test_rotary_tables_of_another_shape_are_refused():
    qkv = jnp.zeros((1, 128, 3 * 128), jnp.bfloat16)
    cos, sin = fa.rotary_tables(jnp.arange(128), 1e4, 2, 64)
    for bad in ((cos[:64], sin[:64]), (cos[:, :64], sin[:, :64]),
                (cos.astype(jnp.bfloat16), sin)):
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


def test_heads_that_tile_no_lane_block_take_the_xla_path(monkeypatch):
    """Three heads of 64: ``supports`` is False, and the module never
    reaches the kernels, whatever the sequence."""
    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.models.attention import MultiHeadAttention

    def never(*a, **kw):
        raise AssertionError('kernel path taken')

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', never)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    mha = MultiHeadAttention(192, 3, causal=False)
    assert mha.kernel_shape((2, 3, 32, 64)) is None
    assert MultiHeadAttention(256, 4).kernel_shape((2, 4, 32, 64)) \
        == (2, 4, 32, 64)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 192), jnp.float32)
    assert mha.apply(mha.init(jax.random.PRNGKey(0)), x).shape == x.shape


@pytest.mark.parametrize('kind', sorted(_CALL_KINDS))
def test_checkpoint_policy_keeps_the_forward_kernel_out_of_the_backward(
        kind, kernel_calls):
    """Three scanned blocks (projection, kernel, projection) under
    ``jax.checkpoint``: with the policy that saves what the merged call
    names, the gradient runs the forward kernel once a layer, without
    it twice, and gives the same bits either way."""
    causal, window = _CALL_KINDS[kind]
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 64, 64), jnp.float32)
    ws = jnp.asarray(rng.randn(3, 64, 4 * 64) * 0.1, jnp.float32)

    def block(h, w):
        o = fa.flash_attention_merged(h @ w[:, :192], 4, causal=causal,
                                      window=window)
        return h + o @ w[:, 192:], None

    def loss(policy):
        fn = jax.checkpoint(block, policy=policy)
        return lambda x, ws: jnp.sum(jax.lax.scan(fn, x, ws)[0] ** 2)

    keep = jax.checkpoint_policies.save_only_these_names(
        *fa.CHECKPOINT_NAMES)
    band = '_band' if window else ''
    names = ['flash_fwd' + band, 'flash_dq' + band, 'flash_dkv' + band]
    calls = {policy: kernel_calls(jax.make_jaxpr(jax.grad(
        loss(policy), (0, 1)))(x, ws)) for policy in (keep, None)}
    assert calls[keep] == dict.fromkeys(names, 3)
    assert calls[None] == dict(dict.fromkeys(names, 3),
                               **{names[0]: 6})
    for got, want in zip(jax.grad(loss(keep), (0, 1))(x, ws),
                         jax.grad(loss(None), (0, 1))(x, ws)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_mesh_dispatches_via_nested_manual(monkeypatch):
    """Under a dp/tp GSPMD mesh the module hops into a nested shard_map
    so the kernel runs on local shards — and the numbers still match the
    pure-DP run."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}

    def losses(spec):
        tr = Trainer(model, optax.adam(1e-2), spec=spec)
        state = tr.init(jax.random.PRNGKey(0))
        out = []
        for _ in range(2):
            state, m = tr.step(state, batch)
            out.append(float(m['loss']))
        return out

    tp_losses = losses(ParallelSpec(tp=2))
    assert calls['n'] > 0, 'nested-manual kernel path not taken'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10**9)
    dp_losses = losses(ParallelSpec())
    np.testing.assert_allclose(tp_losses, dp_losses, atol=3e-4)


def test_flash_parity_on_dp8_gspmd_mesh_long_seq(monkeypatch):
    """dp=8 GSPMD mesh at seq 2048 (the real crossover regime,
    MIN_KERNEL_SEQ untouched): the nested-manual flash path engages and
    matches the jnp attention path numerically (interpret mode)."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    cfg = TransformerConfig(vocab=64, dim=32, n_layers=1, n_heads=2,
                            max_len=2048, dtype=jnp.float32,
                            scan_layers=False)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 64, (8, 2048)),
             'targets': rng.randint(0, 64, (8, 2048))}

    def one_loss():
        tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(dp=8))
        state = tr.init(jax.random.PRNGKey(0))
        _, m = tr.step(state, batch)
        return float(m['loss'])

    flash_loss = one_loss()
    assert calls['n'] > 0, 'nested-manual kernel path not taken'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10 ** 9)
    jnp_loss = one_loss()
    np.testing.assert_allclose(flash_loss, jnp_loss, rtol=2e-4)


def test_flash_dispatch_with_extra_live_mesh_axes(monkeypatch):
    """A live size>1 mesh axis beyond data/heads (here: expert) no
    longer drops long-seq attention to the jnp path (round-2 weak item):
    the nested-manual region runs over data+heads, leaves the extra axis
    untouched, and numbers match the pure-DP run."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}

    def losses(spec):
        tr = Trainer(model, optax.adam(1e-2), spec=spec)
        state = tr.init(jax.random.PRNGKey(0))
        out = []
        for _ in range(2):
            state, m = tr.step(state, batch)
            out.append(float(m['loss']))
        return out

    mixed = losses(ParallelSpec(dp=2, tp=2, ep=2))
    assert calls['n'] > 0, \
        'kernel path must engage despite the live expert axis'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10 ** 9)
    dp_losses = losses(ParallelSpec())
    np.testing.assert_allclose(mixed, dp_losses, atol=3e-4)


def test_module_dispatches_to_kernel(monkeypatch):
    """MultiHeadAttention routes to the kernel exactly when execution is
    device-local and the shape clears the crossover."""
    from autodist_tpu.models.attention import MultiHeadAttention

    calls = {}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['hit'] = True
        return real(*a, **kw)

    import autodist_tpu.models.attention as attn_mod
    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    mha = MultiHeadAttention(32, 2)
    params = mha.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 32), jnp.float32)
    out = mha.apply(params, x)
    assert out.shape == (2, 32, 32)
    assert calls.get('hit'), 'kernel path not taken for local execution'


# ---------------------------------------------------------------------------
# grouped kv heads and the causal band (PR 33)
# ---------------------------------------------------------------------------

def _repeated_head_form(q, k, v, h, kv, causal, window, theta=None):
    """Plain attention on ``[b, s, heads * d]`` operands with each kv
    head repeated for the query heads of its group."""
    from autodist_tpu.models.attention import rotary
    b, s, _ = q.shape
    d = q.shape[-1] // h
    qh, kh, vh = (_split(x, n) for x, n in ((q, h), (k, kv), (v, kv)))
    if theta is not None:
        qh, kh = (rotary(x, jnp.arange(s), theta) for x in (qh, kh))
    kh, vh = (jnp.repeat(x, h // kv, axis=1) for x in (kh, vh))
    return _merge(local_flash_attention(qh, kh, vh, causal=causal,
                                        window=window))


# (heads, kv heads, seq, head_dim, causal, window, rotary, packed): the
# one-pass and the multi-block causal paths, a step that holds a whole
# group and one that holds part of it, the causal band (w - 1, 0) on one
# block and on several, one array or three
_GQA_CASES = {
    'one_pass_causal': (4, 2, 256, 128, True, None, False, False),
    'one_pass_rotary_packed': (4, 2, 256, 128, True, None, True, True),
    'multi_block_causal': (8, 2, 2048, 128, True, None, True, True),
    'band_1_kv_head': (4, 1, 1024, 128, True, (255, 0), True, True),
    'band_wide': (4, 2, 1024, 128, True, (1023, 7), False, False),
    'not_causal_d256': (2, 1, 512, 256, False, None, False, True),
}


@pytest.mark.parametrize('case', sorted(_GQA_CASES))
def test_grouped_kv_heads_match_the_repeated_head_form(case):
    """Forward and the gradients of q, k and v: ``flash_dkv`` adds a kv
    head's dk and dv up over its group inside the kernel, where
    ``jax.grad`` of the repeated-head form sums the copies."""
    from autodist_tpu.models.attention import rope_frequencies
    h, kv, s, d, causal, window, rot, packed = _GQA_CASES[case]
    rng = np.random.RandomState(0)
    q, k, v, w = (jnp.asarray(rng.randn(1, s, n * d), jnp.float32)
                  for n in (h, kv, kv, h))
    theta = rope_frequencies(500000.0, d, dict(
        factor=16.0, original_max_position_embeddings=64, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.25)) if rot else None
    tables = fa.rotary_tables(jnp.arange(s), theta, h, d) if rot else None

    def kernel(q, k, v):
        operands = (jnp.concatenate([q, k, v], -1),) if packed else (q, k, v)
        o = fa.flash_attention_merged(operands, h, causal=causal,
                                      window=window, rotary=tables,
                                      kv_heads=kv, interpret=True)
        return jnp.sum(o * w), o

    def plain(q, k, v):
        o = _repeated_head_form(q, k, v, h, kv, causal, window, theta)
        return jnp.sum(o * w), o
    (_, got_o), got = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want_o), want = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(
        q, k, v)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize('w', [64, 256, 1024])
def test_causal_band_matches_a_masked_softmax(w):
    """The band ``(w - 1, 0)``: query i sees keys j with ``0 <= i - j <
    w``, against a softmax under that mask written out."""
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, (1, 2, 1024, 64))
    back = np.arange(1024)[:, None] - np.arange(1024)[None, :]
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / 8.0
    scores = jnp.where((back >= 0) & (back < w), scores, -jnp.inf)
    want = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, -1), v)
    got = fa.flash_attention(q, k, v, causal=True, window=(w - 1, w - 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_grouped_kv_heads_need_a_head_to_be_a_lane_block():
    assert fa.supports((4, 32, 8192, 128), kv_heads=4)
    assert fa.preferred((4, 32, 8192, 128), (1023, 0), kv_heads=4)
    assert not fa.supports((4, 16, 8192, 64), kv_heads=4)
    assert not fa.supports((4, 6, 8192, 128), kv_heads=4)
    assert fa.supports((4, 16, 8192, 64), kv_heads=16)
    q = jnp.zeros((1, 64, 4 * 64))
    kv = jnp.zeros((1, 64, 2 * 64))
    with pytest.raises(ValueError, match='need a head to be a lane block'):
        fa.flash_attention_merged((q, kv, kv), 4, kv_heads=2)


# -- latent attention: a q/k head in two parts, one rotary key ------------

def _latent_reference(qp, kn, v, kr, dims, theta, causal):
    """Plain jnp: q ``[b, s, h, nope | rope]`` (head-major, the published
    order), k_nope and v ``[b, s, h, .]``, the one rotary key ``[b, s,
    rope]``; rotate-half on the rope parts, the sum of the two
    contractions over ``sqrt(nope + rope)``, softmax, ``[b, s, h * v]``."""
    nope, rope, dv = dims
    b, s, h, _ = qp.shape
    cos, sin = fa.rotary_angles(jnp.arange(s), theta, rope)
    cos, sin = (jnp.concatenate([t, t], -1) for t in (cos, sin))

    def turned(x):                              # [..., s, rope]
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin
    q_rope = turned(qp[..., nope:].transpose(0, 2, 1, 3))
    scores = (jnp.einsum('bqhd,bkhd->bhqk', qp[..., :nope], kn)
              + jnp.einsum('bhqd,bkd->bhqk', q_rope, turned(kr))) \
        * (nope + rope) ** -0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1),
                      v).reshape(b, s, h * dv)


# (batch, seq, heads, (nope, rope, v), causal, block_q, block_k, columns
# of c): several tiles a side with dead, crossed and whole ones under the
# mask; rectangular tiles both ways; two and four heads to a rope lane
# block; a nope part of two lane blocks beside a v head of one; one block
# a side at the plan's own blocks; the key beside latents of several
# widths (the model's 512 among them).
_LATENT_CASES = {
    'causal_tiles': (2, 256, 4, (128, 64, 128), True, 64, 64, 192),
    'full_wide_q_tiles': (1, 256, 4, (128, 64, 128), False, 128, 64, 192),
    'causal_wide_k_tiles': (1, 256, 2, (128, 64, 128), True, 64, 128, 576),
    'four_heads_a_lane_block': (1, 128, 8, (128, 32, 128), True, 64, 64,
                                160),
    'nope_wider_than_v': (1, 128, 2, (256, 64, 128), True, 64, 64, 192),
    'one_block_the_plans_own': (1, 128, 2, (128, 64, 128), True, None, None,
                                128),
}


@pytest.mark.parametrize('case', sorted(_LATENT_CASES))
def test_latent_kernels_match_plain_attention(case):
    """``flash_attention_latent`` (interpret mode) against plain jnp,
    forward and every gradient: dq in the kernels' column order, dk_nope
    and dv as the two runs of one array, and the ONE rotary key's
    gradient, summed over all the heads inside ``flash_dkv_mla`` and
    zero beside the key's columns of ``c``."""
    b, s, h, dims, causal, bq, bk, wc = _LATENT_CASES[case]
    nope, rope, dv = dims
    rng = np.random.RandomState(3)
    qp = jnp.asarray(rng.randn(b, s, h, nope + rope), jnp.float32)
    kn = jnp.asarray(rng.randn(b, s, h, nope), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, dv), jnp.float32)
    c = jnp.asarray(rng.randn(b, s, wc), jnp.float32)
    do = jnp.asarray(rng.randn(b, s, h * dv), jnp.float32)
    theta = 1e6
    cols = jnp.asarray(fa.latent_columns(h, dims))
    assert sorted(np.asarray(cols)) == list(range(h * (nope + rope)))
    tables = fa.rotary_tables(jnp.arange(s), theta, h, rope)

    def kernel(qp, kn, v, c):
        kv = jnp.concatenate([kn.reshape(b, s, -1), v.reshape(b, s, -1)], -1)
        return fa.flash_attention_latent(
            qp.reshape(b, s, -1)[..., cols], kv, c, h, dims, tables,
            causal=causal, block_q=bq, block_k=bk)

    def plain(qp, kn, v, c):
        return _latent_reference(qp, kn, v, c[..., :rope], dims, theta,
                                 causal)
    with jax.default_matmul_precision('highest'):
        got, got_vjp = jax.vjp(kernel, qp, kn, v, c)
        want, want_vjp = jax.vjp(plain, qp, kn, v, c)
        got_grads, want_grads = got_vjp(do), want_vjp(do)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=5e-5, rtol=5e-5)
    # the key's gradient adds up over every head and is not nothing
    assert float(jnp.max(jnp.abs(got_grads[3][..., :rope]))) > 0.1
    assert not np.any(np.asarray(got_grads[3][..., rope:]))


def test_latent_call_leaves_its_plan_and_names_its_kernels():
    from autodist_tpu import telemetry
    b, s, h, dims = 1, 128, 2, (128, 64, 128)
    q = jnp.zeros((b, s, h * 192), jnp.float32)
    kv = jnp.zeros((b, s, h * 256), jnp.float32)
    c = jnp.zeros((b, s, 192), jnp.float32)
    tables = fa.rotary_tables(jnp.arange(s), 1e6, h, 64)

    def loss(q, kv, c):
        return jnp.sum(fa.flash_attention_latent(q, kv, c, h, dims, tables))
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, c).as_text(
        debug_info=True)
    for name in ('flash_fwd_mla', 'flash_dq_mla', 'flash_dkv_mla'):
        assert name in text
    plan = [r['tags'] for r in telemetry.get().loop_records()
            if r['name'] == 'flash.plan'][-1]
    assert (plan['qk_dim'], plan['v_dim'], plan['rope_dim'],
            plan['shared_rope_key'], plan['head_dim'], plan['rotary'],
            plan['heads_per_lane_block']) == (192, 128, 64, True, 192, True,
                                              2)
    # o in the v head's width and the f32 lse: what a call keeps
    assert fa.saved_bytes((4, 32, 8192, 192), jnp.bfloat16, 128) \
        == 4 * 32 * 8192 * (128 * 2 + 4)
    assert fa.saved_bytes((4, 32, 8192, 128), jnp.bfloat16) \
        == fa.saved_bytes((4, 32, 8192, 192), jnp.bfloat16, 128)


def test_supports_and_preferred_latent():
    model = (128, 64, 128)
    assert fa.supports_latent((4, 32, 8192, 192), model)
    assert fa.preferred_latent((4, 32, 8192, 192), model)
    assert not fa.preferred_latent((4, 32, 256, 192), model)    # XLA's
    assert fa.supports_latent((1, 8, 512, 160), (128, 32, 128))
    assert fa.supports_latent((1, 2, 512, 320), (256, 64, 128))
    # an odd head count at two heads to a lane block, parts that tile no
    # lane block: XLA's
    assert not fa.supports_latent((1, 3, 512, 192), model)
    assert not fa.supports_latent((1, 4, 512, 12), (8, 4, 6))
    assert not fa.supports_latent((1, 4, 512, 160), (96, 64, 128))
    assert not fa.supports_latent((1, 4, 512, 192), (128, 64, 192))
    assert fa.latent_group(32, model) == 2 and fa.latent_group(
        4, (8, 4, 6)) == 4
    # the kernels' order of q: a lane block's heads' nope parts, then
    # their rope parts
    assert fa.latent_columns(2, (2, 1, 2)) == [0, 1, 3, 4, 2, 5]
    with pytest.raises(ValueError, match='supports_latent'):
        fa.flash_attention_latent(
            jnp.zeros((1, 128, 3 * 192)), jnp.zeros((1, 128, 3 * 256)),
            jnp.zeros((1, 128, 192)), 3, model,
            fa.rotary_tables(jnp.arange(128), 1e6, 2, 64))
