"""Pallas flash-attention kernel parity (interpret mode on the CPU mesh):
the full walk over every key block, and the static plan that lays it out.

Mirrors the reference's numeric-equivalence test style (SURVEY.md §4):
the kernel must match the straightforward jnp attention — forward and
gradients — for causal/full, odd block splits and every branch the plan
can take. The other paths of the kernels have a file each
(``tests/test_flash_*.py``, helpers in ``tests/flash_helpers.py``) so
that ``--dist loadfile`` gives each a worker.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.ring_attention import local_flash_attention
from flash_helpers import merge_heads, rand_qkv, split_heads


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('shape', [(2, 3, 128, 64), (1, 2, 96, 32)])
def test_forward_parity(causal, shape):
    rng = np.random.RandomState(0)
    q, k, v = rand_qkv(rng, shape)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = local_flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('causal', [True, False])
def test_gradient_parity(causal):
    rng = np.random.RandomState(1)
    q, k, v = rand_qkv(rng, (2, 2, 64, 32))

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
    q, k, v = rand_qkv(rng, (1, 1, 40, 16))
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
    q, k, v = rand_qkv(rng, (1, 1, 2048, 16))
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


@pytest.mark.parametrize('case', sorted(_PLAN_CASES))
def test_plan_branch_parity(case):
    """Forward and all three gradients of the kernels, run with an
    explicit plan on ``[b, s, heads * head_dim]`` operands, against the
    plain f32 attention."""
    h, s, d, causal, bq, bk, g = _PLAN_CASES[case]
    rng = np.random.RandomState(7)
    q, k, v = rand_qkv(rng, (2, h, s, d))
    w = jnp.asarray(rng.randn(2, h, s, d), jnp.float32)
    scale = d ** -0.5
    assert fa._is_pow2(scale) == (d in (16, 64))
    assert g % (fa._lane_block(h, d) // d) == 0
    blocks = fa.Blocks(bq, bk, g)
    plan = fa.Plan(blocks, blocks, blocks)

    def kernel(q, k, v):
        return split_heads(fa._flash((merge_heads(q), merge_heads(k), merge_heads(v)), None, h,
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
    q, k, v = rand_qkv(rng, (1, 2, 512, 16))

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
