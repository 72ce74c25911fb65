"""The flash kernels' tiled band (``window=``): only the tiles that hold
a pair of the band are walked, against a dense masked softmax, and the
grid's tile counts against a count position by position. The row form
of a narrow band is ``tests/test_flash_band_row.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.ring_attention import local_flash_attention
from flash_helpers import dense_band, rand_qkv


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
    q, k, v = rand_qkv(rng, (2, h, s, d))
    w = jnp.asarray(rng.randn(2, h, s, d), jnp.float32)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=False, window=window,
                                  block_q=bq, block_k=bk)

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))

    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v)),
        np.asarray(dense_band(q, k, v, window)), atol=2e-5, rtol=2e-5)
    (got_l, got), (want_l, want) = grads(kernel)(q, k, v), grads(
        lambda q, k, v: dense_band(q, k, v, window))(q, k, v)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('transposed', [False, True])
@pytest.mark.parametrize('seq,bq,bk,window', [
    (8192, 128, 128, (64, 64)), (256, 32, 32, (70, 70)),
    (256, 64, 32, (20, 40)), (256, 32, 64, (64, 64)), (512, 128, 256, (0, 0)),
])
def test_band_tile_counts_match_brute_force(seq, bq, bk, window, transposed):
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
    tiles, got_live, got_masked = fa._tile_counts(
        seq, bq, bk, False, window, transposed)
    assert (got_live, got_masked) == (live, masked)
    outer = seq // (bk if transposed else bq)
    assert live <= tiles == outer * fa._inner_blocks(
        seq, fa.Blocks(bq, bk, 1), window, transposed)
    # dead tiles only where the band leaves the sequence or a run of
    # blocks is one short of the longest
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
    q, k, v = rand_qkv(rng, (1, 2, 64, 16))
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
