"""Grouped kv heads and the causal band in the flash kernels (PR 33),
against the form with every kv head repeated to its group.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.ring_attention import local_flash_attention
from flash_helpers import merge_heads, rand_qkv, split_heads


def _repeated_head_form(q, k, v, h, kv, causal, window, theta=None):
    """Plain attention on ``[b, s, heads * d]`` operands with each kv
    head repeated for the query heads of its group."""
    from autodist_tpu.models.attention import rotary
    b, s, _ = q.shape
    d = q.shape[-1] // h
    qh, kh, vh = (split_heads(x, n) for x, n in ((q, h), (k, kv), (v, kv)))
    if theta is not None:
        qh, kh = (rotary(x, jnp.arange(s), theta) for x in (qh, kh))
    kh, vh = (jnp.repeat(x, h // kv, axis=1) for x in (kh, vh))
    return merge_heads(local_flash_attention(qh, kh, vh, causal=causal,
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
    q, k, v = rand_qkv(rng, (1, 2, 1024, 64))
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
