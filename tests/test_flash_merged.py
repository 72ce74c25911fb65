"""The flash kernels on the merged layout ``[b, s, heads * head_dim]``:
the call the model makes is the public one with the heads merged, q, k
and v read out of one array are three separate operands, and the
checkpoint names keep the forward kernel out of the backward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.ring_attention import local_flash_attention
from flash_helpers import dense_band, merge_heads, rand_qkv, split_heads


# (causal, window) of the three kinds of call a cell makes
_CALL_KINDS = {'global': (False, None), 'causal': (True, None),
               'band': (False, (8, 8))}


@pytest.mark.parametrize('kind', sorted(_CALL_KINDS))
def test_merged_call_is_the_public_one_with_the_heads_merged(kind):
    """``flash_attention([b, h, s, d])`` is the merged call between two
    transposes: the same bits, forward and gradients."""
    causal, window = _CALL_KINDS[kind]
    rng = np.random.RandomState(5)
    q, k, v = rand_qkv(rng, (2, 4, 64, 16))
    w = jnp.asarray(rng.randn(2, 64, 64), jnp.float32)

    def public(q, k, v):
        return merge_heads(fa.flash_attention(q, k, v, causal=causal,
                                         window=window))

    def merged(q, k, v):
        return fa.flash_attention_merged(
            (merge_heads(q), merge_heads(k), merge_heads(v)), 4, causal=causal,
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
    q, k, v = (split_heads(x, h) for x in jnp.split(qkv, 3, axis=-1))
    want = local_flash_attention(q, k, v, causal=causal, window=window) \
        if window is None else dense_band(q, k, v, window)
    np.testing.assert_allclose(np.asarray(split_heads(packed(qkv), h)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)


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
