"""The latent-attention flash kernels (PR 39): a q/k head in two parts,
one rotary key shared by the heads, against plain jnp attention.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import flash_attention as fa


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
