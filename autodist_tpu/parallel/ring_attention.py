"""Ring attention: sequence/context-parallel exact attention.

The reference has no long-context support at all (SURVEY.md §5: absent);
this is the greenfield TPU-native subsystem. Design follows the public
ring-attention recipe (Liu et al., arXiv:2310.01889): the sequence axis is
sharded over the ``seq`` mesh axis; each device keeps its Q shard resident
and rotates K/V shards around the ring with ``ppermute`` while
accumulating the attention output with a numerically-stable online
softmax (flash-attention accumulation). Communication overlaps compute on
TPU because XLA's latency-hiding scheduler overlaps the ppermute DMA with
the per-block matmuls.

Runs inside ``shard_map``; the inner block math is pure jnp (XLA fuses
it into the ring schedule) so the same code executes on the CPU test
mesh. The single-device long-sequence path uses the Pallas flash kernel
instead (kernels/flash_attention.py via models/attention.py).
"""
import jax
import jax.numpy as jnp



def _block_attn(q, k, v, mask, sm_scale):
    """One (Q-shard x KV-block) flash-style partial: returns
    (unnormalized out, running max, running sum) contributions."""
    # q: [B, H, Sq, D], k/v: [B, H, Sk, D], mask: [Sq, Sk] additive
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = s + mask
    m = jnp.max(s, axis=-1)                       # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B,H,Sq]
    o = jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v)
    return o.astype(jnp.float32), m, l


def ring_attention(q, k, v, axis_name, causal=True, sm_scale=None):
    """Exact attention over a ring-sharded sequence axis.

    Args:
        q, k, v: [batch, heads, seq_shard, head_dim] local shards.
        axis_name: mesh axis carrying the sequence shards.
        causal: apply a causal mask using *global* positions.
        sm_scale: softmax scale (default 1/sqrt(head_dim)).

    Returns:
        [batch, heads, seq_shard, head_dim] local output shard.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_shard = q.shape[2]
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5

    q_pos = my * s_shard + jnp.arange(s_shard)

    def mask_for(kv_owner):
        if not causal:
            return None
        k_pos = kv_owner * s_shard + jnp.arange(s_shard)
        allowed = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(allowed, 0.0, -1e30).astype(jnp.float32)

    # Online-softmax accumulators.
    acc = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m_run = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    l_run = jnp.zeros(q.shape[:3], jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry, rotate):
        acc, m_run, l_run, k_cur, v_cur = carry
        owner = (my - step) % n  # whose KV block we hold after `step` hops
        o, m, l = _block_attn(q, k_cur, v_cur, mask_for(owner), sm_scale)
        m_new = jnp.maximum(m_run, m)
        alpha = jnp.exp(m_run - m_new)       # rescale old accumulator
        beta = jnp.exp(m - m_new)            # rescale new block
        acc = acc * alpha[..., None] + o * beta[..., None]
        l_run = l_run * alpha + l * beta
        m_run = m_new
        if rotate:  # the final hop would be idle; skip it
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m_run, l_run, k_cur, v_cur

    carry = (acc, m_run, l_run, k, v)
    # python loop: n is static and small; lets XLA pipeline the ring
    for step in range(n):
        carry = body(step, carry, rotate=step < n - 1)
    acc, m_run, l_run, _, _ = carry

    # Fully-masked rows (can't happen with causal self-attention because
    # position attends to itself) would produce l_run == 0; guard anyway.
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.astype(q.dtype)


def local_flash_attention(q, k, v, causal=True, sm_scale=None, window=None,
                          mask=None):
    """Single-device exact attention with the same accumulation; used as
    the non-SP fallback so numerics match ring_attention bit-for-bit-ish.

    ``window = (left, right)`` keeps keys ``i - left .. i + right`` for
    query ``i``; under ``causal`` the band is ``(left, 0)`` and holds
    the mask. A sequence several bands long is
    computed in query blocks against the keys each block's band reaches
    (:func:`_band_attention`), so no ``[s, s]`` score matrix exists; a
    short one under a dense mask. ``mask``: a boolean ``[queries, keys]``
    array of the pairs that are live, for a mask that is neither (the
    block-diffusion mask of a short sequence:
    ``flash_attention.block_diffusion_mask``); given with
    ``causal=False`` and no ``window``."""
    if mask is not None and (causal or window is not None):
        raise ValueError('local_flash_attention: a mask array is given '
                         'with causal=False and window=None')
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if window is not None:
        if causal:
            window, causal = (window[0], 0), False
        block = _band_block(q.shape[2], window)
        if block:
            return _band_attention(q, k, v, window, sm_scale, block)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    sq, sk = s.shape[-2], s.shape[-1]
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    elif window is not None:
        ahead = jnp.arange(sk)[None, :] - jnp.arange(sq)[:, None]
        s = jnp.where((ahead >= -window[0]) & (ahead <= window[1]), s, -1e30)
    elif mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v)


def _band_block(seq, window):
    """Query-block size of the blocked band, or None where the dense
    mask is as cheap: the sequence is under four bands long, or splits
    into no block of 8 rows or more."""
    left, right = window
    if seq <= 4 * (left + right + 1):
        return None
    block = 8
    while block < max(128, left, right) and seq % (2 * block) == 0:
        block *= 2
    return block if seq % block == 0 else None


def _band_attention(q, k, v, window, sm_scale, block):
    """Band attention in query blocks of ``block``: block ``n`` meets
    keys ``n * block - left .. (n + 1) * block + right`` (gathered from
    zero-padded k and v), so the scores are ``[b, h, s / block, block,
    block + left + right]``."""
    b, h, s, d = q.shape
    left, right = window
    n, width = s // block, block + left + right
    pad = ((0, 0), (0, 0), (left, right), (0, 0))
    at = jnp.arange(n)[:, None] * block + jnp.arange(width)[None, :]
    kb = jnp.pad(k, pad)[:, :, at]                    # [b, h, n, width, d]
    vb = jnp.pad(v, pad)[:, :, at]
    sc = jnp.einsum('bhnqd,bhnkd->bhnqk', q.reshape(b, h, n, block, d), kb,
                    preferred_element_type=jnp.float32) * sm_scale
    kpos = at[:, None, :] - left                      # [n, 1, width]
    qpos = (jnp.arange(n)[:, None] * block
            + jnp.arange(block)[None, :])[:, :, None]  # [n, block, 1]
    mask = ((kpos >= 0) & (kpos < s) & (kpos >= qpos - left)
            & (kpos <= qpos + right))
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    o = jnp.einsum('bhnqk,bhnkd->bhnqd', p.astype(v.dtype), vb)
    return o.reshape(b, h, s, d)
