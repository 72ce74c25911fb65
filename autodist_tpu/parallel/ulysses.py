"""Ulysses sequence parallelism: all-to-all head/sequence transposition.

The second of the two public long-context recipes (DeepSpeed-Ulysses,
arXiv:2309.14509; the reference has neither — SURVEY.md §5 long-context:
absent). Where ring attention (parallel/ring_attention.py) keeps Q local
and rotates K/V around the ``seq`` mesh axis, Ulysses transposes the
sharding instead: one ``all_to_all`` re-shards activations from
sequence-sharded/full-heads to head-sharded/full-sequence, runs ordinary
*local* attention per head group (which composes with the Pallas flash
kernel, since the whole sequence is device-local), and a second
``all_to_all`` transposes back.

Trade-off vs ring: 2 all-to-alls of activation size per layer (cheap on
ICI) instead of n-1 K/V hops, but heads must divide the ``seq`` axis so
it caps at n <= n_heads; ring has no such cap. Select per-step with
``ParallelSpec(sp_mode='ulysses')``.
"""
import jax

from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.parallel.axes import unsharded_execution
from autodist_tpu.parallel.ring_attention import local_flash_attention


def _local_attn(q, k, v, causal, sm_scale):
    if unsharded_execution() and fa.preferred(q.shape):
        return fa.flash_attention(q, k, v, causal=causal,
                                  sm_scale=sm_scale)
    return local_flash_attention(q, k, v, causal=causal,
                                 sm_scale=sm_scale)


def ulysses_attention(q, k, v, axis_name, causal=True, sm_scale=None):
    """Exact attention over a sequence-sharded axis via all-to-all.

    Args:
        q, k, v: [batch, heads, seq_shard, head_dim] local shards with
            the FULL head dimension (sequence sharded over ``axis_name``).
        axis_name: mesh axis carrying the sequence shards.
        causal: standard causal mask (positions are global after the
            transposition — no offset bookkeeping needed).
        sm_scale: softmax scale (default 1/sqrt(head_dim)).

    Returns:
        [batch, heads, seq_shard, head_dim] local output shard.
    """
    n = jax.lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % n != 0:
        raise ValueError(
            'ulysses sp_mode needs heads %% sp == 0 (heads=%d, sp=%d); '
            'use sp_mode="ring" for this config' % (heads, n))
    if n == 1:
        return _local_attn(q, k, v, causal, sm_scale)

    def to_heads(x):   # [b, h, s/n, d] -> [b, h/n, s, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    o = _local_attn(q, k, v, causal, sm_scale)
    # [b, h/n, s, d] -> [b, h, s/n, d]
    return jax.lax.all_to_all(o, axis_name, split_axis=2,
                              concat_axis=1, tiled=True)
