"""Pallas TPU flash attention: block-tiled exact attention, fwd + bwd.

The reference has no attention kernels at all (its models are TF graphs;
SURVEY.md §2.3 lists no TP/SP) — this is TPU-native greenfield, the block
primitive promised by parallel/ring_attention.py. Algorithm is the public
flash-attention-2 recipe: the score matrix is never materialized in HBM;
each (Q-block × KV-block) tile runs on the MXU with an online-softmax
accumulator held in VMEM scratch, and the backward pass recomputes P from
the saved logsumexp instead of storing it.

Layout: q/k/v are [batch, heads, seq, head_dim]; the grid is
(batch, heads, q-blocks, kv-blocks) with the kv dimension innermost and
sequential ("arbitrary") so the VMEM accumulators carry across kv steps;
batch/heads/q-blocks are parallel. Causal masking is by global position,
and fully-masked tiles are skipped with predication (the classic ~2x
saving on causal attention).

On the CPU backend the same kernels run in Pallas interpret mode, so the
CPU test mesh exercises the identical code path (tests/test_flash_attention.py);
every other backend compiles them.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30   # same masking constant as parallel/ring_attention.py
_LANES = 128      # TPU lane width: m/l scratch replicate across lanes


def _pick_block(seq, target):
    for b in (target, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and seq % b == 0 and b <= seq:
            return b
    return None


def _default_blocks(seq):
    """Measured-on-v5e block heuristic: small tiles pay grid overhead at
    long seq, so scale tile size with the sequence (q-block, kv-block)."""
    if seq <= 256:
        return 128, 128
    if seq <= 1024:
        return 256, 512
    return 512, 1024


def supports(shape, block=128):
    """Whether flash_attention can run for [B, H, S, D] (S divisible
    into >=8-row blocks)."""
    s = shape[2]
    return _pick_block(s, block) is not None


# Measured crossover vs XLA's fused attention on v5e: at short seq the
# whole score matrix fits on-chip and XLA's fusion wins; the kernel wins
# once [S, S] spills to HBM (isolated fwd+bwd bf16: 1.2x at 2k, 28x at
# 8k). Round-5 END-TO-END check on bert_large (remat, scanned layers)
# moved the threshold from 1024 to 512: full-model tokens/s at seq 512
# is ~10% HIGHER with the kernel (34.3k vs 31.0k at B=96) while seq
# 128/256 strongly favor XLA (45.8k vs 32.6k; 40.2k vs 26.6k) — under
# remat the attention recompute doubles the [S,S] traffic, which the
# kernel avoids earlier than the isolated crossover suggested.
MIN_KERNEL_SEQ = 512


def preferred(shape):
    """True when the Pallas kernel is expected to beat XLA's fused
    attention for this [B, H, S, D] shape."""
    return shape[2] >= MIN_KERNEL_SEQ and supports(shape)


def _interpret_default():
    return jax.default_backend() == 'cpu'


def _causal_mask(s, qi, ki, bq, bk):
    """Apply the global-position causal mask to one [bq, bk] score tile."""
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _tile_live(qi, ki, bq, bk):
    """False only for tiles strictly above the causal diagonal
    (fully masked -> safe to skip)."""
    return qi * bq + bq - 1 >= ki * bk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_scr, m_scr, l_scr,
                *, sm_scale, causal, bq, bk, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def tile():
        q = q_ref[0, 0]                       # [bq, D]
        k = k_ref[0, 0]                       # [bk, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        m_prev = m_scr[:, :1]                                 # [bq, 1]
        l_prev = l_scr[:, :1]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        @pl.when(_tile_live(qi, ki, bq, bk))
        def _():
            tile()
    else:
        tile()

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, :1] + jnp.log(l))


def _fwd(q, k, v, causal, sm_scale, bq, bk, interpret):
    b, h, s, d = q.shape
    nq, nk = s // bq, s // bk
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, bq=bq, bk=bk, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
        name='flash_fwd',
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale, causal, bq, bk, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                   # [bq, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(_tile_live(qi, ki, bq, bk))
        def _():
            tile()
    else:
        tile()

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, sm_scale, causal, bq, bk, nq):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, ki, bq, bk)
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(_tile_live(qi, ki, bq, bk))
        def _():
            tile()
    else:
        tile()

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, sm_scale, bq, bk, interpret):
    b, h, s, d = q.shape
    nq, nk = s // bq, s // bk
    # delta = rowsum(dO * O): tiny elementwise reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B, H, S, 1]

    qkv_spec = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=qkv_spec,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
        name='flash_dq',
    )(q, k, v, do, lse, delta)

    # dk/dv: grid iterates q-blocks innermost for each kv-block
    kv_first_spec = [
        pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=(b, h, nk, nq),
        in_specs=kv_first_spec,
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel',
                                 'arbitrary')),
        interpret=interpret,
        name='flash_dkv',
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, bq, bk, interpret):
    o, _ = _fwd(q, k, v, causal, sm_scale, bq, bk, interpret)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, bq, bk, interpret):
    o, lse = _fwd(q, k, v, causal, sm_scale, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal, sm_scale, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Exact attention over [batch, heads, seq, head_dim] tensors.

    Differentiable (custom VJP, flash backward). Requires ``seq`` to
    split into uniform blocks (``supports()``); callers fall back to the
    jnp path otherwise. Block sizes default to a measured seq-dependent
    heuristic. ``interpret`` defaults to True on the CPU backend only
    (so the same kernel code runs on the CPU test mesh); any other
    backend compiles the kernel or fails.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    dq_blk, dk_blk = _default_blocks(q.shape[2])
    bq = _pick_block(q.shape[2], block_q or dq_blk)
    bk = _pick_block(q.shape[2], block_k or dk_blk)
    if bq is None or bk is None:
        raise ValueError('flash_attention: seq %d not blockable; check '
                         'supports() first' % q.shape[2])
    if interpret is None:
        interpret = _interpret_default()
    return _flash(q, k, v, causal, float(sm_scale), bq, bk, interpret)
