"""Pallas TPU fused pointwise-conv + BatchNorm kernel.

The round-3 xplane profile of the ResNet-101 step (BASELINE.md) showed
the step is activation-bandwidth-bound: 42% layout-copy waits and 36%
BatchNorm moment reductions — each BN site costs one full HBM read of a
multi-hundred-MB activation, and the normalize+relu another read+write.
The reference has no kernels at all (its conv perf came from cuDNN via
the TF runtime); this is the TPU-native answer: a 1x1 convolution IS a
matmul ``[B*H*W, Cin] x [Cin, Cout]``, so the BBN work rides the MXU
pass:

- **epilogue**: per-channel moment sums (sum y, sum y^2) accumulate in
  f32 from the MXU accumulator while the tile is still in VMEM — the
  BN-statistics pass over the conv output costs ZERO extra HBM traffic;
- **prologue**: the PREVIOUS BatchNorm's normalize+affine+ReLU
  (``relu(x*a + b)``, per-input-channel a/b) applies to each input tile
  on the way into the MXU — the consumer-side elementwise pass also
  vanishes.

Backward is a hand-written vjp in plain XLA ops (two MXU matmuls plus
fused elementwise) — dW = xn^T dY and dx = dY W^T are already
MXU-shaped, so the custom kernel is only needed where XLA could not
fuse: the forward's stats+normalize traffic.

Like kernels/flash_attention.py, the same kernel runs in Pallas
interpret mode on the CPU backend only, so the CPU test mesh exercises
the identical code path; any other backend compiles it
(``chip_smoke.py`` compiles it on the chip and compares with plain jnp).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _interpret_default():
    return jax.default_backend() == 'cpu'


def supports(n_rows, c_in, c_out, block_n=None):
    """Whether the fused kernel can serve [N, Cin] x [Cin, Cout]:
    lane-aligned outputs (the stats accumulators live per output
    channel), sublane-aligned inputs (Mosaic pads the contraction to
    128 lanes — DenseNet's growth-32 concats ride the kernel at some
    lane waste, still a large win over the extra HBM passes), and a row
    count divisible into tiles (padded rows would corrupt the moment
    sums)."""
    bn = block_n or _pick_block_n(n_rows)
    return (c_in % 8 == 0 and c_out % 128 == 0 and bn is not None)


def _pick_block_n(n_rows):
    for b in (512, 256, 128, 64, 32, 16, 8):
        if n_rows % b == 0 and b <= n_rows:
            return b
    return None


def _pick_block_cout(c_out):
    for b in (512, 256, 128):
        if c_out % b == 0 and b <= c_out:
            return b
    return c_out


def _kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s1_ref, s2_ref, *,
            prologue, prologue_relu, want_stats, out_dtype):
    # grid = (n_out_tiles, m_tiles): m is the INNER (sequential) dim so
    # the per-out-channel moment accumulators stay resident in VMEM for
    # a whole column strip while the W tile for that strip loads once.
    i = pl.program_id(1)
    x = x_ref[...]
    if prologue:
        xn = x.astype(jnp.float32) * a_ref[...] + b_ref[...]
        if prologue_relu:
            xn = jnp.maximum(xn, 0.0)
        x = xn.astype(x_ref.dtype)
    acc = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(out_dtype)

    # stats outputs are ALWAYS initialized (want_stats=False promises
    # zeros, not uninitialized memory)
    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)
    if want_stats:
        # moment sums from the f32 accumulator, free of HBM traffic
        s1_ref[...] += jnp.sum(acc, axis=0, keepdims=True)
        s2_ref[...] += jnp.sum(acc * acc, axis=0, keepdims=True)


def _fwd_call(x2d, w, a, b, prologue_relu, want_stats, out_dtype,
              block_n, interpret):
    n, c_in = x2d.shape
    c_out = w.shape[1]
    bm = block_n or _pick_block_n(n)
    bco = _pick_block_cout(c_out)
    prologue = a is not None
    if a is None:
        a = jnp.ones((1, c_in), jnp.float32)
        b = jnp.zeros((1, c_in), jnp.float32)
    grid = (c_out // bco, n // bm)
    kernel = functools.partial(
        _kernel, prologue=prologue, prologue_relu=prologue_relu,
        want_stats=want_stats, out_dtype=out_dtype)
    y, s1, s2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c_in), lambda j, i: (i, 0)),
            pl.BlockSpec((c_in, bco), lambda j, i: (0, j)),
            pl.BlockSpec((1, c_in), lambda j, i: (0, 0)),
            pl.BlockSpec((1, c_in), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bco), lambda j, i: (i, j)),
            pl.BlockSpec((1, bco), lambda j, i: (0, j)),
            pl.BlockSpec((1, bco), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, c_out), out_dtype),
            jax.ShapeDtypeStruct((1, c_out), jnp.float32),
            jax.ShapeDtypeStruct((1, c_out), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret,
    )(x2d, w.astype(x2d.dtype), a.reshape(1, c_in).astype(jnp.float32),
      b.reshape(1, c_in).astype(jnp.float32))
    return y, s1.reshape(c_out), s2.reshape(c_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused(x2d, w, a, b, prologue_relu, want_stats, out_dtype, block_n,
           interpret):
    return _fwd_call(x2d, w, a, b, prologue_relu, want_stats, out_dtype,
                     block_n, interpret)


def _fused_fwd(x2d, w, a, b, prologue_relu, want_stats, out_dtype,
               block_n, interpret):
    out = _fwd_call(x2d, w, a, b, prologue_relu, want_stats, out_dtype,
                    block_n, interpret)
    y, _, _ = out
    return out, (x2d, w, a, b, y)


def _fused_bwd(prologue_relu, want_stats, out_dtype, block_n, interpret,
               res, cts):
    """Plain-XLA vjp: two MXU matmuls + fused elementwise.

    With outputs (y, s1, s2), s1 = sum_rows(y), s2 = sum_rows(y^2), the
    effective output cotangent is dY = dy + ds1 + 2*y*ds2 (broadcast
    over rows); then dW = xn^T dY, dxn = dY W^T, and the prologue
    (relu(x*a+b)) backprops elementwise with xn recomputed (cheap; XLA
    fuses it into the matmul operand).

    Every [N, C]-sized intermediate stays in the ACTIVATION dtype (bf16
    in the benchmark configs) — f32 is reserved for [C] vectors and
    reduction accumulators. An f32 dY/xn here doubles the backward's
    HBM bytes and triggers layout-copy storms on the stage-1/-2
    activations (round-4 profile: multi-hundred-MB f32 copies)."""
    x2d, w, a, b, y = res
    dy, ds1, ds2 = cts
    cdt = x2d.dtype  # activation/MXU dtype
    dY = dy.astype(cdt)
    if want_stats:
        dY = dY + ds1.astype(cdt)[None, :] + \
            y.astype(cdt) * (2.0 * ds2).astype(cdt)[None, :]
    if a is not None:
        av = a.reshape(1, -1).astype(cdt)
        bv = b.reshape(1, -1).astype(cdt)
        xn = x2d * av + bv
        if prologue_relu:
            xn = jnp.maximum(xn, 0)
        xn_c = xn
    else:
        xn_c = x2d
    dw = jax.lax.dot_general(xn_c, dY, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dxn = jax.lax.dot_general(dY, w.astype(cdt),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dxn = dxn.astype(cdt)
    if a is not None:
        if prologue_relu:
            dxn = jnp.where(xn > 0, dxn, 0)
        dx = dxn * av
        # convert+multiply+reduce fuse into ONE bf16 HBM read with f32
        # register math (no f32 [N, C] temporary)
        da = jnp.sum(dxn.astype(jnp.float32) * x2d.astype(jnp.float32),
                     axis=0, dtype=jnp.float32)
        db = jnp.sum(dxn.astype(jnp.float32), axis=0,
                     dtype=jnp.float32)
        da = da.reshape(a.shape).astype(a.dtype)
        db = db.reshape(b.shape).astype(b.dtype)
    else:
        dx = dxn
        da = None
        db = None
    return dx, dw.astype(w.dtype), da, db


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_pointwise(x, w, scale=None, bias=None, prologue_relu=False,
                    want_stats=True, out_dtype=None, stride=1,
                    block_n=None, interpret=None):
    """Fused 1x1 conv (+ BN prologue/epilogue) on NHWC input.

    Args:
        x: [B, H, W, Cin] activations.
        w: [Cin, Cout] pointwise kernel (a [1, 1, Cin, Cout] HWIO conv
            kernel reshaped).
        scale, bias: optional per-Cin normalize+affine applied to ``x``
            on the way into the MXU (the PREVIOUS BatchNorm's folded
            coefficients); ``prologue_relu`` applies ReLU after.
        want_stats: also return (sum y, sum y^2) per output channel,
            accumulated in the epilogue (the NEXT BatchNorm's moments).
        stride: 1x1 conv stride (spatial subsample before the matmul).
        out_dtype: output dtype (defaults to x.dtype).

    Returns:
        ``(y [B, H', W', Cout], s1 [Cout], s2 [Cout])``; s1/s2 are
        zeros when ``want_stats=False``.
    """
    if interpret is None:
        interpret = _interpret_default()
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    batch, hh, ww, c_in = x.shape
    n = batch * hh * ww
    out_dtype = out_dtype or x.dtype
    y, s1, s2 = _fused(x.reshape(n, c_in), w,
                       None if scale is None else scale,
                       None if scale is None else bias,
                       bool(prologue_relu), bool(want_stats),
                       jnp.dtype(out_dtype), block_n, bool(interpret))
    return y.reshape(batch, hh, ww, w.shape[1]), s1, s2
