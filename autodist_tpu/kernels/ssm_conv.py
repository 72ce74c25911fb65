"""Pallas TPU kernels for the short causal conv of a selective
state-space layer with its SiLU: ``silu(conv(xBC) + bias)`` of Mamba-2's
mixer, forward and backward under one ``jax.custom_vjp``, on the columns
of the in-projection's own output.

For ``proj [batch, seq, width]`` (what the projection wrote), ``taps [k,
channels]`` and ``bias [channels]`` (f32), the channels being the
``channels`` columns of ``proj`` from ``offset`` on::

    pre_t = sum_i taps_i xBC_{t - (k - 1) + i} + bias     zeros before the sequence
    out_t = silu(pre_t)                                   in proj's dtype

and the output comes apart as the caller's ``widths`` say (Mamba-2: x |
B | C, the three arrays ``kernels/ssd_scan.py`` takes), each an array of
its own: nothing slices a ``[batch, seq, channels]`` result afterwards.

**The kernels** (named in a trace). ``ssm_conv_fwd`` walks grid
``(batch, lane step, row block)``. A grid step holds, for each part, a
tile of :data:`ROWS` rows by the part's lane tile (``widths`` over the
number of lane steps, at most :data:`MAX_TILE` lanes: 1024 | 256 | 256
at Mamba-2's 4096 | 1024 | 1024), read IN PLACE from ``proj``: the block's
index map adds the part's column offset, which a multiple of the tile
makes a whole number of blocks. The ``k - 1`` rows before a row block
are the last of a second operand, the SAME array one block of
:data:`HALO` rows back (zeros at the first row block of each batch row:
nothing leaks from one sequence into the next). The tile and its halo
are laid end to end in VMEM; the body takes :data:`SUB` rows of one
128-lane block at a time (two loops, traced once: :func:`_walk`), widens
them to f32, makes the ``k`` shifted copies by sublane rotations
(``pltpu.roll`` of the run and an aligned slice of it), and multiplies,
sums, adds the bias, applies SiLU and rounds, all on the tile.

``ssm_conv_bwd`` walks grid ``(lane step, batch, row block)`` over the
same columns and the three cotangents. It recomputes ``pre`` on the
tile (so nothing of the forward is kept but ``proj`` itself, which the
projection's own backward needs anyway), ``gp = g silu'(pre)``, and::

    dxBC_t = sum_i taps_i gp_{t + (k - 1) - i}            zeros after the sequence
    d_taps_i = sum_t gp_t xBC_{t - (k - 1) + i}           d_bias = sum_t gp_t

``gp`` of the ``k - 1`` rows AFTER a pass's rows is recomputed from a
halo on both sides (``HALO`` more rows of ``xBC`` and of ``g``), so the
row blocks are independent of each other; the two sums accumulate in
f32, eight partial rows a tap in the output block that stays in VMEM
over a lane step's whole walk, and XLA adds the eight. Every product,
the bias, SiLU and its derivative are f32 on the tile; inputs and
outputs are the model's dtype. No f32 tensor of activation size exists
in HBM in either pass.

**The gradient is taken with respect to the columns, not the
projection.** A ``custom_vjp`` over ``proj`` would hand back a cotangent
of ``proj``'s whole shape, zero outside the conv's columns, for XLA to
add to the other columns' (two passes over the widest tensor of the
layer). :func:`conv_silu` instead passes the parts' columns as slices
of ``proj`` that the forward never reads (XLA drops them) and the
backward answers with ``dxBC`` part by part; the array the kernels read
is the same ``proj`` with its gradient stopped.

:func:`supports` says which shapes the kernels take; every other shape,
and the tests' witness, is :func:`reference`, the same function in
``jax.numpy``. On the CPU backend the kernels run in Pallas interpret
mode; every other backend compiles them.
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of the sequence a grid step holds, and rows a pass of the body
# computes (what is live in vector registers at once).
ROWS = 1024
SUB = 64
# Rows of the halo operands: a tile of bf16 in HBM, two of f32.
HALO = 16
MAX_TILE = 1024
TAPS = 4
_LANES = 128
_SUBLANES = 8
_VMEM_LIMIT_BYTES = 64 << 20

Plan = collections.namedtuple(
    'Plan', 'block_rows sub_rows steps tiles')


def _interpret_default():
    return jax.default_backend() == 'cpu'


def _starts(offset, widths):
    """The first column of each part."""
    return [offset + sum(widths[:p]) for p in range(len(widths))]


def plan(seq, width, offset, widths, taps):
    """How the kernels would walk ``proj [., seq, width]`` for the parts
    ``widths`` from column ``offset`` on under ``taps`` taps (a
    :class:`Plan`: rows a block, rows a pass, lane steps, the parts' lane
    tiles), or ``None`` where they do not take the shape."""
    rows = min(ROWS, seq)
    sub = min(SUB, rows)
    if (taps != TAPS or not widths or seq % rows or rows % sub
            or sub % HALO or offset + sum(widths) > width
            or any(w <= 0 or w % _LANES for w in widths)):
        return None
    most = math.gcd(*(w // _LANES for w in widths))
    starts = _starts(offset, widths)
    for steps in range(1, most + 1):
        if most % steps:
            continue
        tiles = tuple(w // steps for w in widths)
        if max(tiles) <= MAX_TILE and not any(
                s % t for s, t in zip(starts, tiles)):
            return Plan(rows, sub, steps, tiles)
    return None


def supports(seq, width, offset, widths, taps):
    """Whether :func:`conv_silu` runs the kernels (else
    :func:`reference`): ``taps`` of :data:`TAPS`, parts and an offset of
    whole lane blocks that some lane tile of at most :data:`MAX_TILE`
    divides, a sequence of whole row blocks."""
    return plan(seq, width, offset, widths, taps) is not None


# ---------------------------------------------------------------------------
# the same function in jax.numpy
# ---------------------------------------------------------------------------

def _shifted_sum(x, taps, offsets, pad):
    s = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), pad, (0, 0)))
    return sum(padded[:, o:o + s] * taps[i] for i, o in enumerate(offsets))


@jax.custom_vjp
def causal_conv(x, taps, bias):
    """The depthwise causal conv of ``x [b, s, c]`` over ``taps [k, c]``
    as ``k`` shifted products, plus ``bias [c]``, in f32: ``out_t =
    sum_i taps_i x_{t - (k - 1) + i}``, zeros before the sequence.

    The backward pass is written out: ``dx_t = sum_i taps_i g_{t + (k -
    1) - i}`` is the same sum of shifted products over the cotangent
    (zeros after the sequence). Left to autodiff it is ``k`` padded f32
    copies of the cotangent written and read again, 2.4 GB a layer at
    16,384 tokens x 6144 channels, in a fusion that carries no scope's
    name."""
    k = taps.shape[0]
    return _shifted_sum(x, taps, range(k), (k - 1, 0)) + bias


def _causal_conv_fwd(x, taps, bias):
    return causal_conv(x, taps, bias), (x, taps)


def _causal_conv_bwd(res, g):
    x, taps = res
    k, s = taps.shape[0], x.shape[1]
    dx = _shifted_sum(g, taps, range(k - 1, -1, -1), (0, k - 1))
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    d_taps = jnp.stack([jnp.sum(g * padded[:, i:i + s], axis=(0, 1))
                        for i in range(k)])
    return dx.astype(x.dtype), d_taps, jnp.sum(g, axis=(0, 1))


causal_conv.defvjp(_causal_conv_fwd, _causal_conv_bwd)


def _columns(x, offset, widths):
    """``x``'s columns from ``offset`` on as the parts of ``widths``."""
    return tuple(x[..., s:s + w]
                 for s, w in zip(_starts(offset, widths), widths))


def reference(proj, taps, bias, offset, widths):
    """:func:`conv_silu` in ``jax.numpy``: the columns sliced out,
    :func:`causal_conv` and SiLU in f32, the result in ``proj``'s dtype
    and sliced into its parts. Any shape."""
    xbc = proj[..., offset:offset + sum(widths)]
    out = jax.nn.silu(causal_conv(xbc, taps, bias)).astype(proj.dtype)
    return _columns(out, 0, widths)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _roll(x, shift):
    """``out[t] = x[t - shift]`` along the rows, round the ends."""
    shift %= x.shape[0]
    return pltpu.roll(x, shift, 0) if shift else x


def _shifted(ext, k, rows):
    """The ``k`` runs a conv of ``k`` taps multiplies, from ``ext``
    whose first :data:`HALO` rows lie before the rows asked for:
    ``out[i][t] = ext[HALO + t - (k - 1) + i]``, ``rows`` rows each."""
    return [_roll(ext, k - 1 - i)[HALO:HALO + rows] for i in range(k)]


def _walk(rows, sub, width, body):
    """``body(r, lanes)`` for every pass of ``sub`` of the ``rows`` rows
    and every block of 128 of the ``width`` lanes. Both are loops and
    not Python's, so that a body is traced once however many lane
    blocks a tile has (at 1.1 ms an operation on the chip's host, twelve
    copies of the two bodies were 4 s of every start); the lane blocks
    of a pass are unrolled when the kernel is lowered: they are
    independent, and rolled up the forward is 0.83 ms for 0.67."""
    def a_pass(m, _):
        r = pl.multiple_of(m * sub, sub)

        def a_block(b, _):
            body(r, pl.ds(pl.multiple_of(b * _LANES, _LANES), _LANES))
        jax.lax.fori_loop(0, width // _LANES, a_block, None, unroll=True)
    jax.lax.fori_loop(0, rows // sub, a_pass, None)


def _lay(run_ref, *pieces):
    """``pieces`` (value, rows) end to end along ``run_ref``'s rows."""
    at = 0
    for value, rows in pieces:
        run_ref[at:at + rows] = value
        at += rows


def _unless(edge, ref):
    """``ref``'s block, or zeros where ``edge`` (a halo past an end of
    the sequence)."""
    return jnp.where(edge, jnp.zeros_like(ref), ref[...])


def _pre(ext, taps, bias, rows):
    """``rows`` rows of the conv plus its bias from the run ``ext``
    (f32, :data:`HALO` rows before the first row asked for), and the
    shifted runs it multiplied."""
    shifted = _shifted(ext, taps.shape[0], rows)
    return bias + sum(taps[i:i + 1] * run
                      for i, run in enumerate(shifted)), shifted


def _fwd_part(first, sub, x_ref, before_ref, taps_ref, bias_ref, out_ref,
              run_ref):
    rows = x_ref.shape[0]
    _lay(run_ref, (_unless(first, before_ref), HALO), (x_ref[...], rows))

    def a_block(r, lanes):
        ext = run_ref[pl.ds(r, sub + HALO), lanes].astype(jnp.float32)
        pre, _ = _pre(ext, taps_ref[:, lanes], bias_ref[:, lanes], sub)
        out_ref[pl.ds(r, sub), lanes] = (
            pre * jax.lax.logistic(pre)).astype(out_ref.dtype)
    _walk(rows, sub, out_ref.shape[-1], a_block)


def _fwd_kernel(*refs, parts, sub):
    """Per part: its tile of ``proj``, the halo before it, its taps and
    bias; then the parts' outputs; then a run of scratch each."""
    first = pl.program_id(2) == 0
    ins, rest = refs[:4 * parts], refs[4 * parts:]
    for p in range(parts):
        _fwd_part(first, sub, *ins[4 * p:4 * p + 4], rest[p],
                  rest[parts + p])


def _bwd_part(first, last, fresh, sub, x_ref, before_ref, after_ref, g_ref,
              g_after_ref, taps_ref, bias_ref, dx_ref, sums_ref, x_run_ref,
              g_run_ref):
    rows, k = x_ref.shape[0], taps_ref.shape[0]

    @pl.when(fresh)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
    _lay(x_run_ref, (_unless(first, before_ref), HALO), (x_ref[...], rows),
         (_unless(last, after_ref), HALO))
    _lay(g_run_ref, (g_ref[...], rows), (_unless(last, g_after_ref), HALO))
    wide = sub + HALO         # a pass's rows and the halo after them

    def a_block(r, lanes):
        ext = x_run_ref[pl.ds(r, wide + HALO), lanes].astype(jnp.float32)
        g = g_run_ref[pl.ds(r, wide), lanes].astype(jnp.float32)
        taps = taps_ref[:, lanes]
        pre, shifted = _pre(ext, taps, bias_ref[:, lanes], wide)
        sig = jax.lax.logistic(pre)
        gp = g * (sig * (1.0 + pre * (1.0 - sig)))
        # dx_t = sum_i taps_i gp_{t + (k - 1) - i}
        dx_ref[pl.ds(r, sub), lanes] = sum(
            taps[i:i + 1] * _roll(gp, -(k - 1 - i))[:sub]
            for i in range(k)).astype(dx_ref.dtype)
        gp = gp[:sub]
        for i, of in enumerate([gp * run[:sub] for run in shifted] + [gp]):
            # eight partial rows: whole vector registers added, no
            # reduction across sublanes
            sums_ref[i * _SUBLANES:(i + 1) * _SUBLANES, lanes] += of.reshape(
                sub // _SUBLANES, _SUBLANES, _LANES).sum(axis=0)
    _walk(rows, sub, dx_ref.shape[-1], a_block)


def _bwd_kernel(*refs, parts, sub):
    """Per part: its tile of ``proj`` and the halo on both sides, its
    cotangent's tile and the halo after it, its taps and bias; then per
    part ``dx`` and the sums (eight partial rows for each tap, then for
    the bias); then two runs of scratch each. Grid ``(lane step, batch,
    row block)``: the sums' block is one a lane step."""
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    fresh = jnp.logical_and(pl.program_id(1) == 0, first)
    ins, outs, runs = (refs[:7 * parts], refs[7 * parts:9 * parts],
                       refs[9 * parts:])
    for p in range(parts):
        _bwd_part(first, last, fresh, sub, *ins[7 * p:7 * p + 7],
                  *outs[2 * p:2 * p + 2], *runs[2 * p:2 * p + 2])


def _specs(plan, seq, offset, widths, order):
    """Per part, by name, the block specs of a tile of ``proj``'s columns
    (``x``) and of the halo ``before`` and ``after`` it in the same
    columns; of a tile of an array of the part's own width (``part``: an
    output, a cotangent) and the halo after it (``part_after``); of the
    ``taps``, the ``bias`` and the backward's ``sums``. ``order`` turns
    the grid's indices into ``(batch, lane step, row block)``."""
    rows = plan.block_rows
    per, ends = rows // HALO, seq // HALO - 1

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *g: index(*order(*g)))

    def before(i):
        return jnp.maximum(i * per - 1, 0)

    def after(i):
        return jnp.minimum((i + 1) * per, ends)
    out = []
    for tile, own, col in zip(plan.tiles, _starts(0, widths),
                              _starts(offset, widths)):
        own, col = own // tile, col // tile
        out.append({
            'x': spec((None, rows, tile),
                      lambda b, j, i, c=col: (b, i, c + j)),
            'before': spec((None, HALO, tile),
                           lambda b, j, i, c=col: (b, before(i), c + j)),
            'after': spec((None, HALO, tile),
                          lambda b, j, i, c=col: (b, after(i), c + j)),
            'part': spec((None, rows, tile), lambda b, j, i: (b, i, j)),
            'part_after': spec((None, HALO, tile),
                               lambda b, j, i: (b, after(i), j)),
            'taps': spec((TAPS, tile), lambda b, j, i, c=own: (0, c + j)),
            'bias': spec((1, tile), lambda b, j, i, c=own: (0, c + j)),
            'sums': spec(((TAPS + 1) * _SUBLANES, tile),
                         lambda b, j, i: (0, j)),
        })
    return out


# (jitted: the bodies are a few hundred operations to trace, and a step
# calls each kernel from every Mamba-2 layer, again where the checkpoint
# and the custom-vjp's rules trace it anew; under one `jit` of its own a
# call is traced once a process and shape, whatever the number of sites)
@functools.partial(jax.jit, static_argnames=('offset', 'widths', 'plan',
                                             'interpret'))
def _forward_call(proj, taps, bias, offset, widths, plan, interpret):
    bsz, seq, _ = proj.shape
    specs = _specs(plan, seq, offset, widths, lambda b, j, i: (b, j, i))
    bias = bias.reshape(1, -1)
    return tuple(pl.pallas_call(
        functools.partial(_fwd_kernel, parts=len(widths), sub=plan.sub_rows),
        grid=(bsz, plan.steps, seq // plan.block_rows),
        in_specs=[s[name] for s in specs
                  for name in ('x', 'before', 'taps', 'bias')],
        out_specs=[s['part'] for s in specs],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, w), proj.dtype)
                   for w in widths],
        scratch_shapes=[pltpu.VMEM((HALO + plan.block_rows, tile), proj.dtype)
                        for tile in plan.tiles],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name='ssm_conv_fwd',
    )(*(a for _ in widths for a in (proj, proj, taps, bias))))


@functools.partial(jax.jit, static_argnames=('offset', 'widths', 'plan',
                                             'interpret'))
def _backward_call(proj, taps, bias, cts, offset, widths, plan, interpret):
    bsz, seq, _ = proj.shape
    specs = _specs(plan, seq, offset, widths, lambda j, b, i: (b, j, i))
    bias = bias.reshape(1, -1)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, parts=len(widths), sub=plan.sub_rows),
        grid=(plan.steps, bsz, seq // plan.block_rows),
        in_specs=[s[name] for s in specs for name in (
            'x', 'before', 'after', 'part', 'part_after', 'taps', 'bias')],
        out_specs=[s[name] for s in specs for name in ('part', 'sums')],
        out_shape=[shape for w in widths for shape in (
            jax.ShapeDtypeStruct((bsz, seq, w), proj.dtype),
            jax.ShapeDtypeStruct(((TAPS + 1) * _SUBLANES, w), jnp.float32))],
        scratch_shapes=[pltpu.VMEM((rows, tile), proj.dtype)
                        for tile in plan.tiles
                        for rows in (plan.block_rows + 2 * HALO,
                                     plan.block_rows + HALO)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name='ssm_conv_bwd',
    )(*(a for ct in cts for a in (proj, proj, proj, ct, ct, taps, bias)))
    sums = jnp.concatenate(outs[1::2], axis=1).reshape(
        TAPS + 1, _SUBLANES, -1).sum(axis=1)
    return tuple(outs[0::2]), sums[:TAPS], sums[TAPS]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv_silu(cols, proj, taps, bias, offset, widths, plan, interpret):
    del cols            # what the gradient is taken with respect to
    return _forward_call(proj, taps, bias, offset, widths, plan, interpret)


def _conv_silu_fwd(cols, proj, taps, bias, offset, widths, plan, interpret):
    return (_conv_silu(cols, proj, taps, bias, offset, widths, plan,
                       interpret), (proj, taps, bias))


def _conv_silu_bwd(offset, widths, plan, interpret, res, cts):
    proj, taps, bias = res
    d_cols, d_taps, d_bias = _backward_call(
        proj, taps, bias, [ct.astype(proj.dtype) for ct in cts], offset,
        widths, plan, interpret)
    return d_cols, None, d_taps.astype(taps.dtype), d_bias.astype(bias.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(proj, taps, bias, offset, widths, interpret=None):
    """``silu(conv(xBC) + bias)`` (module docstring) of the ``sum(widths)``
    columns of ``proj [B, S, width]`` from ``offset`` on, in ``proj``'s
    dtype, as a tuple of arrays ``[B, S, w]``, one for each ``w`` of
    ``widths``; ``taps [k, channels]`` and ``bias [channels]`` in f32.
    The kernels where :func:`supports` says so, else :func:`reference`.
    """
    widths = tuple(widths)
    how = plan(proj.shape[1], proj.shape[2], offset, widths, taps.shape[0])
    if how is None:
        return reference(proj, taps, bias, offset, widths)
    if interpret is None:
        interpret = _interpret_default()
    return _conv_silu(_columns(proj, offset, widths),
                      jax.lax.stop_gradient(proj),
                      taps.astype(jnp.float32), bias.astype(jnp.float32),
                      offset, widths, how, interpret)
