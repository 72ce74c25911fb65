"""Pallas TPU kernels for the RMSNorm over every q head and every k head
of an attention layer's fused projection (Qwen3's ``q_norm`` / ``k_norm``),
forward and backward under one ``jax.custom_vjp``.

For ``x [rows, width]`` (what the projection wrote: heads of ``d`` lanes
side by side) and ``scale [heads * d]`` (f32: the first ``heads`` heads'
weights, lane for lane), all in f32::

    inv = rsqrt(mean over the head's d lanes of x^2 + eps)
    y = ((x inv) scale)          on the first heads * d lanes
    y = x                        on the lanes behind them (v)

in ``x``'s dtype, which is ``models/attention.head_rms_norm``, the form the
kernels are held to in the tests and the one every other shape keeps.

**The kernels** (named in a trace). ``qk_norm_fwd`` walks grid ``(lane
step, row block)`` over ``x`` WHERE IT LIES; a step holds a tile of
``rows`` rows by whole heads, all of them normed or none (at most
:data:`MAX_TILE` lanes where a head is no wider). The body takes
:data:`SUB` rows of one head at a time in two walks over the head's
128-lane blocks (:func:`_walk`): the first adds ``x^2`` up block by
block (whole vector registers; one reduction across the lanes a head and
pass), the second writes ``(x inv) scale`` rounded once; a pass's heads
are unrolled when the kernel is lowered, so that one head's reduction
and ``rsqrt`` wait under the next's loads and products. A tile behind
the normed heads is copied bit for bit (the output is an array of its
own: ``x`` is what the backward reads, so it cannot be written in
place). Nothing else is written: no f32 tensor of activation size exists
in HBM and nothing reshapes lanes into heads.

``qk_norm_bwd`` walks the same grid over ``x`` and the cotangent ``dy``.
It makes ``inv`` again on the tile (so the forward keeps nothing but its
inputs), and with ``u = dy scale`` and ``unit = x inv``::

    dx = inv (u - unit mean(u unit))   (the mean over the head's lanes)
    d_scale = sum over the rows of dy unit

``dx`` rounded once; a tile behind the normed heads hands its cotangent
through. The sums over the rows go into the step's own output block,
eight partial rows (whole vector registers, no reduction across
sublanes): ``f32[row blocks, 8, width]``, which XLA adds up.

:func:`supports` says which shapes the kernels take; :func:`head_norm`
raises on any other: the caller keeps its ``jax.numpy`` form for those
(``models/attention.py``). On the CPU backend the kernels run in Pallas
interpret mode; every other backend compiles them.
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a grid step holds at a tile of MAX_TILE lanes (more at a narrower
# tile, fewer where one head alone is wider: the tile's size is kept),
# and rows a pass of the body computes (the fastest of the sweep on a
# v5e, `tools/qk_norm_bench.py`: passes of 64 rows cost the backward 6%).
ROWS = 2048
SUB = 256
MAX_TILE = 1024
_LANES = 128
_SUBLANES = 8
# A pass's rows are whole tiles of a bf16 operand.
_ROW_TILE = 16
_VMEM_LIMIT_BYTES = 64 << 20

Plan = collections.namedtuple(
    'Plan', 'block_rows sub_rows steps tile head_lanes normed_steps')


def _interpret_default():
    return jax.default_backend() == 'cpu'


def plan(rows, width, heads, d):
    """How the kernels would walk ``[rows, width]`` with the first
    ``heads`` heads of ``d`` lanes normed (a :class:`Plan`: rows a block,
    rows a pass, lane steps, lanes a tile, lanes a head, the lane steps
    whose heads are normed), or ``None`` where they do not take the
    shape."""
    if (d <= 0 or d % _LANES or width % d or heads < 1
            or heads * d > width or rows % _ROW_TILE):
        return None
    # whole heads to a tile, normed or not: as many as MAX_TILE holds of
    # those counts that divide both
    both = math.gcd(heads, width // d)
    per = max(k for k in range(1, both + 1)
              if both % k == 0 and (k == 1 or k * d <= MAX_TILE))
    tile = per * d
    block = _ROW_TILE
    while rows % (2 * block) == 0 and 2 * block * tile <= ROWS * MAX_TILE:
        block *= 2
    sub = min(SUB, block)
    if block % sub or sub % _ROW_TILE:
        return None
    return Plan(block, sub, width // tile, tile, d, heads // per)


def supports(rows, width, heads, d):
    """Whether :func:`head_norm` has kernels for the shape: heads of
    whole lane blocks, rows that split into whole row blocks in passes
    of whole (16, 128) tiles."""
    return plan(rows, width, heads, d) is not None


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _block(k):
    """The ``k``-th block of 128 lanes."""
    return pl.ds(pl.multiple_of(k * _LANES, _LANES), _LANES)


def _walk(blocks, body, carry=None):
    """``carry = body(k, carry)`` for each of a head's ``blocks`` 128-lane
    blocks. A loop and not Python's, so that a body is traced once
    however wide a head is (``kernels/ssm_gate_norm._walk``); unrolled
    when the kernel is lowered: the blocks are independent."""
    return jax.lax.fori_loop(0, blocks, body, carry, unroll=True)


def _for_each_head(rows, sub, tile, width, body):
    """``body(r, of)`` for every pass of ``sub`` of the ``rows`` rows and
    every head of ``width`` of the ``tile`` lanes: ``r`` the pass's rows,
    ``of(k)`` the head's ``k``-th lane block among the tile's. The heads
    of a pass are unrolled when the kernel is lowered: a head's chain
    (products, a reduction across lanes, ``rsqrt``, products) is latency
    and no work, and the next head's fills it."""
    def a_pass(m, _):
        r = pl.ds(pl.multiple_of(m * sub, sub), sub)

        def a_head(g, _):
            body(r, lambda k: _block(g * (width // _LANES) + k))
        jax.lax.fori_loop(0, tile // width, a_head, None, unroll=True)
    jax.lax.fori_loop(0, rows // sub, a_pass, None)


def _normed_or_copied(steps, src_ref, dst_ref, normed):
    """``normed()`` in the first ``steps`` lane steps; behind them the
    tile of ``src_ref`` into ``dst_ref`` as it is."""
    pl.when(pl.program_id(0) < steps)(normed)

    @pl.when(pl.program_id(0) >= steps)
    def _():
        dst_ref[...] = src_ref[...]


def _f32(ref, rows, lanes):
    return ref[rows, lanes].astype(jnp.float32)


def _across(acc, width):
    """The mean over a head of what ``acc [sub, 128]`` added up block by
    block, on every lane of a block."""
    mean = jnp.sum(acc, axis=-1, keepdims=True) * (1.0 / width)
    return jnp.broadcast_to(mean, acc.shape)


def _fwd_kernel(x_ref, scale_ref, y_ref, *, sub, width, steps, eps):
    rows, tile = y_ref.shape
    blocks = width // _LANES

    def a_head(r, of):
        def first(k, squares):
            x = _f32(x_ref, r, of(k))
            return squares + x * x
        squares = _walk(blocks, first, jnp.zeros((sub, _LANES), jnp.float32))
        inv = jax.lax.rsqrt(_across(squares, width) + eps)

        def second(k, carry):
            at = of(k)
            y_ref[r, at] = (_f32(x_ref, r, at) * inv
                            * scale_ref[:, at]).astype(y_ref.dtype)
            return carry
        _walk(blocks, second)
    _normed_or_copied(
        steps, x_ref, y_ref,
        lambda: _for_each_head(rows, sub, tile, width, a_head))


def _bwd_kernel(x_ref, dy_ref, scale_ref, dx_ref, sums_ref, *, sub, width,
                steps, eps):
    rows, tile = dx_ref.shape
    blocks = width // _LANES
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def fold(run):
        # eight partial rows: whole vector registers added
        return run.reshape(sub // _SUBLANES, _SUBLANES, _LANES).sum(axis=0)

    def a_head(r, of):
        def first(k, carry):
            squares, products = carry
            at = of(k)
            x = _f32(x_ref, r, at)
            u = _f32(dy_ref, r, at) * scale_ref[:, at]
            return squares + x * x, products + u * x
        zeros = jnp.zeros((sub, _LANES), jnp.float32)
        squares, products = _walk(blocks, first, (zeros, zeros))
        inv = jax.lax.rsqrt(_across(squares, width) + eps)
        # mean(u unit) = inv mean(u x)
        back = inv * _across(products, width)

        def second(k, carry):
            at = of(k)
            dy = _f32(dy_ref, r, at)
            unit = _f32(x_ref, r, at) * inv
            dx_ref[r, at] = (inv * (dy * scale_ref[:, at] - unit * back)
                             ).astype(dx_ref.dtype)
            sums_ref[:, at] += fold(dy * unit)
            return carry
        _walk(blocks, second)
    _normed_or_copied(
        steps, dy_ref, dx_ref,
        lambda: _for_each_head(rows, sub, tile, width, a_head))


def _call(kernel, name, plan, like, ins, outs, out_shape, eps, interpret):
    """The ``pallas_call`` ``name`` of ``kernel`` over ``like [rows,
    width]``'s grid ``(lane step, row block)``: ``ins`` and ``outs`` name
    their block specs, a tile of an array of ``like``'s shape (``part``),
    the tile's lanes of the scale (``row``), the backward's sums
    (``sums``)."""
    rows, tile = plan.block_rows, plan.tile
    specs = {
        'part': pl.BlockSpec((rows, tile), lambda j, i: (i, j)),
        'row': pl.BlockSpec((1, tile), lambda j, i: (0, j)),
        'sums': pl.BlockSpec((None, _SUBLANES, tile),
                             lambda j, i: (i, 0, j)),
    }
    return pl.pallas_call(
        functools.partial(kernel, sub=plan.sub_rows, width=plan.head_lanes,
                          steps=plan.normed_steps, eps=eps),
        grid=(plan.steps, like.shape[0] // rows),
        in_specs=[specs[kind] for kind in ins],
        out_specs=[specs[kind] for kind in outs],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name=name)


def _lanes_of(scale, width):
    """``scale`` on its lanes of a row of ``width`` (the lanes behind it
    are never read)."""
    return jnp.pad(scale, (0, width - scale.shape[0])).reshape(1, width)


# (jitted, as `kernels/ssm_gate_norm.py`'s calls are: a step calls each
# kernel from every layer, again where the checkpoint and the
# custom-vjp's rules trace it anew; under one `jit` of its own a call is
# traced once a process and shape, whatever the number of sites)
@functools.partial(jax.jit, static_argnames=('eps', 'plan', 'interpret'))
def _forward_call(x, scale, eps, plan, interpret):
    y, = _call(_fwd_kernel, 'qk_norm_fwd', plan, x, ('part', 'row'),
               ('part',), [jax.ShapeDtypeStruct(x.shape, x.dtype)], eps,
               interpret)(x, _lanes_of(scale, x.shape[1]))
    return y


@functools.partial(jax.jit, static_argnames=('eps', 'plan', 'interpret'))
def _backward_call(x, scale, dy, eps, plan, interpret):
    rows, width = x.shape
    dx, sums = _call(
        _bwd_kernel, 'qk_norm_bwd', plan, x, ('part', 'part', 'row'),
        ('part', 'sums'),
        [jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(
            (rows // plan.block_rows, _SUBLANES, width), jnp.float32)],
        eps, interpret)(x, dy, _lanes_of(scale, width))
    return dx, sums.sum(axis=(0, 1))[:scale.shape[0]]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _head_norm(x, scale, eps, plan, interpret):
    return _forward_call(x, scale, eps, plan, interpret)


def _head_norm_fwd(x, scale, eps, plan, interpret):
    return _head_norm(x, scale, eps, plan, interpret), (x, scale)


def _head_norm_bwd(eps, plan, interpret, res, dy):
    x, scale = res
    return _backward_call(x, scale, dy.astype(x.dtype), eps, plan, interpret)


_head_norm.defvjp(_head_norm_fwd, _head_norm_bwd)


def head_norm(x, scale, d, eps, interpret=None):
    """``x [..., width]`` with each of its first ``len(scale) / d`` heads
    of ``d`` lanes RMS-normalised over its own lanes and multiplied by
    its lanes of ``scale``, the lanes behind them as they are (module
    docstring; ``models/attention.head_rms_norm`` through the kernels),
    in ``x``'s dtype and shape. A shape the kernels do not take
    (:func:`supports`) raises."""
    width = x.shape[-1]
    rows = x.size // width
    heads, rest = divmod(scale.shape[0], d)
    how = None if rest else plan(rows, width, heads, d)
    if how is None:
        raise ValueError(
            'qk_norm has no kernels for %d normed lanes in heads of %d of '
            '%s: ask supports() first' % (scale.shape[0], d, x.shape))
    if interpret is None:
        interpret = _interpret_default()
    return _head_norm(x.reshape(rows, width), scale.astype(jnp.float32),
                      float(eps), how, interpret).reshape(x.shape)
