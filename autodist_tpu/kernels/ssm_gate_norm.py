"""Pallas TPU kernels for the tail of a selective state-space layer:
Mamba-2's skip ``D x``, its gate and its norm over groups of lanes,
forward and backward under one ``jax.custom_vjp``.

For ``y`` (the scan's output) and ``x`` (the conv's) ``[batch, seq,
inner]``, ``z`` the ``inner`` columns of ``proj [batch, seq, width]``
(what the in-projection wrote) from ``offset`` on, ``skip`` and ``scale
[inner]`` (f32; ``skip`` is ``D`` repeated over each head's lanes), and
``groups`` groups of ``inner / groups`` lanes, all in f32::

    t = y + skip x          u = t silu(z)
    r = rsqrt(mean over the lane's group of u^2 + eps)
    out = (u r) scale                                     in proj's dtype

which is ``models/core.GatedGroupRMSNorm`` on ``y + D x`` (the gate
INSIDE the norm), the form the kernels are held to in the tests.

**The kernels** (named in a trace). ``ssm_gate_norm_fwd`` walks grid
``(batch, lane step, row block)``; a step holds a tile of ``rows`` rows
by whole groups of lanes (at most :data:`MAX_TILE` lanes where a group
is no wider) of ``y``, of ``x`` and of ``z``, the last read IN PLACE
from ``proj``: its block's index map adds the column offset, which a
multiple of the tile makes a whole number of blocks, as
``kernels/ssm_conv.py`` reads xBC. The body takes :data:`SUB` rows of one
group at a time, in two walks over the group's 128-lane blocks
(:func:`_walk`): the first makes ``u`` in f32, keeps it in VMEM scratch
and adds ``u^2`` up block by block (whole vector registers; one
reduction across the lanes a group and pass), the second reads ``u``
back and writes ``(u r) scale`` rounded. Nothing else is written: no f32
tensor of activation size exists in HBM, and the out-projection's matmul
gets a plain operand in the model's dtype.

``ssm_gate_norm_bwd`` walks the same grid over the same three tiles and
the cotangent ``g`` of the output. It makes ``t``, ``u`` and ``r`` again
on the tile (so nothing of the forward is kept but its inputs, which the
scan's, the conv's and the projection's own backward need anyway), and
with ``w = g scale``::

    du = r w - u r^3 mean(w u)          (the means over the lane's group)
    dy = du silu(z)      dx = skip dy      dz = du t silu'(z)
    d_scale = sum_t g u r               d_skip = sum_t dy x

The first walk of a group keeps ``u``, ``t``, ``silu(z)`` and
``silu'(z)`` in scratch and adds up ``u^2`` and ``w u``; the second
writes ``dy``, ``dx`` (the skip's part of x's cotangent: the scan's own
is added by XLA) and ``dz`` in the model's dtype, and adds the two sums
over the rows into the step's own output block, eight partial rows each
(whole vector registers, no reduction across sublanes): ``f32[batch, row
blocks, 16, inner]``, which XLA adds up; ``d D`` comes by ordinary
autodiff of the repeat. Every product, the sigmoid (``lax.logistic``:
the divide is exact) and ``rsqrt`` are f32 on the tile.

**The gradient is taken with respect to z's columns, not the
projection** (``kernels/ssm_conv.py``'s way): :func:`gate_norm` passes
the columns as a slice of ``proj`` that the forward never reads (XLA
drops it) and the backward answers with ``dz`` as an array of its own,
which XLA lays into the projection's cotangent beside the conv's and
dt's; the array the kernels read is the same ``proj`` with its gradient
stopped.

:func:`supports` says which shapes the kernels take; :func:`gate_norm`
raises on any other: the caller keeps its ``jax.numpy`` form for those
(``models/ssm.py``). On the CPU backend the kernels run in Pallas
interpret mode; every other backend compiles them.
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of the sequence a grid step holds at a tile of MAX_TILE lanes
# (fewer where one group alone is wider: the tile's size is kept), and
# rows a pass of the body computes.
ROWS = 1024
SUB = 64
MAX_TILE = 1024
_LANES = 128
_SUBLANES = 8
# A pass's rows are whole tiles of a bf16 operand.
_ROW_TILE = 16
_VMEM_LIMIT_BYTES = 64 << 20

Plan = collections.namedtuple(
    'Plan', 'block_rows sub_rows steps tile group_lanes z_step')


def _interpret_default():
    return jax.default_backend() == 'cpu'


def plan(seq, width, offset, inner, groups):
    """How the kernels would walk ``[., seq, inner]`` in ``groups``
    groups with z at columns ``offset`` on of ``proj [., seq, width]`` (a
    :class:`Plan`: rows a block, rows a pass, lane steps, lanes a tile,
    lanes a group, the lane step of ``proj`` that z begins at), or ``None``
    where they do not take the shape."""
    if (groups < 1 or inner <= 0 or inner % groups or offset < 0
            or offset + inner > width or (inner // groups) % _LANES):
        return None
    lanes = inner // groups
    # whole groups to a tile: as many as MAX_TILE holds of those counts
    # that z's offset is a whole number of tiles of
    fits = [k for k in range(groups, 0, -1) if groups % k == 0
            and (k == 1 or k * lanes <= MAX_TILE)
            and offset % (k * lanes) == 0]
    if not fits:
        return None
    tile = fits[0] * lanes
    rows = min(ROWS, seq)
    while rows * tile > ROWS * MAX_TILE and rows % (2 * _ROW_TILE) == 0:
        rows //= 2
    sub = min(SUB, rows)
    if seq % rows or rows % sub or sub % _ROW_TILE:
        return None
    return Plan(rows, sub, inner // tile, tile, lanes, offset // tile)


def supports(seq, width, offset, inner, groups):
    """Whether :func:`gate_norm` has kernels for the shape: groups of
    whole lane blocks, z's offset a whole number of tiles, a sequence of
    whole row blocks in passes of whole (16, 128) tiles."""
    return plan(seq, width, offset, inner, groups) is not None


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _block(k):
    """The ``k``-th block of 128 lanes."""
    return pl.ds(pl.multiple_of(k * _LANES, _LANES), _LANES)


def _walk(blocks, body, carry=None):
    """``carry = body(k, carry)`` for each of a group's ``blocks``
    128-lane blocks. A loop and not Python's, so that a body is traced
    once however wide a group is (``kernels/ssm_conv._walk``); unrolled
    when the kernel is lowered: the blocks are independent."""
    return jax.lax.fori_loop(0, blocks, body, carry, unroll=True)


def _for_each_group(rows, sub, tile, width, body):
    """``body(r, of)`` for every pass of ``sub`` of the ``rows`` rows and
    every group of ``width`` of the ``tile`` lanes: ``r`` the pass's
    rows, ``of(k)`` the group's ``k``-th lane block among the tile's."""
    def a_pass(m, _):
        r = pl.ds(pl.multiple_of(m * sub, sub), sub)

        def a_group(g, _):
            body(r, lambda k: _block(g * (width // _LANES) + k))
        jax.lax.fori_loop(0, tile // width, a_group, None)
    jax.lax.fori_loop(0, rows // sub, a_pass, None)


def _f32(ref, rows, lanes):
    return ref[rows, lanes].astype(jnp.float32)


def _across(acc, width):
    """The mean over a group of what ``acc [sub, 128]`` added up block
    by block, on every lane of a block."""
    mean = jnp.sum(acc, axis=-1, keepdims=True) * (1.0 / width)
    return jnp.broadcast_to(mean, acc.shape)


def _fwd_kernel(y_ref, x_ref, z_ref, skip_ref, scale_ref, out_ref, u_ref, *,
                sub, width, eps):
    rows, tile = out_ref.shape
    blocks = width // _LANES

    def a_group(r, of):
        def first(k, squares):
            lanes, at = _block(k), of(k)
            z = _f32(z_ref, r, at)
            t = _f32(y_ref, r, at) + skip_ref[:, at] * _f32(x_ref, r, at)
            u = t * (z * jax.lax.logistic(z))
            u_ref[:, lanes] = u
            return squares + u * u
        squares = _walk(blocks, first, jnp.zeros((sub, _LANES), jnp.float32))
        rs = jax.lax.rsqrt(_across(squares, width) + eps)

        def second(k, carry):
            lanes, at = _block(k), of(k)
            out_ref[r, at] = (u_ref[:, lanes] * rs
                              * scale_ref[:, at]).astype(out_ref.dtype)
            return carry
        _walk(blocks, second)
    _for_each_group(rows, sub, tile, width, a_group)


def _bwd_kernel(y_ref, x_ref, z_ref, g_ref, skip_ref, scale_ref, dy_ref,
                dx_ref, dz_ref, sums_ref, u_ref, t_ref, gate_ref, slope_ref,
                *, sub, width, eps):
    rows, tile = dy_ref.shape
    blocks = width // _LANES
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def fold(run):
        # eight partial rows: whole vector registers added
        return run.reshape(sub // _SUBLANES, _SUBLANES, _LANES).sum(axis=0)

    def a_group(r, of):
        def first(k, carry):
            squares, products = carry
            lanes, at = _block(k), of(k)
            z = _f32(z_ref, r, at)
            t = _f32(y_ref, r, at) + skip_ref[:, at] * _f32(x_ref, r, at)
            sig = jax.lax.logistic(z)
            gate = z * sig
            u = t * gate
            u_ref[:, lanes] = u
            t_ref[:, lanes] = t
            gate_ref[:, lanes] = gate
            slope_ref[:, lanes] = sig * (1.0 + z * (1.0 - sig))
            w = _f32(g_ref, r, at) * scale_ref[:, at]
            return squares + u * u, products + w * u
        zeros = jnp.zeros((sub, _LANES), jnp.float32)
        squares, products = _walk(blocks, first, (zeros, zeros))
        rs = jax.lax.rsqrt(_across(squares, width) + eps)
        back = rs * rs * rs * _across(products, width)

        def second(k, carry):
            lanes, at = _block(k), of(k)
            u, g = u_ref[:, lanes], _f32(g_ref, r, at)
            du = rs * (g * scale_ref[:, at]) - back * u
            dy = du * gate_ref[:, lanes]
            dy_ref[r, at] = dy.astype(dy_ref.dtype)
            dx_ref[r, at] = (dy * skip_ref[:, at]).astype(dx_ref.dtype)
            dz_ref[r, at] = (du * t_ref[:, lanes]
                             * slope_ref[:, lanes]).astype(dz_ref.dtype)
            sums_ref[:_SUBLANES, at] += fold(g * (u * rs))
            sums_ref[_SUBLANES:, at] += fold(dy * _f32(x_ref, r, at))
            return carry
        _walk(blocks, second)
    _for_each_group(rows, sub, tile, width, a_group)


def _specs(plan):
    """By name, the block specs over grid ``(batch, lane step, row
    block)``: a tile of an array of ``inner`` lanes (``part``), the tile
    of z's columns of ``proj`` (``z``), a row of ``skip`` or ``scale``
    (``row``), the backward's sums (``sums``)."""
    rows, tile = plan.block_rows, plan.tile
    return {
        'part': pl.BlockSpec((None, rows, tile), lambda b, j, i: (b, i, j)),
        'z': pl.BlockSpec((None, rows, tile),
                          lambda b, j, i: (b, i, plan.z_step + j)),
        'row': pl.BlockSpec((1, tile), lambda b, j, i: (0, j)),
        'sums': pl.BlockSpec((None, None, 2 * _SUBLANES, tile),
                             lambda b, j, i: (b, i, 0, j)),
    }


def _call(kernel, name, plan, like, ins, outs, out_shape, scratch, eps,
          interpret):
    """The ``pallas_call`` ``name`` of ``kernel`` over ``like``'s batch
    and rows: ``ins`` and ``outs`` name their specs (:func:`_specs`),
    ``scratch`` counts its f32 runs of a pass's rows by a group."""
    bsz, seq, _ = like.shape
    specs = _specs(plan)
    return pl.pallas_call(
        functools.partial(kernel, sub=plan.sub_rows, width=plan.group_lanes,
                          eps=eps),
        grid=(bsz, plan.steps, seq // plan.block_rows),
        in_specs=[specs[kind] for kind in ins],
        out_specs=[specs[kind] for kind in outs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.sub_rows, plan.group_lanes),
                                   jnp.float32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name=name)


# (jitted, as `kernels/ssm_conv.py`'s calls are: a step calls each kernel
# from every Mamba-2 layer, again where the checkpoint and the
# custom-vjp's rules trace it anew; under one `jit` of its own a call is
# traced once a process and shape, whatever the number of sites)
@functools.partial(jax.jit, static_argnames=('eps', 'plan', 'interpret'))
def _forward_call(y, x, proj, skip, scale, eps, plan, interpret):
    out, = _call(
        _fwd_kernel, 'ssm_gate_norm_fwd', plan, y,
        ('part', 'part', 'z', 'row', 'row'), ('part',),
        [jax.ShapeDtypeStruct(y.shape, proj.dtype)], 1, eps, interpret)(
            y, x, proj, skip.reshape(1, -1), scale.reshape(1, -1))
    return out


@functools.partial(jax.jit, static_argnames=('eps', 'plan', 'interpret'))
def _backward_call(y, x, proj, skip, scale, ct, eps, plan, interpret):
    bsz, seq, inner = y.shape
    part = jax.ShapeDtypeStruct(y.shape, proj.dtype)
    dy, dx, dz, sums = _call(
        _bwd_kernel, 'ssm_gate_norm_bwd', plan, y,
        ('part', 'part', 'z', 'part', 'row', 'row'),
        ('part', 'part', 'part', 'sums'),
        [part, part, part, jax.ShapeDtypeStruct(
            (bsz, seq // plan.block_rows, 2 * _SUBLANES, inner),
            jnp.float32)], 4, eps, interpret)(
                y, x, proj, ct, skip.reshape(1, -1), scale.reshape(1, -1))
    d_scale, d_skip = sums.reshape(-1, 2, _SUBLANES, inner).sum(axis=(0, 2))
    return dy, dx, dz, d_skip, d_scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _gate_norm(y, x, z_cols, proj, skip, scale, eps, plan, interpret):
    del z_cols          # what z's gradient is taken with respect to
    return _forward_call(y, x, proj, skip, scale, eps, plan, interpret)


def _gate_norm_fwd(y, x, z_cols, proj, skip, scale, eps, plan, interpret):
    return (_gate_norm(y, x, z_cols, proj, skip, scale, eps, plan,
                       interpret), (y, x, proj, skip, scale))


def _gate_norm_bwd(eps, plan, interpret, res, ct):
    y, x, proj, skip, scale = res
    dy, dx, dz, d_skip, d_scale = _backward_call(
        y, x, proj, skip, scale, ct.astype(proj.dtype), eps, plan,
        interpret)
    return dy, dx, dz, None, d_skip, d_scale


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gate_norm(y, x, proj, skip, scale, offset, groups, eps, interpret=None):
    """``GroupRMSNorm((y + skip x) silu(z)) scale`` (module docstring) in
    ``proj``'s dtype, z the ``inner`` columns of ``proj [B, S, width]``
    from ``offset`` on; ``y`` and ``x [B, S, inner]`` in ``proj``'s
    dtype, ``skip`` and ``scale [inner]``. Through the kernels; a shape
    they do not take (:func:`supports`) raises."""
    inner = y.shape[-1]
    how = plan(proj.shape[1], proj.shape[2], offset, inner, groups)
    if how is None:
        raise ValueError(
            'ssm_gate_norm has no kernels for %d lanes in %d groups with z '
            'at column %d of %s: ask supports() first'
            % (inner, groups, offset, proj.shape))
    if interpret is None:
        interpret = _interpret_default()
    return _gate_norm(
        y.astype(proj.dtype), x.astype(proj.dtype),
        proj[..., offset:offset + inner], jax.lax.stop_gradient(proj),
        skip.astype(jnp.float32), scale.astype(jnp.float32), float(eps),
        how, interpret)
