"""Pallas TPU grouped matrix products over row groups: what a
mixture-of-experts layer multiplies once its rows are ordered by expert.

``lhs [m, k]`` holds rows in groups, one group an expert, and ``rhs [g,
k, n]`` a matrix a group. The rows are laid out in TILES of
:data:`TILE_ROWS`: a group starts on a tile and is padded to whole
tiles (``models/moe.py`` orders its rows so), which makes every tile
one group's. A call is told each tile's group (``tile_group [m /
TILE_ROWS]``, int32) and how many leading tiles are live (``live``,
int32 ``[1]``); both are scalar-prefetched, so the index maps pick the
group's matrix for a tile and the tiles past the live ones are skipped:
their grid steps fetch nothing (the index maps hold the last live
tile's blocks) and compute nothing, and THE TIME OF A CALL FOLLOWS THE
LIVE ROWS, not the buffer. Rows of tiles that are not live are left
unwritten: a caller masks them.

Three calls, named in a trace:

* ``moe_gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g]``, ``[m, n]``;
* ``moe_gmm_dx``: the same with the group's matrix transposed (``rhs [g,
  n, k]`` read as it lies, contracted over its last dimension): the
  gradient w.r.t. ``lhs`` from the gradient w.r.t. ``out``;
* ``moe_gmm_dw``: ``acc[g] += lhs[rows of g]^T @ rhs[rows of g]``, a
  group at a time, into an f32 ``acc [g, k, n]`` that comes in and goes
  out in place (``input_output_aliases``): groups with no live tile in
  the call are not touched, so a caller adds a layer's rows up over
  several calls.

The grid walks the row tiles innermost, so a group's matrix (or its
``acc`` block) stays in VMEM over the group's tiles. ``k`` is not
tiled: the whole contraction of a row tile is one block (2304 and 896
wide in Mellum2's experts: 1.2 MB of bf16 for 256 rows).

On the CPU backend the kernels run in Pallas interpret mode; every other
backend compiles them. A ``pallas_call`` is opaque to GSPMD: under a
mesh that shards the operands a caller runs the kernels on each device's
shard in a manual region (``models/moe.py`` does). :func:`reference` and
:func:`reference_dw` are the same arithmetic through
``jax.lax.ragged_dot``, for the tests and for comparison on the chip;
no model path calls them.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 256
_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20
# Widest block of an output dimension: 896 of Mellum2's 1792 (gate and
# up) and 768 of its 2304.
_MAX_BLOCK = 1024


def _block(dim, limit=_MAX_BLOCK):
    """The widest multiple of 128 that divides ``dim`` and is no wider
    than ``limit``; ``dim`` itself where it is no wider, or has none."""
    if dim <= limit:
        return dim
    return max((b for b in range(_LANES, limit + 1, _LANES)
                if dim % b == 0), default=dim)


def _interpret_default():
    return jax.default_backend() == 'cpu'


def _live_tile(m, live):
    """Row tile ``m``, or for a tile past the live ones the last live
    one, whose blocks the pipeline holds already."""
    return jnp.minimum(m, jnp.maximum(live[0] - 1, 0))


def _gmm_kernel(tile_group, live, lhs_ref, rhs_ref, out_ref, *, transposed):
    del tile_group

    @pl.when(pl.program_id(1) < live[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transposed else \
            (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def gmm(lhs, rhs, tile_group, live, transposed=False, interpret=None):
    """``out [m, n]`` with ``out[tile] = lhs[tile] @ rhs[tile_group[tile]]``
    for the ``live`` leading tiles (module docstring); ``transposed``:
    ``rhs [g, n, k]``, the call ``moe_gmm_dx``."""
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tn = _block(n)
    if interpret is None:
        interpret = _interpret_default()

    def rows(j, i, tile_group, live):
        return _live_tile(i, live), 0

    def matrix(j, i, tile_group, live):
        g = tile_group[_live_tile(i, live)]
        return (g, j, 0) if transposed else (g, 0, j)

    def out(j, i, tile_group, live):
        return _live_tile(i, live), j
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // TILE_ROWS),
            in_specs=[pl.BlockSpec((TILE_ROWS, k), rows),
                      pl.BlockSpec((None, tn, k) if transposed
                                   else (None, k, tn), matrix)],
            out_specs=pl.BlockSpec((TILE_ROWS, tn), out)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name='moe_gmm_dx' if transposed else 'moe_gmm',
    )(tile_group, live, lhs, rhs)


def _dw_kernel(tile_group, live, lhs_ref, rhs_ref, acc_ref, out_ref, sum_ref):
    m, tiles = pl.program_id(2), pl.num_programs(2)
    n_live = live[0]
    group = tile_group[_live_tile(m, live)]

    @pl.when(m < n_live)
    def _():
        first = jnp.logical_or(
            m == 0, tile_group[jnp.maximum(m - 1, 0)] != group)
        last = jnp.logical_or(
            m == n_live - 1,
            tile_group[jnp.minimum(m + 1, tiles - 1)] != group)

        @pl.when(first)
        def _():
            sum_ref[...] = jnp.zeros_like(sum_ref)
        sum_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...] + sum_ref[...]

    # nothing live: the one block the grid holds goes back as it came
    @pl.when(jnp.logical_and(n_live == 0, m == 0))
    def _():
        out_ref[...] = acc_ref[...]


def gmm_dw(lhs, rhs, tile_group, live, acc, interpret=None):
    """``acc [g, k, n]`` (f32) with ``lhs[tile]^T @ rhs[tile]`` of every
    live tile added to its group's matrix, in place (``moe_gmm_dw``)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = _block(k), _block(n)
    if interpret is None:
        interpret = _interpret_default()

    def lhs_rows(i, j, t, tile_group, live):
        return _live_tile(t, live), i

    def rhs_rows(i, j, t, tile_group, live):
        return _live_tile(t, live), j

    def matrix(i, j, t, tile_group, live):
        return tile_group[_live_tile(t, live)], i, j
    block = pl.BlockSpec((None, tk, tn), matrix)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // TILE_ROWS),
            in_specs=[pl.BlockSpec((TILE_ROWS, tk), lhs_rows),
                      pl.BlockSpec((TILE_ROWS, tn), rhs_rows),
                      block],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        # operands count the two prefetched scalars: acc is the fifth
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name='moe_gmm_dw',
    )(tile_group, live, lhs, rhs, acc)


# ---------------------------------------------------------------------------
# the same through jax.lax.ragged_dot (tests and comparison only)
# ---------------------------------------------------------------------------

def _group_rows(tile_group, live, groups):
    """Rows of each group among the live tiles (whole tiles)."""
    tiles = jnp.arange(tile_group.shape[0])
    return TILE_ROWS * jnp.sum(
        jnp.logical_and(tile_group[:, None] == jnp.arange(groups)[None, :],
                        tiles[:, None] < live[0]), axis=0, dtype=jnp.int32)


def reference(lhs, rhs, tile_group, live, transposed=False):
    """:func:`gmm` through ``jax.lax.ragged_dot`` (the rows past the
    live tiles come out as zeros)."""
    if transposed:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return jax.lax.ragged_dot(
        lhs, rhs, _group_rows(tile_group, live, rhs.shape[0]),
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def reference_dw(lhs, rhs, tile_group, live, acc):
    """:func:`gmm_dw` as the transpose of ``jax.lax.ragged_dot``."""
    sizes = _group_rows(tile_group, live, acc.shape[0])
    _, vjp = jax.vjp(lambda w: jax.lax.ragged_dot(
        lhs.astype(jnp.float32), w, sizes), jnp.zeros(acc.shape, jnp.float32))
    return acc + vjp(rhs.astype(jnp.float32))[0].astype(acc.dtype)
