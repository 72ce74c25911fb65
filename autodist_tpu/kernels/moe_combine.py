"""Pallas TPU kernel that adds the rows of a mixture-of-experts layer
back onto their tokens: ``out[t] = sum over the rows r of token t of
weight * rows[r]``, the layer's combine, by the ORDER the rows lie in
and not by a general scatter-add.

``models/moe.py`` lays its rows out by group (an expert), and inside a
group by token, ascending, no token twice. So for a block of
:data:`TOKEN_BLOCK` tokens and one group, the rows that belong to the
block's tokens are ONE CONTIGUOUS RUN of the buffer, never longer than
the block. The caller says where each pair's row lies (``row_of
[tokens, groups]``, -1 where the token has none in the group); the run
of a (block, group) starts at the smallest of the block's rows there.

The kernel walks the token blocks (the grid) and, inside a block, the
groups. For a (block, group) it copies a fixed WINDOW of
:data:`WINDOW_ROWS` rows, from the run's first row rounded down to a
tile of the buffer in HBM (:data:`_ALIGN` rows), into VMEM; it builds
the one-hot ``[TOKEN_BLOCK, WINDOW_ROWS]`` that says which window row
is which token's (``row_of - window start == column``) and SELECTS the
rows with it on the MXU. A one-hot product in the rows' own dtype with
f32 accumulation selects exactly: each output element is one row's
value plus zeros. The token's f32 weight multiplies the selected row
afterwards, in f32, and the block's sum over the groups is kept in VMEM
in f32 and written once. No atomics, no read-modify-write of the
``[tokens, dim]`` sum, no sort. The copies run ahead of the products,
:data:`_COPIES` windows in flight or in use, from a pair to the
following pairs that have a row, across blocks too: the pairs without
one are passed by, so a call costs what its pairs with rows cost.

A window is a block and a tile long, so the run lies inside it wherever
it starts: there is no second window and no slow path. What a window
holds outside the run (the group's neighbouring rows, padding, the next
group) no one-hot column points at, but it is multiplied by zero, so it
must be FINITE: the caller fills every row below ``limit`` and the
windows stay below it.

On the CPU backend the kernel runs in Pallas interpret mode (it shares
``grouped_matmul._interpret_default``); every other backend compiles
it. :func:`reference` is the same sum through XLA's scatter-add, for
the tests and for comparison on the chip; no model path calls it.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.kernels import grouped_matmul as gm

TOKEN_BLOCK = 128
# Rows of a tile of bf16 in HBM: a copy starts on one.
_ALIGN = 16
WINDOW_ROWS = TOKEN_BLOCK + _ALIGN
# Window copies in flight or in use: one multiplied, the others on their way.
_COPIES = 4
_VMEM_LIMIT_BYTES = 64 << 20


def _windows(row_of, limit, rows):
    """The (token block, group) pairs, flat by block then group: each
    pair's window ``start`` (the run's first row rounded down to
    :data:`_ALIGN`, and held below ``limit - WINDOW_ROWS``: the run still
    lies inside, it ends below ``limit``); its ``ordinal`` among the
    pairs that have a row; and ``nth``, the pair that is the k-th with a
    row (``pairs`` past the last; :data:`_COPIES` longer than the
    pairs)."""
    blocks = row_of.shape[0] // TOKEN_BLOCK
    by_block = row_of.reshape(blocks, TOKEN_BLOCK, -1)
    has = by_block >= 0
    first = jnp.min(jnp.where(has, by_block, rows), axis=1)
    last = jnp.maximum(jnp.minimum(limit, rows) - WINDOW_ROWS, 0)
    start = jnp.clip(first // _ALIGN * _ALIGN, 0, last // _ALIGN * _ALIGN)
    live = jnp.any(has, axis=1).ravel()
    pairs = live.shape[0]
    nth = jnp.sort(jnp.where(live, jnp.arange(pairs), pairs))
    return (start.astype(jnp.int32).ravel(),
            (jnp.cumsum(live) - live).astype(jnp.int32),
            jnp.pad(nth, (0, _COPIES), constant_values=pairs).astype(
                jnp.int32))


def _combine_kernel(start, ordinal, nth, fresh, row_of_ref, *refs, groups,
                    weighted, carried, precision):
    weight_ref = refs[0] if weighted else None
    rows_hbm = refs[weighted]
    sum_ref = refs[weighted + 1] if carried else None
    out_ref, window, sem, acc = refs[-4:]
    pairs = pl.num_programs(0) * groups
    base = pl.program_id(0) * groups
    ahead = _COPIES - 1

    def copy(pair):
        slot = ordinal[pair] % _COPIES
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(start[pair], _ALIGN),
                              WINDOW_ROWS)],
            window.at[slot], sem.at[slot])

    def start_copy(pair):
        @pl.when(pair < pairs)
        def _():
            copy(pair).start()

    @pl.when(base == 0)
    def _():
        for k in range(ahead):
            start_copy(nth[k])

    acc[...] = jnp.where(fresh[0] != 0, 0.0, sum_ref[...]) if carried \
        else jnp.zeros_like(acc)
    row_of = row_of_ref[...]
    group_lane = jax.lax.broadcasted_iota(jnp.int32, row_of.shape, 1)
    column = jax.lax.broadcasted_iota(
        jnp.int32, (TOKEN_BLOCK, WINDOW_ROWS), 1)

    def of_group(table, g):
        """Column ``g`` of a ``[TOKEN_BLOCK, groups]`` table, ``[
        TOKEN_BLOCK, 1]``."""
        return jnp.sum(jnp.where(group_lane == g, table, 0), axis=1,
                       keepdims=True)

    def group(g, carry):
        pair = base + g
        k = ordinal[pair]

        @pl.when(nth[k] == pair)
        def _():
            start_copy(nth[k + ahead])
            copy(pair).wait()
            rows = window[k % _COPIES]
            onehot = (of_group(row_of, g) - start[pair] == column)
            picked = jax.lax.dot_general(
                onehot.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            if weighted:
                picked = picked * of_group(weight_ref[...], g)
            acc[...] += picked
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def combine(rows, row_of, weight=None, limit=None, onto=None, fresh=False,
            out_dtype=None, interpret=None):
    """``out [tokens, dim]`` with ``out[t] = sum over the groups g with
    row_of[t, g] >= 0 of weight[t, g] * rows[row_of[t, g]]`` (module
    docstring), summed in f32 and given in ``out_dtype`` (default: that
    of ``rows``); the call ``moe_combine``. With ``onto [tokens, dim]``
    (f32) the sums are added to it, in place (``input_output_aliases``),
    so a caller adds a layer's rows up over several calls; the result is
    f32 then. Where ``fresh`` (a bool scalar, traced or not) holds,
    ``onto`` is what the sums are written over and is not read: the
    first call of a loop whose calls are one.

    ``rows [n, dim]``; ``row_of [tokens, groups]`` int32, inside a
    group ascending with the token, one row apart (a contiguous run a
    token block); ``weight [tokens, groups]`` f32, or ``None`` for
    weights of one; ``limit`` (int32 scalar, default ``n``): the rows
    below it are finite and every ``row_of`` is below it."""
    n, dim = rows.shape
    tokens, groups = row_of.shape
    if n < WINDOW_ROWS:
        raise ValueError('moe_combine: %d rows are fewer than a window of '
                         '%d' % (n, WINDOW_ROWS))
    if interpret is None:
        interpret = gm._interpret_default()
    out_dtype = jnp.float32 if onto is not None else out_dtype or rows.dtype
    limit = n if limit is None else jnp.reshape(limit, ())
    blocks = -(-tokens // TOKEN_BLOCK)
    spare = blocks * TOKEN_BLOCK - tokens

    def whole_blocks(table, fill=0):
        return jnp.pad(table, ((0, spare), (0, 0)),
                       constant_values=fill) if spare else table
    row_of = whole_blocks(row_of, -1)
    tables = [row_of]
    if weight is not None:
        tables.append(whole_blocks(weight.astype(jnp.float32)))
    scalars = _windows(row_of, limit, n) + (
        jnp.reshape(fresh, (1,)).astype(jnp.int32),)
    operands = tables + [rows] + ([onto] if onto is not None else [])
    # f32 rows (the tests') are selected exactly only at full precision
    precision = jax.lax.Precision.HIGHEST \
        if rows.dtype == jnp.float32 else None

    def block(i, *_):
        return i, 0

    def sum_so_far(i, start, ordinal, nth, fresh):
        # over a fresh sum every step holds block 0: nothing is fetched
        return jnp.where(fresh[0] != 0, 0, i), 0
    by_block = pl.BlockSpec((TOKEN_BLOCK, groups), block)
    return pl.pallas_call(
        functools.partial(_combine_kernel, groups=groups,
                          weighted=weight is not None,
                          carried=onto is not None, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(blocks,),
            in_specs=[by_block] * len(tables)
            + [pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((TOKEN_BLOCK, dim), sum_so_far)]
            * (onto is not None),
            out_specs=pl.BlockSpec((TOKEN_BLOCK, dim), block),
            scratch_shapes=[pltpu.VMEM((_COPIES, WINDOW_ROWS, dim),
                                       rows.dtype),
                            pltpu.SemaphoreType.DMA((_COPIES,)),
                            pltpu.VMEM((TOKEN_BLOCK, dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, dim), out_dtype),
        # operands count the prefetched scalars: onto is the last
        input_output_aliases={len(scalars) + len(operands) - 1: 0}
        if onto is not None else {},
        # the copies chain from one grid step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name='moe_combine',
    )(*scalars, *operands)


def unwritten(rows, dim, dtype, interpret=None):
    """A ``[rows, dim]`` buffer that nothing has written (the call
    ``moe_rows_buffer``, a kernel without a body: no pass over the
    memory to clear it). For a caller that writes the rows it will read:
    :func:`combine` reads none from ``limit`` on."""
    if interpret is None:
        interpret = gm._interpret_default()
    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct((rows, dim), dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), interpret=interpret,
        name='moe_rows_buffer')()


def reference(rows, row_of, weight=None, out_dtype=None):
    """:func:`combine` through XLA's scatter-add (what the layer ran
    before the kernel)."""
    tokens, groups = row_of.shape
    token = jnp.repeat(jnp.arange(tokens), groups)
    at = row_of.ravel()
    picked = jnp.take(rows, jnp.maximum(at, 0), axis=0).astype(jnp.float32)
    if weight is not None:
        picked = picked * weight.astype(jnp.float32).ravel()[:, None]
    out = jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
        jnp.where((at >= 0)[:, None], picked, 0.0))
    return out.astype(out_dtype or rows.dtype)
