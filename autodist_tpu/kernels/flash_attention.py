"""Pallas TPU flash attention: block-tiled exact attention, fwd + bwd.

The reference has no attention kernels at all (its models are TF graphs;
SURVEY.md §2.3 lists no TP/SP) — this is TPU-native greenfield, the block
primitive promised by parallel/ring_attention.py. Algorithm is the public
flash-attention-2 recipe: the score matrix is never materialized in HBM;
each (Q-block × KV-block) tile runs on the MXU, softmax statistics stay
in f32, and the backward pass recomputes P from the saved logsumexp
instead of storing it.

Layout: q, k, v, ``do`` are read from and ``o``, dq, dk, dv written to
``[batch, seq, heads * head_dim]``, the layout the qkv projection writes
and the output projection reads: lane-dense, unpadded, and nothing is
transposed or copied between a projection and a kernel. q, k and v may
be three arrays or the three thirds of the projection's one
``[b, s, 3 * h * d]`` (an index map each over the same array), and dq,
dk, dv then land in one such array: ``flash_dq`` writes its first
third, ``flash_dkv`` takes that array in and writes dk into the second
in place (``input_output_aliases``), and dv goes over the last.
Three ``pallas_call``s (``flash_fwd``, ``flash_dq``, ``flash_dkv``; the
benchmark reads them by these names) share one grid shape, (batch,
heads / G, outer blocks, inner blocks): a grid step holds the ``G * d``
lanes of G heads of one tile and walks them in an unrolled loop, so the
fixed cost of a step is paid once for G tiles and the scheduler can
fill one head's softmax with the next one's matmuls. The inner
dimension is sequential ("arbitrary") and carries the VMEM
accumulators; the rest are parallel.

Heads in the lanes (:func:`_lane_block`): a step's lanes are whole LANE
BLOCKS of ``max(128, head_dim)`` lanes: one head of 128 lanes or more,
two heads at head_dim 64, four at 32. A head's tile is taken out of its
block with no lane slice (a [., 64] slice at lane 64 is a lane rotate
for every operand and a masked store for every result: 15-25% slower
on a v5e, PERF.md §6, PR 29). Instead the OTHER heads' lanes of one
operand of each contraction over the lanes are zeroed with a ``where``
on a lane iota (q for ``q k^T``, ``do`` for ``do v^T``; k and v for the
transposed tiles of ``flash_dkv``), and the contraction runs over all
128 lanes: the same passes of a 128-deep MXU as over 64. A matmul
against the block's lanes (``p v``, ``ds k``, ``p^T do``, ``ds^T q``)
yields [., 128] of which the head's own 64 columns mean something; the
heads' results are ``where``d together and stored once, lane-dense.
The behaviour follows ``head_dim``, which the code sees in its input.

What a step computes is chosen by Python ``if``s on the static plan
(:func:`_plan`: block sizes and G per kernel, from ``seq``, ``causal``
and the local head count), one body per kernel:

* **One inner block** (``nk == 1`` for fwd/dq, ``nq == 1`` for dkv):
  the step owns a whole row of tiles, so a plain max-subtracted softmax
  writes ``o`` and ``lse`` (or the gradients) straight from it: no
  running max, no rescale, no scratch. Under a causal mask a step
  computes only the key (for dkv: query) ranges that hold an unmasked
  position: the square the diagonal crosses, masked, and what lies
  below it, with no mask (:func:`_for_the_live_row`).
* **Several inner blocks**: online softmax / accumulation in VMEM
  scratch. Causal tiles come in three kinds, told from ``qi``, ``ki``
  and the block sizes: dead (above the diagonal: skipped, its index map
  clamped to the nearest live block so nothing is fetched), crossed by
  the diagonal (masked) and below it (no mask at all)
  (:func:`_for_each_tile_kind`).
* The row statistics ``lse`` and ``delta`` live in HBM as
  ``[b, h, 1, s]``, the sequence along the lanes: as ``[b, h, s, 1]``
  XLA lays them out ``T(8, 128)``, 128-fold padded (403 MB for
  ``[96, 16, 512, 1]`` f32). ``flash_dkv`` computes the TRANSPOSED tile
  ``k q^T`` ([bk, bq]), where they broadcast down the sublanes and
  ``dv = p^T do``, ``dk = ds^T q`` are plain matmuls; ``flash_fwd`` and
  ``flash_dq`` turn the row to a column once a head and step.
* ``delta = rowsum(dO * O)`` of each head is made by ``flash_dq``, from
  the blocks of ``do`` and ``o`` it holds anyway (``o`` is one operand
  more), and handed to ``flash_dkv`` as ``[b, h, 1, s]``. With ``do``
  and ``o`` in the kernels' layout XLA has no cheap way to it: a
  reduce over 64-lane groups whose result has ``s`` along the lanes
  cost it an f32 copy of the whole product (PERF.md §6, PR 29).
* A **band call** (``window = (left, right)``: query i sees keys
  i - left .. i + right) is planned from the band and not from the
  sequence, in one of two forms that follow the band's reach
  (:func:`_band_form`; the ``flash.plan`` tag ``band_form``). The three
  calls are named ``flash_fwd_band``, ``flash_dq_band``,
  ``flash_dkv_band`` in both; with ``window=None`` nothing of either is
  on the path.

  - ``'row'``, a band that reaches no further than a lane block to
    either side (``max(window) <= 128``: ModernBERT's window layers, 64
    keys each side): ONE pass over each row block's own keys. The keys
    a block of rows can see are its own and a corner of each
    neighbour, which a grid step holds as three operands of the same
    array (the section "a narrow band" below): no inner grid
    dimension, no scratch, no online softmax, one score block of
    ``[sub, sub + 2 corner]`` a head and sub-block. The tiled walk
    spent 2-3 times the elements there, each under the online
    softmax's bookkeeping (PERF.md §6, PR 40).
  - ``'tiles'``, a wider band (Mellum2's causal window of 1024 keys),
    and a narrow one the row form has no body for (grouped kv heads, a
    sequence that is no multiple of 128, block sizes asked for by
    hand): the tiled walk under the online softmax with a shorter
    inner grid dimension. Blocks as wide as the band reaches to one
    side (:func:`_band_targets`), an inner dimension as long as the
    longest run of inner blocks an outer block's band reaches
    (:func:`_band_inner_blocks`), index maps that walk that run and
    clamp the steps outside the sequence onto a block the pipeline
    already holds (:func:`_band_fetch`), and the band's mask only on
    tiles an edge of the band crosses. Its tiles are many times the
    band's edge, and the row form has nothing to give it.
* A softmax scale that is a power of two (head_dim 16, 64, 256) is
  folded into ``q`` ([bq, d]) instead of multiplying every [bq, bk]
  tile, which is exact in any binary float format; any other scale
  stays on the tile.
* **Rotary positions** (``flash_attention_merged(..., rotary=(cos,
  sin))``; ModernBERT's layers): the tables of :func:`rotary_tables`,
  ``[seq, lanes of a lane block]`` f32 (a head's ``[seq, d]`` repeated
  over the block's heads; 4 MB each at seq 8192), are operands of each
  of the six calls, each twice: blocked ``(rows, lanes)`` by the row
  index maps of the q and of the k operand (``_outer``, ``_kv_row``,
  ``_band_fetch``, whatever the batch and the head group). Every body
  rotates the q and k blocks it loads, on the tile in VMEM and before
  anything else touches them (:func:`_reads`): ``x cos + turn(x) sin``
  in f32, rounded ONCE to the operands' dtype, then the scale's fold
  and the other heads' mask as without tables. ``turn`` is the
  rotate-half of each head's ``d`` lanes inside the lane block
  (:func:`_turn`: two ``pltpu.roll`` along the lanes, a select on
  ``lane % d < d / 2``, the sign; no lane slice). ``flash_fwd``
  rotates q and k; ``flash_dq`` rotates q and k, accumulates the
  gradient w.r.t. the ROTATED q and turns it back (``dq cos - turn(dq)
  sin``, the rotation's transpose) from the f32 accumulator before its
  one store; ``flash_dkv`` rotates k and q and turns dk back the same
  way; v, ``do``, ``o``, dv, ``lse`` and ``delta`` never meet the
  tables. So the model hands over the projection's ``[b, s, 3 * h *
  d]`` as it is, rotary or not, the cotangent is one array written in
  place, and the step holds no rotated copy of q and k. Chosen by a
  Python ``if`` on whether tables were handed in: without them nothing
  of it is on the path (the table-less calls' Mosaic modules are
  pinned op for op in ``tests/test_tpu_bringup.py``).

Where the time goes at head_dim 64 on a v5e (PERF.md §6, PR 25 and 29):
QK^T contracts over one head's 64 lanes and PV yields one head's 64
columns whatever the layout (``p`` differs by head), so every matmul
fills half of the 128 x 128 MXU. The backward pair runs within 5% of
that half-filled-MXU bound at seq 512; the forward is bound by neither
the MXU nor its DMAs (halving its bytes did not move it) but by the
softmax's elementwise work.

* **Latent attention** (``flash_attention_latent``: a q/k head of a
  128-lane part of its own beside a narrower rotary part whose key is
  ONE for all heads, a v head of another width; ``flash_fwd_mla``,
  ``flash_dq_mla``, ``flash_dkv_mla``) is a section of its own at the
  end of the file, with bodies of its own over the helpers above: with
  the other entry points nothing of it is on the path.

On the CPU backend the same kernels run in Pallas interpret mode, so the
CPU test mesh exercises the identical code path (tests/test_flash_attention.py);
every other backend compiles them.
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry

NEG_INF = -1e30   # same masking constant as parallel/ring_attention.py
_LANES = 128      # TPU lane width


def _pick_block(seq, target):
    for b in (target, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and seq % b == 0 and b <= seq:
            return b
    return None


def _block_targets(seq, causal, window=None):
    """(q-block, kv-block) targets of each kernel; a sequence no longer
    than a target is one block. From sweeps on a v5e at head_dim 64
    (my chip runs, PR 25; PERF.md §6). A causal row of up to 1024 keys
    is one inner block, so that each kernel computes only the live
    ranges of its outer block; the outer block trades masked work
    (smaller is less) against steps and branches (larger is fewer), and
    each kernel settles elsewhere. Otherwise the forward is fastest
    with 1024 x 1024 tiles (the whole row as far as that: the online
    softmax costs more than the tiles it would skip), the backward pair
    at 512 x 512. A band call (``window``) is sized to the band and not
    to the sequence (:func:`_band_targets`)."""
    if window is not None:
        return _band_targets(window)
    if causal and seq <= 1024:
        return {'fwd': (512, 1024), 'dq': (256, 1024), 'dkv': (1024, 512)}
    return {'fwd': (1024, 1024), 'dq': (512, 512), 'dkv': (512, 512)}


# Base targets of the tiled walk of a band (:func:`_band_targets` scales
# them up for a wide one), from a sweep of {128, 256, 512}^2 at
# ModernBERT's reach, [4, 16, 8192, 64] on a v5e (my chip runs, PR 26),
# when that band took this walk: 3.54 / 2.39 / 2.35 ms a call, forward
# / dq / dkv; a tile cost about 0.19 us a head whatever its size and an
# element of it 7.6 ps. A band of that reach takes the row form since
# PR 40 (``_ROW_TARGETS`` below has its sweep); what is left to these
# is a wide band's base, and a narrow band the row form has no body for.
_BAND_TARGETS = {'fwd': (128, 512), 'dq': (256, 256), 'dkv': (128, 256)}


# No tile of a wide band's call grows past its kernel's cap here.
# Measured on a v5e at Mellum2's band (a causal window of 1024 keys: a
# reach of 1023; 32 query heads over 4 kv heads of 128, 4 x 8192
# positions, rotary inside; my chip runs, PR 33; PERF.md §6), ms a call
# with every kernel's tiles capped at 256 / 512 / 1024 a side: the
# forward 11.37 (256 x 512) / 12.11 / 9.58, the backward pair 16.53 /
# 17.06 / 23.72. The forward is bound by its softmax's elementwise work
# and gains from fewer, larger steps though a 1024 x 1024 tile on the
# band's edge is half dead; the backward kernels pay for every dead
# element with matmuls and settle at the smallest tile.
_BAND_MAX_BLOCK = {'fwd': 1024, 'dq': 256, 'dkv': 256}


def _band_targets(window):
    """Block targets of a band call: the measured targets for a band
    that reaches up to a lane-wide block (128) to either side, scaled up
    by powers of two for a wider one as far as the kernel's
    ``_BAND_MAX_BLOCK``, so that an outer block meets a few inner blocks
    and the tiles stay close to the band's width."""
    reach = max(window)
    scale = 1
    while scale * _LANES < reach:
        scale *= 2
    return {kernel: (min(scale * bq, max(bq, _BAND_MAX_BLOCK[kernel])),
                     min(scale * bk, max(bk, _BAND_MAX_BLOCK[kernel])))
            for kernel, (bq, bk) in _BAND_TARGETS.items()}


# Score-tile elements (G x bq x bk) one grid step may hold; its f32
# temporaries (s, p, dp, ds) are 4 bytes each of that, and the unrolled
# head loop is compiled G times. 8 heads a step ran 1-2% faster than 4
# at 512 x 512 and took twice as long to compile (my chip runs, PR 25).
_STEP_TILE_ELEMS = 1 << 20
_MAX_HEADS_PER_STEP = 8
# The default scoped VMEM (16 MiB) is short of a 1024 x 1024 f32 tile
# with its exp and bf16 copy; a v5e core has 128 MiB.
_VMEM_LIMIT_BYTES = 64 << 20


def _lane_block(heads, head_dim):
    """Lanes of a block of the ``[b, s, heads * head_dim]`` layout: one
    head of 128 lanes or more, else the heads that fill 128 lanes (two
    at head_dim 64, four at 32). Heads that do not tile the lanes that
    way (``supports``: a model of under 128 lanes in all, an odd head
    count at head_dim 64) are one block, the whole minor dimension."""
    if head_dim % _LANES == 0:
        return head_dim
    if _LANES % head_dim == 0 and heads * head_dim % _LANES == 0:
        return _LANES
    return heads * head_dim


def _whole_blocks(heads, per_block, target):
    """The most heads, up to ``target``, that are whole lane blocks
    (``per_block`` heads each) and divide the (local) head count; one
    block at least."""
    blocks = heads // per_block
    return per_block * max(c for c in range(1, blocks + 1) if blocks % c == 0
                           and c * per_block <= max(target, per_block))


def _heads_per_step(heads, bq, bk, per_block=1, least=1):
    """Heads a grid step holds: whole lane blocks that divide the head
    count, as many as stay inside the step budget (``least`` of them
    whatever the budget) and ``_MAX_HEADS_PER_STEP``."""
    return _whole_blocks(heads, per_block, max(least, min(
        _MAX_HEADS_PER_STEP, _STEP_TILE_ELEMS // (bq * bk))))


Blocks = collections.namedtuple('Blocks', 'block_q block_k heads_per_step')
Plan = collections.namedtuple('Plan', 'fwd dq dkv')   # a Blocks each


def _tile_live(qi, ki, bq, bk):
    """False only for tiles strictly above the causal diagonal
    (fully masked -> safe to skip)."""
    return qi * bq + bq - 1 >= ki * bk


def _tile_crossed(qi, ki, bq, bk):
    """True where some position of the tile lies above the diagonal."""
    return ki * bk + bk - 1 > qi * bq


# A band call (``window = (left, right)``: query i sees keys i - left ..
# i + right) walks, for each outer block, only the inner blocks the band
# reaches: grid step ``j`` of the inner dimension is inner block
# ``_band_first + j``, and the inner dimension is as long as the longest
# such run. ``back`` and ``ahead`` are the band's reach from the outer
# block towards lower and higher positions: (left, right) where queries
# are the outer blocks, (right, left) for ``flash_dkv``.

def _band_reach(window, transposed):
    left, right = window
    return (right, left) if transposed else (left, right)


def _band_first(outer, size, inner_size, back):
    """First inner block the band reaches from outer block ``outer``;
    negative where the band starts before the sequence does."""
    return (outer * size - back) // inner_size


def _band_last(outer, size, inner_size, ahead):
    return (outer * size + size - 1 + ahead) // inner_size


def _band_inner_blocks(seq, size, inner_size, window, transposed):
    """Length of the inner grid dimension of a band call."""
    back, ahead = _band_reach(window, transposed)
    return max(_band_last(o, size, inner_size, ahead)
               - _band_first(o, size, inner_size, back) + 1
               for o in range(seq // size))


def _band_tile_live(qi, ki, bq, bk, seq, window):
    """Whether tile (qi, ki) is inside the sequence and holds a pair of
    the band."""
    left, right = window
    return ((ki >= 0) & (ki < seq // bk) & (qi >= 0) & (qi < seq // bq)
            & (ki * bk + bk - 1 >= qi * bq - left)
            & (ki * bk <= qi * bq + bq - 1 + right))


def _band_tile_crossed(qi, ki, bq, bk, window):
    """True where some pair of the tile lies outside the band."""
    left, right = window
    return ((ki * bk < qi * bq + bq - 1 - left)
            | (ki * bk + bk - 1 > qi * bq + right))


def _tile_counts(seq, bq, bk, causal, window=None, transposed=False):
    """(tiles, live, masked) of one (batch, head): tiles in the grid,
    those that hold an unmasked position, and those of the live ones the
    causal diagonal, or an edge of the band, crosses (which need the
    mask). The grid of a band call holds the band's tiles only."""
    if window is not None:
        size, inner = (bk, bq) if transposed else (bq, bk)
        back, _ = _band_reach(window, transposed)
        grid = [(o, _band_first(o, size, inner, back) + j)
                for o in range(seq // size) for j in range(
                    _band_inner_blocks(seq, size, inner, window,
                                       transposed))]
        if transposed:
            grid = [(qi, ki) for ki, qi in grid]
        live = [t for t in grid
                if _band_tile_live(*t, bq, bk, seq, window)]
        return len(grid), len(live), sum(
            bool(_band_tile_crossed(*t, bq, bk, window)) for t in live)
    grid = [(qi, ki) for qi in range(seq // bq) for ki in range(seq // bk)]
    if not causal:
        return len(grid), len(grid), 0
    live = [t for t in grid if _tile_live(*t, bq, bk)]
    return len(grid), len(live), sum(_tile_crossed(*t, bq, bk) for t in live)


# A block-diffusion call (``block_diffusion = B``; BD3-LM, arXiv:
# 2503.09573) is the third description of live pairs: the ``2 L`` rows of
# a sequence are a noised copy ``x_t`` (rows ``0 .. L - 1``) and the clean
# one ``x_0`` (rows ``L .. 2 L - 1``), row ``i`` of either in block ``i //
# B``, and query row ``r`` sees key row ``c`` iff
#
#   r in x_t, c in x_t:  blk(c) == blk(r)     (a noised block sees itself)
#   r in x_t, c in x_0:  blk(c) <  blk(r)     (and the clean earlier blocks)
#   r in x_0, c in x_0:  blk(c) <= blk(r)     (the clean copy: block-causal)
#   r in x_0, c in x_t:  never
#
# ``L^2 + L B`` pairs, a quarter of the square. The three kernels take
# SQUARE tiles of ``t`` rows (``B | t | L``), ``n = L / t`` a half, so a
# tile lies in one quadrant and the mask crosses only the tiles of equal
# index in their halves (three diagonals of the ``2 n x 2 n`` square):
# there it is two shifts and two compares on the tile's own iotas. A
# query tile ``i`` of either half walks the clean key tiles ``0 .. i``
# (inner steps ``0 .. n - 1``; those past ``i`` are dead and fetch
# nothing) and, at inner step ``n``, the noised tile ``i`` (dead for a
# clean query tile): one online softmax over both sources. A noised row
# of the sequence's first block meets no clean key at all; its running
# max stays NEG_INF through the clean tiles and the noised tile's real
# max wipes out what they left, as in a band call. Transposed
# (``flash_dkv_bd``): a clean key tile ``i`` takes the query tiles ``i ..
# n - 1`` of BOTH halves, a noised key tile its own query tile alone; the
# inner dimension walks all ``2 n`` query tiles, the noised first. Each
# kernel has a ``t`` of its own (``_BD_TARGETS``): everything above is by
# the kernel's own tile, and what passes between them is by row.

Bd = collections.namedtuple('Bd', 'block tiles')   # B, tiles a half


def _bd_of(block_diffusion, seq, size):
    """The :class:`Bd` of a kernel whose square tiles are ``size`` rows,
    over ``seq = 2 L`` rows; None without the mask."""
    if block_diffusion is None:
        return None
    return Bd(block_diffusion, seq // 2 // size)


def _bd_inner(outer, j, n, transposed=False):
    """The inner tile of grid step ``j``: a query tile's clean key tile
    ``j`` and, at ``j == n``, the noised tile of the query tile's own
    index; transposed, query tile ``j`` of the ``2 n``."""
    if transposed:
        return j
    return jnp.where(j == n, outer % n, n + j)


def _bd_tile_live(qi, ki, n):
    """Whether tile (qi, ki) of the ``2 n x 2 n`` square holds a live
    pair: a clean key tile up to the query tile's index in its half, or
    a noised query tile's own noised key tile."""
    return ((ki >= n) & (ki - n <= qi % n)) | ((ki < n) & (ki == qi))


def _bd_tile_crossed(qi, ki, n):
    """True for the live tiles the mask crosses: those of equal index in
    their halves."""
    return ki % n == qi % n


def _bd_tile_counts(n):
    """(tiles of the square, live ones, those of them the mask crosses)
    of one (batch, head): ``n (n + 1) / 2`` clean tiles for either half
    of the queries and the ``n`` noised ones."""
    return 4 * n * n, n * n + 2 * n, 3 * n


def _bd_mask(qi, ki, size, bd, transposed=False):
    """Boolean tile of the block-diffusion mask on a crossed tile
    ``[size, size]`` (rows are keys where ``transposed``): with ``diff``
    the key's block less the query's, ``== 0`` noised on noised, ``< 0``
    noised on clean, ``<= 0`` clean on clean, as one pair of bounds
    chosen by the tile's quadrant."""
    block, n = bd
    shift = block.bit_length() - 1
    q_dim = 1 if transposed else 0
    blk = [jax.lax.shift_right_logical(jax.lax.broadcasted_iota(
        jnp.int32, (size, size), dim), shift) for dim in (q_dim, 1 - q_dim)]
    diff = blk[1] - blk[0]
    k_noised = ki < n
    lo = jnp.where(k_noised, 0, -size)
    hi = jnp.where(jnp.logical_and(qi < n, jnp.logical_not(k_noised)), -1, 0)
    return jnp.logical_and(diff >= lo, diff <= hi)


def _for_each_bd_tile(tile, qi, ki, size, bd, transposed=False):
    """:func:`_for_each_tile_kind` under the block-diffusion mask."""
    is_live = _bd_tile_live(qi, ki, bd.tiles)
    crossed = _bd_tile_crossed(qi, ki, bd.tiles)

    @pl.when(jnp.logical_and(is_live, crossed))
    def _():
        tile(_bd_mask(qi, ki, size, bd, transposed))
    if bd.tiles > 1:
        @pl.when(jnp.logical_and(is_live, jnp.logical_not(crossed)))
        def _():
            tile(None)


def block_diffusion_mask(rows, block):
    """The block-diffusion mask as a boolean ``[rows, rows]`` array
    (``rows = 2 L``: the noised copy, then the clean one), straight from
    the four rules: what the XLA path masks its scores with
    (``parallel/ring_attention.local_flash_attention(mask=...)``: short
    sequences, the CPU tests) and the kernels' witness."""
    half = rows // 2
    at = jnp.arange(rows)
    clean, blk = at >= half, (at % half) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return jnp.where(
        q_clean, k_clean & (k_blk <= q_blk),
        jnp.where(k_clean, k_blk < q_blk, k_blk == q_blk))


def check_block_diffusion(block_diffusion, causal=False, window=None):
    """``block_diffusion`` as an int, or None. The mask is a description
    of live pairs of its own: beside ``causal`` or a ``window`` it
    raises."""
    if block_diffusion is None:
        return None
    block = int(block_diffusion)
    if block < 1:
        raise ValueError('flash_attention: block_diffusion=%r must be a '
                         'positive block length' % (block_diffusion,))
    check_window(window, causal, block)
    if causal:
        raise ValueError(
            'flash_attention: block_diffusion=%d beside causal=True: the '
            'block-diffusion mask is the third description of live pairs '
            '(after causal and the band) and holds its own mask, the clean '
            'copy\'s block-causal one included; give causal=False'
            % block)
    return block


def check_window(window, causal=False, block_diffusion=None):
    """``window`` as a pair of ints, or None. Under a causal mask the
    band is ``(left, 0)``: query i sees keys i - left .. i, and the band
    holds the mask (a caller then runs the band call with no causal
    flag beside it). A band beside the block-diffusion mask raises: the
    two are descriptions of the same thing."""
    if window is None:
        return None
    if block_diffusion is not None:
        raise ValueError(
            'flash_attention: window %r beside block_diffusion=%r: the '
            'block-diffusion mask is the third description of live pairs '
            '(after causal and the band) and holds its own mask; give one '
            'of the two' % (window, block_diffusion))
    left, right = (int(w) for w in window)
    if left < 0 or right < 0:
        raise ValueError('flash_attention: window %r must be (left, right) '
                         'with neither negative' % (window,))
    return (left, 0) if causal else (left, right)


def supports(shape, block=128, window=None, kv_heads=None,
             block_diffusion=None):
    """Whether flash_attention can run for [B, H, S, D], with or without
    a ``window``: S divisible into >=8-row blocks, and heads that tile
    the lanes of ``[B, S, H * D]`` (:func:`_lane_block`: H * D a
    multiple of 128 in heads of 128 / n or 128 n lanes, or all of it no
    more than one lane block). Grouped kv heads (``kv_heads`` fewer than
    H, dividing it) need a head to be its own lane block (D a multiple
    of 128): a kv head's block is then read for its group's query heads
    with no lane moved. Under ``block_diffusion`` S is the ``2 L`` rows
    of the two copies: L has to split into such blocks, of whole
    diffusion blocks whose length is a power of two (the mask on a tile
    is shifts and compares)."""
    check_window(window, block_diffusion=block_diffusion)
    _, h, s, d = shape
    if kv_heads not in (None, h) and (h % kv_heads or d % _LANES):
        return False
    if block_diffusion is not None:
        size = None if s % 2 else _pick_block(s // 2, block)
        if size is None or size % block_diffusion \
                or block_diffusion & (block_diffusion - 1):
            return False
        s = s // 2
    return _pick_block(s, block) is not None and (
        _lane_block(h, d) == max(_LANES, d) or h * d <= _LANES)


# Crossover with XLA's fused attention, on the model path
# (``MultiHeadAttention``: ``preferred``). Not re-measured since the
# kernels got faster (PERF.md §7): on the ledger (PR 25) BERT-large
# reaches 53.4% MFU at seq 128 under XLA's attention and 46.1% at seq
# 512 under these kernels (38.5% before PR 25); no cell sits between.
MIN_KERNEL_SEQ = 512


def preferred(shape, window=None, kv_heads=None, block_diffusion=None):
    """True when the Pallas kernel is expected to beat XLA's fused
    attention for this [B, H, S, D] shape; the same sequences for a
    band call (``window``) as for a full one, where XLA's side is the
    blocked band of ``local_flash_attention``. The compiled kernels keep
    ``lse`` with the sequence along the lanes, so every block has to be
    lane-wide or the whole sequence (which it is up to the smallest
    block target, 256); a longer sequence that only splits into slivers
    is XLA's to win anyway."""
    s = shape[2]
    if block_diffusion is not None:
        # (a tile of either copy is lane-wide: ``lse`` runs over both)
        return (s >= MIN_KERNEL_SEQ and s % (2 * _LANES) == 0
                and supports(shape, kv_heads=kv_heads,
                             block_diffusion=block_diffusion))
    return (s >= MIN_KERNEL_SEQ and (s % _LANES == 0 or s <= 256)
            and supports(shape, window=window, kv_heads=kv_heads))


def _interpret_default():
    return jax.default_backend() == 'cpu'


def _tile_positions(qi, ki, bq, bk, transposed):
    """Global (query, key) positions over tile (qi, ki): [bq, bk], or
    [bk, bq] for the transposed tile of ``flash_dkv``."""
    shape = (bk, bq) if transposed else (bq, bk)
    q_dim = 1 if transposed else 0
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    return qpos, kpos


def _causal_mask(qi, ki, bq, bk, transposed=False):
    """Boolean tile of the global-position causal mask."""
    qpos, kpos = _tile_positions(qi, ki, bq, bk, transposed)
    return qpos >= kpos


def _window_mask(qi, ki, bq, bk, window, transposed=False):
    """Boolean tile of a band: true where the key lies ``window[0]``
    before to ``window[1]`` after the query."""
    left, right = window
    qpos, kpos = _tile_positions(qi, ki, bq, bk, transposed)
    return jnp.logical_and(kpos >= qpos - left, kpos <= qpos + right)


def _for_each_tile_kind(tile, qi, ki, bq, bk, seq, causal,
                        transposed=False, window=None, bd=None):
    """Run ``tile(mask)`` for the kind of tile (qi, ki) is: not at all
    for a dead one, with the causal (or the band's) mask where the
    diagonal (an edge of the band) crosses it, with ``None`` otherwise.
    A kind the static grid does not contain is not emitted."""
    if bd is not None:
        _for_each_bd_tile(tile, qi, ki, bq, bd, transposed)
        return
    if not causal and window is None:
        tile(None)
        return
    tiles, live, masked = _tile_counts(seq, bq, bk, causal, window,
                                       transposed)
    if window is not None:
        def mask():
            return _window_mask(qi, ki, bq, bk, window, transposed)
    else:
        def mask():
            return _causal_mask(qi, ki, bq, bk, transposed)
    if masked == tiles:
        tile(mask())
        return
    if window is not None:
        is_live = _band_tile_live(qi, ki, bq, bk, seq, window)
        crossed = _band_tile_crossed(qi, ki, bq, bk, window)
    else:
        is_live = _tile_live(qi, ki, bq, bk)
        crossed = _tile_crossed(qi, ki, bq, bk)
    if masked:
        when = crossed if live == tiles else jnp.logical_and(is_live,
                                                             crossed)

        @pl.when(when)
        def _():
            tile(mask())
    if live > masked:
        @pl.when(jnp.logical_and(is_live, jnp.logical_not(crossed)))
        def _():
            tile(None)


def _for_the_live_row(rows, outer, n_outer, size, seq, causal,
                      transposed=False, window=None):
    """One-pass dispatch: the inner block is the whole sequence, so a
    step owns a row of tiles. Run ``rows(parts)`` with the ``(lo, hi,
    mask)`` ranges of the inner sequence that hold an unmasked position.
    Not causal: the whole row (under a band's mask if there is a
    ``window``: a sequence that is one block is short). Causal: the
    square the diagonal crosses (masked, ``size`` wide) and what lies
    below it (no mask): keys before the q-block, or for the transposed
    tiles of ``flash_dkv`` queries after the kv-block. The ranges depend
    on the outer block, so there is one static branch per outer block."""
    if window is not None:
        qi, ki, bq, bk = (0, outer, seq, size) if transposed else \
            (outer, 0, size, seq)
        rows([(0, seq, _window_mask(qi, ki, bq, bk, window, transposed))])
        return
    if not causal:
        rows([(0, seq, None)])
        return
    diagonal = _causal_mask(0, 0, size, size, transposed)
    for i in range(n_outer):
        lo, hi = i * size, (i + 1) * size
        below = (hi, seq) if transposed else (0, lo)
        parts = [(lo, hi, diagonal)]
        if below[0] < below[1]:
            parts.append(below + (None,))

        if n_outer == 1:
            rows(parts)
        else:
            pl.when(outer == i)(functools.partial(rows, parts))


def _is_pow2(x):
    return math.frexp(x)[0] == 0.5


def _to_row(col):
    """[n, 1] -> [1, n] (sublanes to lanes)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


def _to_col(row):
    """[1, n] -> [n, 1] (lanes to sublanes)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scores(a, b, sm_scale, fold, mask):
    """Masked, scaled ``a b^T`` in f32; a folded scale is already in q."""
    s = _dot(a, b, _NT)
    if not fold:
        s = s * sm_scale
    return s if mask is None else jnp.where(mask, s, NEG_INF)


# ---------------------------------------------------------------------------
# heads in the lanes of a block
# ---------------------------------------------------------------------------

def _head_lanes(lanes, d, i):
    """Which lanes of a ``lanes``-wide block are those of its ``i``-th
    head: a [1, lanes] mask, or None where the block is one head."""
    if lanes == d:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.logical_and(lane >= i * d, lane < (i + 1) * d)


def _lane_blocks(g, d, lanes):
    """The lane blocks of a grid step that holds ``g`` heads: for each
    its columns of the step's ``g * d`` and its heads, as ``(head of
    the step, mask of its lanes)``."""
    per = lanes // d
    return [(slice(c * lanes, (c + 1) * lanes),
             [(c * per + i, _head_lanes(lanes, d, i)) for i in range(per)])
            for c in range(g // per)]


def _only(x, keep):
    """``x`` with the lanes of the block's other heads zeroed: contracted
    over all the lanes of the block it is contracted over this head's
    (in as many passes of a 128-deep MXU as over 64 lanes alone)."""
    return x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))


def _place(rest, x, keep):
    """``x`` on this head's lanes and ``rest`` (what the block's heads
    so far left) on the others. A matmul against all the lanes of a
    block yields every head's columns and this head's are the ones that
    mean something; once each head of the block has placed its own,
    the block is whole and stored lane-dense."""
    return x if keep is None or rest is None else jnp.where(keep, x, rest)


# ---------------------------------------------------------------------------
# rotary positions on the tile
# ---------------------------------------------------------------------------

def _turn(x, d):
    """The rotate-half of each head's ``d`` lanes of ``x [rows, lanes]``,
    ``cat(-x2, x1)`` with ``x1, x2`` the head's halves: two rolls along
    the lanes (a lower half takes what lies ``d / 2`` above it, an upper
    half what lies below), a select on the lane and the sign. No lane
    slice, so the heads of a lane block turn together."""
    lanes, half = x.shape[-1], d // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.where(lane % d < half, -pltpu.roll(x, lanes - half, 1),
                     pltpu.roll(x, half, 1))


def _rotate(x, cos, sin, d, back=False):
    """``x [rows, lanes]`` turned by the angles of the position tables'
    blocks ``cos``, ``sin [rows, lanes]``: ``x cos + turn(x) sin`` in
    f32, which the caller rounds once. ``back`` turns by the negative
    angle, ``x cos - turn(x) sin``: the transpose of the rotation
    (``turn^T = -turn``, and the tables repeat across a head's halves),
    which takes the gradient w.r.t. a rotated q or k to that of q or k."""
    x = x.astype(jnp.float32)
    turned = _turn(x, d) * sin
    return x * cos - turned if back else x * cos + turned


def _reads(ref, tables, d):
    """``read(rows, cols)`` of the block ``ref[0, rows, cols]`` of q or
    k: as it lies, or with position ``tables`` (the ``(cos, sin)`` refs
    blocked by the operand's rows) rotated on the tile, in f32, rounded
    once to the operand's dtype, before anything else touches it (the
    scale's fold, the other heads' mask). A lane block's heads read the
    same block: it is rotated once for the life of this ``read``, which
    a caller makes inside the branch that uses it."""
    if tables is None:
        return lambda rows, cols: ref[0, rows, cols]
    cos_ref, sin_ref = tables
    seen = {}

    def read(rows, cols):
        at = (rows.start, rows.stop, cols.start)
        if at not in seen:
            x = ref[0, rows, cols]
            seen[at] = _rotate(x, cos_ref[rows, :], sin_ref[rows, :],
                               d).astype(x.dtype)
        return seen[at]
    return read


def _reads_qk(q_ref, k_ref, tables, d):
    """:func:`_reads` of q and of k; ``tables``: None, or the table refs
    blocked by q's rows and those blocked by k's."""
    q_tables, k_tables = tables or (None, None)
    return _reads(q_ref, q_tables, d), _reads(k_ref, k_tables, d)


def _turned_back(dx, tables, d, rows=Ellipsis):
    """The finished f32 gradient w.r.t. a rotated q or k block turned
    back to that of q or k, before the one rounding of its store;
    ``tables``: None (nothing was rotated), or the ``(cos, sin)`` refs
    blocked by the block's rows (``dx`` is that of their ``rows``)."""
    if tables is None:
        return dx
    cos_ref, sin_ref = tables
    return _rotate(dx, cos_ref[rows], sin_ref[rows], d, back=True)


_ALL = slice(None)


# Grouped kv heads (``group`` query heads to a kv head, ``group > 1``): a
# head is its own lane block, a grid step holds ``g`` query heads of ONE
# group (``g`` divides ``group``) and the group's one block of k and of
# v, which every head of the step reads where it lies.

def _kv_cols(cols, group, lanes):
    """The step's columns of k and v for the query lane block ``cols``:
    the same columns, or the one kv head a grouped step holds."""
    return cols if group == 1 else slice(0, lanes)


def _readers(q_ref, k_ref, tables, d, group):
    """``readers()``: :func:`_reads_qk` for one lane block of a step; the
    heads of a grouped step share one, so that their k block is rotated
    once."""
    if group == 1:
        return lambda: _reads_qk(q_ref, k_ref, tables, d)
    shared = _reads_qk(q_ref, k_ref, tables, d)
    return lambda: shared


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _inner_block(outer, j, size, inner_size, window, transposed=False,
                 bd=None):
    """The inner block of grid step ``j``: ``j`` itself, or for a band
    call the ``j``-th block the band reaches from ``outer`` (which may
    lie outside the sequence: a dead tile), or under the block-diffusion
    mask :func:`_bd_inner`."""
    if bd is not None:
        return _bd_inner(outer, j, bd.tiles, transposed)
    if window is None:
        return j
    back, _ = _band_reach(window, transposed)
    return _band_first(outer, size, inner_size, back) + j


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                sm_scale, fold, causal, bq, bk, nq, nk, g, d, lanes, window,
                n_inner, group, bd=None, tables=None):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _inner_block(qi, j, bq, bk, window, bd=bd)
    blocks = _lane_blocks(g, d, lanes)

    def query(read_q, cols, keep):
        q = read_q(_ALL, cols)                                # [bq, lanes]
        return _only(q * sm_scale if fold else q, keep)

    def rows(parts):
        # plain softmax over the live keys of the row, by ranges
        readers = _readers(q_ref, k_ref, tables, d, group)
        for cols, heads in blocks:
            read_q, read_k = readers()
            kcols = _kv_cols(cols, group, lanes)
            o = None
            for h, keep in heads:
                q = query(read_q, cols, keep)
                ss = [_scores(q, read_k(slice(lo, hi), kcols), sm_scale,
                              fold, mask)
                      for lo, hi, mask in parts]
                m = functools.reduce(jnp.maximum, [
                    jnp.max(s, axis=1, keepdims=True) for s in ss])
                ps = [jnp.exp(s - m) for s in ss]
                l = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
                acc = sum(_dot(p.astype(v_ref.dtype), v_ref[0, lo:hi, kcols],
                               _NN) for p, (lo, hi, _) in zip(ps, parts))
                o = _place(o, acc / l, keep)
                lse_ref[0, h] = _to_row(m + jnp.log(l))
            o_ref[0, :, cols] = o.astype(o_ref.dtype)

    if nk == 1:
        _for_the_live_row(rows, qi, nq, bq, bk, causal, window=window)
        return

    acc_scr, m_scr, l_scr = scratch

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def tile(mask):
        # online softmax. Under a causal mask every row meets an
        # unmasked key in its first live tile (key 0), so the running
        # max is real from then on. In a band call a row may meet none
        # until a later tile: its max stays NEG_INF, p is exp(0) for
        # every key and l and acc hold finite rubbish, which the first
        # real max wipes out (alpha = exp(NEG_INF - m) = 0).
        readers = _readers(q_ref, k_ref, tables, d, group)
        for c, (cols, heads) in enumerate(blocks):
            read_q, read_k = readers()
            kcols = _kv_cols(cols, group, lanes)
            v = v_ref[0, :, kcols]
            alphas = pv = None
            for h, keep in heads:
                s = _scores(query(read_q, cols, keep), read_k(_ALL, kcols),
                            sm_scale, fold, mask)
                m_prev = m_scr[h]                             # [bq, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)                        # [bq, bk]
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1,
                                                      keepdims=True)
                m_scr[h] = m_new
                alphas = _place(alphas, alpha, keep)
                pv = _place(pv, _dot(p.astype(v.dtype), v, _NN), keep)
            acc_scr[c] = acc_scr[c] * alphas + pv

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        window=window, bd=bd)

    @pl.when(j == n_inner - 1)
    def _emit():
        for c, (cols, heads) in enumerate(blocks):
            ls = None
            for h, keep in heads:
                l = l_scr[h]
                ls = _place(ls, l, keep)
                lse_ref[0, h] = _to_row(m_scr[h] + jnp.log(l))
            o_ref[0, :, cols] = (acc_scr[c] / ls).astype(o_ref.dtype)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'parallel', 'arbitrary'),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _inner_blocks(s, blocks, window, transposed=False, bd=None):
    """Length of the inner (sequential) grid dimension: every block of
    the inner operand, or the longest run of them a band reaches; under
    the block-diffusion mask the clean key tiles and one noised
    (transposed: every query tile of both copies)."""
    bq, bk, _ = blocks
    size, inner = (bk, bq) if transposed else (bq, bk)
    if bd is not None:
        return 2 * bd.tiles if transposed else bd.tiles + 1
    if window is None:
        return s // inner
    return _band_inner_blocks(s, size, inner, window, transposed)


def _static(kernel, s, heads, kv_heads, d, causal, sm_scale, blocks, window,
            transposed=False, bd=None):
    """``kernel`` with what a call fixes at trace time."""
    bq, bk, g = blocks
    return functools.partial(
        kernel, sm_scale=sm_scale, fold=_is_pow2(sm_scale), causal=causal,
        bq=bq, bk=bk, nq=s // bq, nk=s // bk, g=g, d=d,
        lanes=_lane_block(heads, d), window=window,
        n_inner=_inner_blocks(s, blocks, window, transposed, bd),
        group=heads // kv_heads, bd=bd)


def _name(kernel, window, bd=None):
    """The ``pallas_call`` name: a band call and one under the
    block-diffusion mask are told from a full one in a trace
    (``flash_fwd_band``, ``flash_fwd_bd``), and the benchmark reads
    them all."""
    if bd is not None:
        return kernel + '_bd'
    return kernel if window is None else kernel + '_band'


def _band_fetch(outer, j, size, inner_size, seq, window, transposed=False):
    """The inner block a band call fetches at grid step ``j``: the
    ``j``-th the band reaches from ``outer``, and for a dead tile (before
    the sequence's start, after its end, or past the band's last block)
    the nearest live one, which the pipeline holds already."""
    back, ahead = _band_reach(window, transposed)
    first = _band_first(outer, size, inner_size, back)
    last = _band_last(outer, size, inner_size, ahead)
    return jnp.clip(first + j, jnp.maximum(first, 0),
                    jnp.minimum(last, seq // inner_size - 1))


# An operand of a call is ``(array, start)``: the ``heads * d`` (for k
# and v: ``kv_heads * d``) columns from ``start`` of a ``[b, s, columns]``
# array. q, k and v may be three arrays or three runs of columns of one
# (the qkv projection's output), which the index maps tell apart and
# nothing copies.

def _head_dim(qkv, heads, kv_heads):
    """``qkv``: the three arrays, or one that holds them side by side."""
    return qkv[0].shape[-1] // (heads + 2 * kv_heads if len(qkv) == 1
                                else heads)


def _operands(qkv, heads, kv_heads, g):
    """What the three calls lay out alike, from what a caller hands over
    and the query heads ``g`` of a grid step: q, k, v as ``(array,
    start)``, the head dim, the lanes of q and of k and v in a step,
    and those of a lane block."""
    d = _head_dim(qkv, heads, kv_heads)
    if len(qkv) == 3:
        operands = [(x, 0) for x in qkv]
    else:
        operands = [(qkv[0], first * d)
                    for first in (0, heads, heads + kv_heads)]
    return operands, d, g * d, (g * d if kv_heads == heads else d), \
        _lane_block(heads, d)


def _group_of(h, j):
    return h


def _rows_spec(rows, width, row_of, start=0, group_of=_group_of):
    """Blocks ``(1, rows, width)`` of a ``[b, s, columns]`` operand on a
    (b, head group, outer, inner) grid: row block ``row_of(outer,
    inner)``, and the ``width`` lanes of head group ``group_of(head
    group, inner)``, counted from column ``start`` (a multiple of
    ``width``: a head group divides the heads)."""
    first = start // width
    return pl.BlockSpec(
        (1, rows, width),
        lambda b, h, i, j: (b, row_of(i, j), first + group_of(h, j)))


def _stat_spec(g, rows, row_of, group_of=_group_of):
    """Blocks of a row statistic (``lse``, ``delta``: ``[b, h, 1, s]``)."""
    return pl.BlockSpec(
        (1, g, 1, rows),
        lambda b, h, i, j: (b, group_of(h, j), 0, row_of(i, j)))


def _kv_group_of(heads, kv_heads, g):
    """The kv block of a (b, query head group, ., .) grid's step: the
    step's own columns, or with grouped kv heads the kv head that the
    step's ``g`` query heads share."""
    if kv_heads == heads:
        return _group_of
    steps = heads // kv_heads // g
    return lambda h, j: h // steps


def _outer(i, j):
    return i


# Rotary position tables (``cos``, ``sin``: ``[s, lanes of a lane block]``
# f32, a head's table repeated over the block's heads) are operands of a
# call that is given them: each twice, blocked by the row index maps of
# the q and of the k operand, whatever the batch and the head group.

def _table_specs(lanes, *blockings):
    """Specs of ``cos`` and ``sin`` for each ``(rows, row_of)``."""
    return [pl.BlockSpec((rows, lanes),
                         lambda b, h, i, j, row_of=row_of: (row_of(i, j), 0))
            for rows, row_of in blockings for _ in range(2)]


def _tabled(kernel, at, *refs):
    """``kernel`` with the four table refs that start at ``at`` (cos and
    sin by q's rows, cos and sin by k's) as its ``tables``."""
    return kernel(*refs[:at], *refs[at + 4:],
                  tables=(refs[at:at + 2], refs[at + 2:at + 4]))


def _kv_row(causal, bq, bk, window=None, seq=None, bd=None):
    """Row block of K/V at step (i, j) of a (b, h, qi, ki) grid. A dead
    causal tile asks for the last live block of its row again, which the
    pipeline already holds, so it fetches nothing; a band call walks the
    band's blocks only (:func:`_band_fetch`); under the block-diffusion
    mask the clean tiles up to the query tile's index (the dead ones
    past it ask for that one again) and, at the last step of a noised
    query tile, its own noised tile."""
    if bd is not None:
        n = bd.tiles

        def row(i, j):
            local = i % n
            return jnp.where(jnp.logical_and(j == n, i < n), local,
                             n + jnp.minimum(j, local))
        return row
    if window is not None:
        return lambda i, j: _band_fetch(i, j, bq, bk, seq, window)
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, ((i + 1) * bq - 1) // bk)


def _fwd(qkv, tables, heads, kv_heads, causal, sm_scale, blocks, interpret,
         window=None, bd=None):
    if isinstance(blocks, Rows):
        return _fwd_row(qkv, tables, heads, sm_scale, blocks, interpret,
                        window)
    bq, bk, g = blocks
    ((q, q0), (k, k0), (v, v0)), d, width, kv_width, lanes = _operands(
        qkv, heads, kv_heads, g)
    b, s, _ = q.shape
    nk = s // bk
    bd = _bd_of(bd, s, bq)
    kv_row = _kv_row(causal, bq, bk, window, s, bd)
    kv_of = _kv_group_of(heads, kv_heads, g)
    kernel = _static(_fwd_kernel, s, heads, kv_heads, d, causal, sm_scale,
                     blocks, window, bd=bd)
    in_specs = [_rows_spec(bq, width, _outer, q0),
                _rows_spec(bk, kv_width, kv_row, k0, kv_of),
                _rows_spec(bk, kv_width, kv_row, v0, kv_of)]
    operands = (q, k, v)
    if tables is not None:
        kernel = functools.partial(_tabled, kernel, len(operands))
        in_specs += _table_specs(lanes, (bq, _outer), (bk, kv_row))
        operands += tuple(tables) * 2
    scratch = [] if nk == 1 else [
        pltpu.VMEM((width // lanes, bq, lanes), jnp.float32),
        pltpu.VMEM((g, bq, 1), jnp.float32),
        pltpu.VMEM((g, bq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, heads // g, s // bq,
              _inner_blocks(s, blocks, window, bd=bd)),
        in_specs=in_specs,
        out_specs=[
            _rows_spec(bq, width, _outer),
            _stat_spec(g, bq, _outer),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * d), q.dtype),
            jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_fwd', window, bd),
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, delta_ref,
               *scratch, sm_scale, fold, causal, bq, bk, nq, nk, g, d, lanes,
               window, n_inner, group, bd=None, tables=None):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _inner_block(qi, j, bq, bk, window, bd=bd)
    blocks = _lane_blocks(g, d, lanes)

    def delta_of(cols, h, keep):
        """``rowsum(dO * O)`` over head ``h``'s lanes, [bq, 1] in f32;
        leaves it in ``delta_ref`` as a row, for ``flash_dkv``."""
        products = (do_ref[0, :, cols].astype(jnp.float32)
                    * o_ref[0, :, cols].astype(jnp.float32))
        delta = jnp.sum(_only(products, keep), axis=1, keepdims=True)
        delta_ref[0, h] = _to_row(delta)
        return delta

    def grad(readers, cols, heads, parts, delta_of=delta_of):
        """dq of a lane block's heads from the key ranges ``parts``;
        with position tables, w.r.t. the rotated q."""
        read_q, read_k = readers()
        kcols = _kv_cols(cols, group, lanes)
        dqs = None
        for h, keep in heads:
            q = read_q(_ALL, cols)
            q = _only(q * sm_scale if fold else q, keep)
            do = _only(do_ref[0, :, cols], keep)
            lse = _to_col(lse_ref[0, h])                      # [bq, 1]
            delta = delta_of(cols, h, keep)
            dq = 0.
            for lo, hi, mask in parts:
                k = read_k(slice(lo, hi), kcols)
                s = _scores(q, k, sm_scale, fold, mask)
                p = jnp.exp(s - lse)                          # [bq, keys]
                dp = _dot(do, v_ref[0, lo:hi, kcols], _NT)
                ds = p * (dp - delta)
                if not fold:
                    ds = ds * sm_scale
                dq = dq + _dot(ds.astype(k.dtype), k, _NN)    # [bq, lanes]
            dqs = _place(dqs, dq, keep)
        return dqs

    def finish(dq):
        # a folded scale multiplied q, not the tile: give dq its factor;
        # and the gradient w.r.t. a rotated q is turned back, from the
        # f32 accumulator, before the one rounding of the store
        dq = dq * sm_scale if fold else dq
        return _turned_back(dq, tables and tables[0], d).astype(dq_ref.dtype)

    def rows(parts):
        readers = _readers(q_ref, k_ref, tables, d, group)
        for cols, heads in blocks:
            dq_ref[0, :, cols] = finish(grad(readers, cols, heads, parts))

    if nk == 1:
        _for_the_live_row(rows, qi, nq, bq, bk, causal, window=window)
        return

    # delta is the same at every inner step: made at the first and kept
    dq_scr, delta_scr = scratch

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        for cols, heads in blocks:
            for h, keep in heads:
                delta_scr[h] = delta_of(cols, h, keep)

    def tile(mask):
        readers = _readers(q_ref, k_ref, tables, d, group)
        for c, (cols, heads) in enumerate(blocks):
            dq_scr[c] = dq_scr[c] + grad(
                readers, cols, heads, [(0, bk, mask)],
                lambda cols, h, keep: delta_scr[h])

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        window=window, bd=bd)

    @pl.when(j == n_inner - 1)
    def _emit():
        for c, (cols, _) in enumerate(blocks):
            dq_ref[0, :, cols] = finish(dq_scr[c])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch,
                sm_scale, fold, causal, bq, bk, nq, nk, g, d, lanes, window,
                n_inner, group, bd=None, tables=None):
    # With grouped kv heads the head dimension of the grid walks the kv
    # heads, and the inner dimension the group's query heads, ``g`` at a
    # step, each over the q-blocks: dk and dv of the kv head add up over
    # all of them in the scratch.
    steps = group // g if group > 1 else 1
    ki, step = pl.program_id(2), pl.program_id(3)
    j = step % n_inner if steps > 1 else step
    qi = _inner_block(ki, j, bk, bq, window, transposed=True, bd=bd)
    blocks = _lane_blocks(g, d, lanes)

    def grads(readers, cols, heads, parts):
        """(dk, dv) of a lane block's heads from the query ranges
        ``parts``, on TRANSPOSED tiles [bk, queries]: lse and delta are
        rows of them, and both gradients plain matmuls. With position
        tables dk is w.r.t. the rotated k."""
        read_q, read_k = readers()
        kcols = _kv_cols(cols, group, lanes)
        dks = dvs = None
        for h, keep in heads:
            k = _only(read_k(_ALL, kcols), keep)
            v = _only(v_ref[0, :, kcols], keep)
            dk = dv = 0.
            for lo, hi, mask in parts:
                q = read_q(slice(lo, hi), cols)
                if fold:
                    q = q * sm_scale   # dk = ds^T (q * scale) as well
                do = do_ref[0, lo:hi, cols]
                s = _scores(k, q, sm_scale, fold, mask)       # [bk, queries]
                p = jnp.exp(s - lse_ref[0, h, :, lo:hi])
                dv = dv + _dot(p.astype(do.dtype), do, _NN)   # [bk, lanes]
                dp = _dot(v, do, _NT)
                ds = p * (dp - delta_ref[0, h, :, lo:hi])
                if not fold:
                    ds = ds * sm_scale
                dk = dk + _dot(ds.astype(q.dtype), q, _NN)
            dks, dvs = _place(dks, dk, keep), _place(dvs, dv, keep)
        return dks, dvs

    def finish(dk):
        return _turned_back(dk, tables and tables[1], d).astype(dk_ref.dtype)

    def rows(parts):
        readers = _readers(q_ref, k_ref, tables, d, group)
        for cols, heads in blocks:
            dk, dv = grads(readers, cols, heads, parts)
            dk_ref[0, :, cols] = finish(dk)
            dv_ref[0, :, cols] = dv.astype(dv_ref.dtype)

    if nq == 1 and group == 1:
        _for_the_live_row(rows, ki, nk, bk, bq, causal, transposed=True,
                          window=window)
        return

    dk_scr, dv_scr = scratch

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def add(parts):
        readers = _readers(q_ref, k_ref, tables, d, group)
        for c, (cols, heads) in enumerate(blocks):
            dk, dv = grads(readers, cols, heads, parts)
            c = c if group == 1 else 0    # a group's heads: one kv block
            dk_scr[c] = dk_scr[c] + dk
            dv_scr[c] = dv_scr[c] + dv

    if nq == 1:
        _for_the_live_row(add, ki, nk, bk, bq, causal, transposed=True,
                          window=window)
    else:
        _for_each_tile_kind(lambda mask: add([(0, bq, mask)]), qi, ki, bq, bk,
                            nq * bq, causal, transposed=True, window=window,
                            bd=bd)

    @pl.when(step == steps * n_inner - 1)
    def _emit():
        for c in range(dk_scr.shape[0]):
            cols = slice(c * lanes, (c + 1) * lanes)
            dk_ref[0, :, cols] = finish(dk_scr[c])
            dv_ref[0, :, cols] = dv_scr[c].astype(dv_ref.dtype)


def _dq(qkv, tables, do, o, lse, heads, kv_heads, causal, sm_scale, blocks,
        interpret, window=None, bd=None):
    """``(dq, delta)``: ``delta = rowsum(dO * O)`` of each head is
    computed here, from the two merged tensors a block at a time, and
    left as ``[b, h, 1, s]`` for ``flash_dkv``."""
    if isinstance(blocks, Rows):
        return _dq_row(qkv, tables, do, o, lse, heads, sm_scale, blocks,
                       interpret, window)
    bq, bk, g = blocks
    ((q, q0), (k, k0), (v, v0)), d, width, kv_width, lanes = _operands(
        qkv, heads, kv_heads, g)
    b, s, _ = do.shape
    bd = _bd_of(bd, s, bq)
    kv_row = _kv_row(causal, bq, bk, window, s, bd)
    kv_of = _kv_group_of(heads, kv_heads, g)
    q_spec, row_spec = _rows_spec(bq, width, _outer), _stat_spec(g, bq, _outer)
    kernel = _static(_dq_kernel, s, heads, kv_heads, d, causal, sm_scale,
                     blocks, window, bd=bd)
    in_specs = [_rows_spec(bq, width, _outer, q0),
                _rows_spec(bk, kv_width, kv_row, k0, kv_of),
                _rows_spec(bk, kv_width, kv_row, v0, kv_of),
                q_spec, q_spec, row_spec]
    operands = (q, k, v, do, o, lse)
    if tables is not None:
        kernel = functools.partial(_tabled, kernel, len(operands))
        in_specs += _table_specs(lanes, (bq, _outer), (bk, kv_row))
        operands += tuple(tables) * 2
    return pl.pallas_call(
        kernel,
        grid=(b, heads // g, s // bq,
              _inner_blocks(s, blocks, window, bd=bd)),
        in_specs=in_specs,
        # dq as q is held: an array of its own, or the first run of
        # one (whose other columns this call leaves unwritten)
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[] if s // bk == 1 else [
            pltpu.VMEM((width // lanes, bq, lanes), jnp.float32),
            pltpu.VMEM((g, bq, 1), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_dq', window, bd),
    )(*operands)


def _dkv(qkv, tables, do, lse, delta, heads, kv_heads, causal, sm_scale,
         blocks, interpret, window=None, dqkv=None, bd=None):
    """``(dk, dv)``; or with ``dqkv``, the ``[b, s, (heads + 2 kv_heads)
    * d]`` array whose first run ``flash_dq`` wrote, ``(dqkv, dv)``: dk
    goes into the second run of that array, in place. With grouped kv
    heads the grid's head dimension walks the kv heads and its inner
    dimension the group's query heads, ``g`` at a step, each over the
    q-blocks (``_dkv_kernel``)."""
    if isinstance(blocks, Rows):
        return _dkv_row(qkv, tables, do, lse, delta, heads, sm_scale, blocks,
                        interpret, window, dqkv)
    bq, bk, g = blocks
    ((q, q0), (k, k0), (v, v0)), d, width, kv_width, lanes = _operands(
        qkv, heads, kv_heads, g)
    b, s, _ = do.shape
    bd = _bd_of(bd, s, bq)
    n_inner = _inner_blocks(s, blocks, window, transposed=True, bd=bd)
    grouped = kv_heads != heads
    steps = heads // kv_heads // g if grouped else 1

    def inner(i):
        return i % n_inner if steps > 1 else i
    # the grid iterates q-blocks innermost for each kv-block; the dead
    # causal tiles come first there, and ask for the first live q-block;
    # a band call walks the q-blocks its kv-block is seen from; under
    # the block-diffusion mask a noised kv-block holds its own q-block
    # throughout, a clean one walks both copies' q-blocks, the dead ones
    # of each (before its own index) asking for the first live one
    if bd is not None:
        n = bd.tiles

        def q_row(j, i):
            qi = inner(i)
            first = jnp.where(qi < n, j - n, j)
            return jnp.where(j < n, j, jnp.maximum(qi, first))
    elif window is not None:
        def q_row(j, i):
            return _band_fetch(j, inner(i), bk, bq, s, window,
                               transposed=True)
    elif causal:
        def q_row(j, i):
            return jnp.maximum(inner(i), (j * bk) // bq)
    else:
        def q_row(j, i):
            return inner(i)
    # the step's query heads: the grid's own head group, or the
    # ``i // n_inner``-th ``g`` heads of kv head ``h``'s group
    q_of = _group_of if not grouped else \
        (lambda h, i: h * steps + i // n_inner)
    row_spec = _stat_spec(g, bq, q_row, q_of)
    acc = pltpu.VMEM((kv_width // lanes, bk, lanes), jnp.float32)
    kernel = _static(_dkv_kernel, s, heads, kv_heads, d, causal, sm_scale,
                     blocks, window, transposed=True, bd=bd)
    in_specs = [_rows_spec(bq, width, q_row, q0, q_of),
                _rows_spec(bk, kv_width, _outer, k0),
                _rows_spec(bk, kv_width, _outer, v0),
                _rows_spec(bq, width, q_row, group_of=q_of),
                row_spec, row_spec]
    operands = (q, k, v, do, lse, delta)
    if tables is not None:
        kernel = functools.partial(_tabled, kernel, len(operands))
        in_specs += _table_specs(lanes, (bq, q_row), (bk, _outer))
        operands += tuple(tables) * 2
    kv_shape = (b, s, kv_heads * d)
    if dqkv is None:
        dk, aliases = jax.ShapeDtypeStruct(kv_shape, k.dtype), {}
    else:
        # the array comes in where it lies and goes out as the first
        # result: the kernel sees it as that result only
        dk, aliases = dqkv, {len(operands): 0}
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands += (dqkv,)
        kernel = functools.partial(_without, kernel, len(operands) - 1)
    return pl.pallas_call(
        kernel,
        grid=(b, kv_heads if grouped else heads // g, s // bk,
              steps * n_inner),
        in_specs=in_specs,
        out_specs=[_rows_spec(bk, kv_width, _outer,
                              0 if dqkv is None else heads * d),
                   _rows_spec(bk, kv_width, _outer)],
        out_shape=[jax.ShapeDtypeStruct(dk.shape, dk.dtype),
                   jax.ShapeDtypeStruct(kv_shape, v.dtype)],
        scratch_shapes=[] if s // bq == 1 and not grouped else [acc, acc],
        input_output_aliases=aliases,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_dkv', window, bd),
    )(*operands)


def _without(kernel, i, *refs):
    """``kernel`` on all its refs but the ``i``-th."""
    return kernel(*refs[:i], *refs[i + 1:])


# ---------------------------------------------------------------------------
# a narrow band: one pass over each row block's own keys
# ---------------------------------------------------------------------------
#
# A band that reaches no further than a lane block to either side
# (``max(window) <= 128``: ModernBERT's 64 keys each side) has nothing
# to walk: the keys a block of rows can see are the block's own and a
# ``corner`` of each neighbour (64 rows where the reach is no more, else
# 128). The three calls then have no inner grid dimension, no scratch
# and no online softmax. Grid (batch, heads / G, seq / rows); a step
# holds ``rows`` rows of its outer operand (q for ``flash_fwd_band`` and
# ``flash_dq_band``, k and v for ``flash_dkv_band``) and of the inner
# one THE RUN of ``corner + rows + corner`` rows round them, as three
# operands of the same array: the neighbour's corner before, the block
# itself, the corner after. The corners' index maps clamp at the
# sequence's ends onto a block in range, and the mask, which knows every
# position, kills what the clamp brought. On the tile the run's pieces
# are rotated once each and laid end to end, and each ``sub`` rows of
# the step (a static loop) meet the ``sub + 2 corner`` rows of the run
# round them: one score block a head, the band's mask, a plain softmax
# (or the backward's ``exp(s - lse)``), the products, one store.

Rows = collections.namedtuple('Rows', 'rows sub corner heads_per_step')

# (rows of a sub-block, sub-blocks a step, lanes a step) of each kernel
# in the row form, from a sweep at ModernBERT's window layers, [4, 16,
# 8192, 64] bf16, 64 keys each side, rotary on the tile, on a v5e (my
# chip run, PR 40; ``tools/flash_band_bench.py --sweep``; each kernel
# alone, ms a call by the host's clock round 20 calls in a row, which
# holds their dispatch: inside the traced step the committed three take
# 3.61 together where they add up to 3.77 here; dkv's column holds about
# 0.7 ms of a copy that the step does not make). The tiled walk beside
# them: 3.55 / 2.86 / 3.16.
#
#   sub x steps   heads: forward            dq                   dkv
#                 2     4     8     16 | 2    4    8    16  | 2    4    8    16
#   128 x 1       2.40  1.66  1.61  1.59 2.86 2.28 2.04 1.78  3.35 2.43 1.97 1.69
#   128 x 2       1.65  1.33  1.23  1.23 2.29 1.99 1.75 1.66  2.46 1.95 1.73 1.58
#   128 x 4       1.34  1.20  1.14  1.11 1.89 1.75 1.67 1.59  2.16 1.75 1.61 1.54
#   128 x 8       1.21  1.01  1.02  1.04 1.73 1.67 1.58 1.56  2.04 1.66 1.56 1.53
#   256 x 1       1.72  1.38  1.29  1.19 2.14 1.69 1.73 1.72  2.77 2.30 2.07 1.92
#   256 x 2       1.42  1.24  1.19  1.19 1.66 1.93 1.77 1.76  2.30 2.07 1.96 1.89
#   256 x 4       1.35  1.29  1.18  1.15 1.50 1.67 1.69 1.74  2.09 1.96 1.91 1.86
#   256 x 8       1.23  1.27  1.15  -    1.51 1.65 1.70 -     2.01 1.91 1.88 -
#
# Fewer, larger steps win in every kernel (a step's fixed cost, and the
# corners' fetch and rotation, are paid once for up to 1024 rows), and
# sub-blocks of 128 rows nearly everywhere: a 256-row sub-block meets
# 384 keys, 1.5 times the elements. ``flash_dq_band`` alone prefers
# them, at one lane block a step. Without rotary the same calls take
# 0.58 / 1.00 / 1.55 at 128 x 4 x 8 against 1.14 / 1.67 / 1.61: the
# forward and dq are bound by their elementwise work and pay the
# rotation in full, dkv hides it.
#
# But a body is unrolled over lane blocks x sub-blocks x heads, and is
# traced and lowered at every start, cache or no cache. At the fastest
# of each column (128 x 8 x 4, 256 x 4 x 2, 128 x 8 x 16: 1.01 / 1.50 /
# 0.82 ms a call with dk written in place) ModernBERT's cell read
# ``setup_s`` 67.4-68.7 s warm against the tiled walk's 45.9 (``jax.trace``
# 29.1 s against 9.6; my chip run, PR 40), and Mosaic's compile of that
# dkv is 21.0 s against 0.9. So the targets are the fastest whose
# forward and backward trace and lower in the tiled walk's time (0.66 s
# against 0.66 here, on the CPU): 1.21 / 1.51 / 1.05 ms a call, and the
# cell's ``setup_s`` 49.8 s against 48.1 (medians; my chip run, PR 40).
_ROW_TARGETS = {'fwd': (128, 4, 256), 'dq': (256, 4, 128),
                'dkv': (128, 2, 512)}


def _band_form(window, seq, group, asked):
    """How a call walks its band: ``None`` without a ``window``,
    ``'row'`` (one pass over each row block's own keys) for a band that
    reaches no further than a lane block to either side, ``'tiles'`` (the
    tiled walk under an online softmax) for a wider one, and for a
    narrow one that the row form has no body for: grouped kv heads, a
    sequence that does not split into lane-wide blocks, block sizes
    asked for by hand (which are the tiled walk's)."""
    if window is None:
        return None
    row = (max(window) <= _LANES and group == 1 and not asked
           and seq % _LANES == 0)
    return 'row' if row else 'tiles'


def _rows(heads, head_dim, seq, window, sub, steps, lanes):
    """The :class:`Rows` of one kernel: its targets cut to the sequence
    (sub-blocks a step) and to the heads (whole lane blocks that divide
    their count, inside ``lanes``: a step's VMEM goes by its lanes)."""
    sub = _pick_block(seq, sub)
    while seq % (sub * steps):
        steps //= 2
    corner = _LANES // 2 if max(window) <= _LANES // 2 else _LANES
    return Rows(sub * steps, sub, corner, _whole_blocks(
        heads, _lane_block(heads, head_dim) // head_dim, lanes // head_dim))


def _run_pieces(blocks, seq, piece, run):
    """``(rows, row-block index map)`` of what a step holds of an
    operand: its own rows, or (``run``) the three pieces of its run,
    the corners in blocks of ``piece`` rows: the last before the step's
    own rows and the first after them, clamped into the sequence."""
    own = (blocks.rows, lambda i: i)
    if not run:
        return [own]
    per, last = blocks.rows // piece, seq // piece - 1
    return [(piece, lambda i: jnp.maximum(i * per - 1, 0)), own,
            (piece, lambda i: jnp.minimum((i + 1) * per, last))]


def _row_specs(blocks, seq, width, start=0, run=False):
    """Specs of a ``[b, s, columns]`` operand on the row form's (b, head
    group, row block) grid: the ``width`` lanes of the head group, from
    column ``start``."""
    first = start // width
    return [pl.BlockSpec((1, n, width), lambda b, h, i, row_of=row_of:
                         (b, row_of(i), first + h))
            for n, row_of in _run_pieces(blocks, seq, blocks.corner, run)]


def _row_stat_specs(blocks, seq, run=False):
    """Specs of a row statistic ``[b, h, 1, s]`` likewise; the run's
    corners arrive as the neighbours' whole lane blocks."""
    return [pl.BlockSpec((1, blocks.heads_per_step, 1, n),
                         lambda b, h, i, row_of=row_of: (b, h, 0, row_of(i)))
            for n, row_of in _run_pieces(blocks, seq, _LANES, run)]


def _row_table_specs(blocks, seq, lanes, run=False):
    """Specs of ``cos`` and ``sin`` by the same rows."""
    return [pl.BlockSpec((n, lanes),
                         lambda b, h, i, row_of=row_of: (row_of(i), 0))
            for n, row_of in _run_pieces(blocks, seq, blocks.corner, run)
            for _ in range(2)]


def _run_of(pieces, tables, d, cols):
    """The run a step holds of columns ``cols`` of q, k, v or ``do``:
    its pieces end to end, ``[corner + rows + corner, lanes]``, each
    rotated once by its own blocks of the position ``tables`` (three
    ``(cos, sin)``; None for v and ``do``, and without rotary)."""
    return jnp.concatenate([
        _reads(ref, table, d)(_ALL, cols)
        for ref, table in zip(pieces, tables or (None,) * 3)], axis=0)


def _stat_run(pieces, h, corner):
    """The run of a row statistic of head ``h``, ``[1, corner + rows +
    corner]``: the last ``corner`` lanes of the neighbour's block before,
    the step's own, the first ``corner`` of the block after. Lane blocks
    end to end, and where a corner is half a block every block of the
    result is the end of one and the start of the next (a select and a
    roll each): no slice off the lanes' grid."""
    before, own, after = (ref[0, h] for ref in pieces)
    blocks = [before] + [own[:, at:at + _LANES]
                         for at in range(0, own.shape[1], _LANES)] + [after]
    if corner == _LANES:
        return jnp.concatenate(blocks, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return jnp.concatenate([
        pltpu.roll(jnp.where(lane >= _LANES - corner, a, b), corner, 1)
        for a, b in zip(blocks, blocks[1:])], axis=1)


def _run_masks(step, blocks, seq, reach):
    """For each sub-block of grid step ``step`` the mask of its score
    block ``[sub, sub + 2 corner]``: rows are the outer operand's
    positions, columns the run's, which start ``corner`` before the
    rows; ``reach``: how far the band goes towards lower and higher
    columns. The band is the same in every sub-block; the first and the
    last of a step may hold columns outside the sequence, where a
    clamped index map brought another block's rows."""
    rows, sub, corner, _ = blocks
    shape = (sub, sub + 2 * corner)
    ahead = jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    back, fore = reach
    band = jnp.logical_and(ahead >= corner - back, ahead <= corner + fore)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    masks = []
    for t in range(rows // sub):
        if t in (0, rows // sub - 1):
            pos = step * rows + (t * sub - corner) + col
            masks.append(jnp.logical_and(
                band, jnp.logical_and(pos >= 0, pos < seq)))
        else:
            masks.append(band)
    return masks


def _sub_blocks(blocks):
    """``(own rows, rows of the run round them)`` of each sub-block of a
    step, as slices of the step's rows and of its run."""
    rows, sub, corner, _ = blocks
    return [(slice(at, at + sub), slice(at, at + sub + 2 * corner))
            for at in range(0, rows, sub)]


def _row_refs(refs, runs, rotary):
    """The refs of a row-form kernel taken apart: ``runs`` says of each
    operand whether it comes as a run (three refs) or as the step's own
    rows (one); behind them, with ``rotary``, cos and sin by the outer
    operand's rows and by each piece of the inner one's run; the rest
    are the results."""
    refs = list(refs)
    operands = [tuple(refs.pop(0) for _ in range(3)) if run else refs.pop(0)
                for run in runs]
    own = run = None
    if rotary:
        own, *run = [(refs.pop(0), refs.pop(0)) for _ in range(4)]
    return operands, own, run, refs


def _fwd_row_kernel(*refs, sm_scale, fold, blocks, seq, d, lanes, window,
                    rotary):
    (q_ref, k_run, v_run), q_tables, k_tables, (o_ref, lse_ref) = _row_refs(
        refs, (False, True, True), rotary)
    masks = _run_masks(pl.program_id(2), blocks, seq, window)
    for cols, heads in _lane_blocks(blocks.heads_per_step, d, lanes):
        q_rows = _reads(q_ref, q_tables, d)(_ALL, cols)
        ks, vs = _run_of(k_run, k_tables, d, cols), _run_of(v_run, None, d,
                                                            cols)
        for (own, seen), mask in zip(_sub_blocks(blocks), masks):
            k, v, o = ks[seen], vs[seen], None
            for h, keep in heads:
                q = q_rows[own]
                s = _scores(_only(q * sm_scale if fold else q, keep), k,
                            sm_scale, fold, mask)
                m = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp(s - m)                            # [sub, span]
                l = jnp.sum(p, axis=1, keepdims=True)
                o = _place(o, _dot(p.astype(v.dtype), v, _NN) / l, keep)
                lse_ref[0, h, :, own] = _to_row(m + jnp.log(l))
            o_ref[0, own, cols] = o.astype(o_ref.dtype)


def _dq_row_kernel(*refs, sm_scale, fold, blocks, seq, d, lanes, window,
                   rotary):
    (q_ref, k_run, v_run, do_ref, o_ref, lse_ref), q_tables, k_tables, \
        (dq_ref, delta_ref) = _row_refs(
            refs, (False, True, True, False, False, False), rotary)
    masks = _run_masks(pl.program_id(2), blocks, seq, window)
    for cols, heads in _lane_blocks(blocks.heads_per_step, d, lanes):
        q_rows = _reads(q_ref, q_tables, d)(_ALL, cols)
        ks, vs = _run_of(k_run, k_tables, d, cols), _run_of(v_run, None, d,
                                                            cols)
        for (own, seen), mask in zip(_sub_blocks(blocks), masks):
            k, v, dqs = ks[seen], vs[seen], None
            do_rows = do_ref[0, own, cols]
            products = (do_rows.astype(jnp.float32)
                        * o_ref[0, own, cols].astype(jnp.float32))
            for h, keep in heads:
                q = q_rows[own]
                q = _only(q * sm_scale if fold else q, keep)
                delta = jnp.sum(_only(products, keep), axis=1, keepdims=True)
                delta_ref[0, h, :, own] = _to_row(delta)
                s = _scores(q, k, sm_scale, fold, mask)
                p = jnp.exp(s - _to_col(lse_ref[0, h, :, own]))
                ds = p * (_dot(_only(do_rows, keep), v, _NT) - delta)
                if not fold:
                    ds = ds * sm_scale
                dqs = _place(dqs, _dot(ds.astype(k.dtype), k, _NN), keep)
            dq = dqs * sm_scale if fold else dqs
            dq_ref[0, own, cols] = _turned_back(dq, q_tables, d, own).astype(
                dq_ref.dtype)


def _dkv_row_kernel(*refs, sm_scale, fold, blocks, seq, d, lanes, window,
                    rotary):
    (q_run, k_ref, v_ref, do_run, lse_run, delta_run), k_tables, q_tables, \
        (dk_ref, dv_ref) = _row_refs(
            refs, (True, False, False, True, True, True), rotary)
    # transposed score blocks [keys, queries]: a key is seen from as far
    # behind it as the band reaches ahead of a query
    masks = _run_masks(pl.program_id(2), blocks, seq, window[::-1])
    for cols, heads in _lane_blocks(blocks.heads_per_step, d, lanes):
        k_rows = _reads(k_ref, k_tables, d)(_ALL, cols)
        qs, dos = _run_of(q_run, q_tables, d, cols), _run_of(do_run, None, d,
                                                             cols)
        if fold:
            qs = qs * sm_scale         # dk = ds^T (q * scale) as well
        stats = {h: (_stat_run(lse_run, h, blocks.corner),
                     _stat_run(delta_run, h, blocks.corner))
                 for h, _ in heads}
        for (own, seen), mask in zip(_sub_blocks(blocks), masks):
            q, do, dks, dvs = qs[seen], dos[seen], None, None
            for h, keep in heads:
                lse, delta = stats[h]
                k = _only(k_rows[own], keep)
                s = _scores(k, q, sm_scale, fold, mask)       # [sub, span]
                p = jnp.exp(s - lse[:, seen])
                dvs = _place(dvs, _dot(p.astype(do.dtype), do, _NN), keep)
                dp = _dot(_only(v_ref[0, own, cols], keep), do, _NT)
                ds = p * (dp - delta[:, seen])
                if not fold:
                    ds = ds * sm_scale
                dks = _place(dks, _dot(ds.astype(q.dtype), q, _NN), keep)
            dk_ref[0, own, cols] = _turned_back(dks, k_tables, d, own).astype(
                dk_ref.dtype)
            dv_ref[0, own, cols] = dvs.astype(dv_ref.dtype)


_ROW_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'parallel'),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _row_call(kernel, name, qkv, tables, heads, sm_scale, blocks, window,
              interpret, operands, specs, out_specs, out_shape, into=None):
    """One call of the row form: ``kernel`` on ``operands`` under
    ``specs``, the position tables behind them, by the outer operand's
    rows and by the pieces of the inner one's run. ``into``: an array
    that comes in where it lies and goes out as the first result (the
    kernel sees it as that result only)."""
    ((q, _), _, _), d, _, _, lanes = _operands(qkv, heads, heads,
                                               blocks.heads_per_step)
    b, s, _ = q.shape
    kernel = functools.partial(
        kernel, sm_scale=sm_scale, fold=_is_pow2(sm_scale), blocks=blocks,
        seq=s, d=d, lanes=lanes, window=window, rotary=tables is not None)
    if tables is not None:
        specs = specs + _row_table_specs(blocks, s, lanes) \
            + _row_table_specs(blocks, s, lanes, run=True)
        operands = operands + tuple(tables) * 4
    aliases = {}
    if into is not None:
        aliases = {len(operands): 0}
        kernel = functools.partial(_without, kernel, len(operands))
        specs = specs + [pl.BlockSpec(memory_space=pl.ANY)]
        operands = operands + (into,)
    return pl.pallas_call(
        kernel,
        grid=(b, heads // blocks.heads_per_step, s // blocks.rows),
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=aliases, compiler_params=_ROW_COMPILER_PARAMS,
        interpret=interpret, name=name)(*operands)


def _fwd_row(qkv, tables, heads, sm_scale, blocks, interpret, window):
    ((q, q0), (k, k0), (v, v0)), d, width, _, _ = _operands(
        qkv, heads, heads, blocks.heads_per_step)
    b, s, _ = q.shape
    return _row_call(
        _fwd_row_kernel, 'flash_fwd_band', qkv, tables, heads, sm_scale,
        blocks, window, interpret, (q, k, k, k, v, v, v),
        _row_specs(blocks, s, width, q0)
        + _row_specs(blocks, s, width, k0, run=True)
        + _row_specs(blocks, s, width, v0, run=True),
        _row_specs(blocks, s, width) + _row_stat_specs(blocks, s),
        [jax.ShapeDtypeStruct((b, s, heads * d), q.dtype),
         jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)])


def _dq_row(qkv, tables, do, o, lse, heads, sm_scale, blocks, interpret,
            window):
    ((q, q0), (k, k0), (v, v0)), _, width, _, _ = _operands(
        qkv, heads, heads, blocks.heads_per_step)
    s = do.shape[1]
    own, stat = _row_specs(blocks, s, width), _row_stat_specs(blocks, s)
    return _row_call(
        _dq_row_kernel, 'flash_dq_band', qkv, tables, heads, sm_scale,
        blocks, window, interpret, (q, k, k, k, v, v, v, do, o, lse),
        _row_specs(blocks, s, width, q0)
        + _row_specs(blocks, s, width, k0, run=True)
        + _row_specs(blocks, s, width, v0, run=True) + own + own + stat,
        # dq as q is held: an array of its own, or the first run of one
        own + stat,
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(lse.shape, jnp.float32)])


def _dkv_row(qkv, tables, do, lse, delta, heads, sm_scale, blocks, interpret,
             window, dqkv):
    ((q, q0), (k, k0), (v, v0)), d, width, _, _ = _operands(
        qkv, heads, heads, blocks.heads_per_step)
    b, s, _ = do.shape
    stat = _row_stat_specs(blocks, s, run=True)
    kv_shape = (b, s, heads * d)
    dk = jax.ShapeDtypeStruct(kv_shape, k.dtype) if dqkv is None else dqkv
    return _row_call(
        _dkv_row_kernel, 'flash_dkv_band', qkv, tables, heads, sm_scale,
        blocks, window, interpret,
        (q, q, q, k, v, do, do, do) + (lse,) * 3 + (delta,) * 3,
        _row_specs(blocks, s, width, q0, run=True)
        + _row_specs(blocks, s, width, k0) + _row_specs(blocks, s, width, v0)
        + _row_specs(blocks, s, width, run=True) + stat + stat,
        _row_specs(blocks, s, width, 0 if dqkv is None else heads * d)
        + _row_specs(blocks, s, width),
        [jax.ShapeDtypeStruct(dk.shape, dk.dtype),
         jax.ShapeDtypeStruct(kv_shape, v.dtype)], into=dqkv)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

# What the forward rule of a ``flash_attention_merged`` call names for a
# checkpoint policy (``jax.checkpoint_policies.save_only_these_names``):
# its output and its row statistics, all the backward needs besides q, k
# and v.
CHECKPOINT_NAMES = ('flash_o', 'flash_lse')


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _flash(qkv, tables, heads, kv_heads, causal, sm_scale, plan, interpret,
           window=None, named=False, bd=None):
    """``qkv``: a tuple of q ``[b, s, heads * d]``, k and v ``[b, s,
    kv_heads * d]``, or of the one ``[b, s, (heads + 2 kv_heads) * d]``
    that holds them side by side; ``tables``: None, or the rotary
    positions' ``(cos, sin)``; ``o [b, s, heads * d]``."""
    return _fwd(qkv, tables, heads, kv_heads, causal, sm_scale, plan.fwd,
                interpret, window, bd)[0]


def _flash_fwd(qkv, tables, heads, kv_heads, causal, sm_scale, plan,
               interpret, window, named, bd):
    o, lse = _fwd(qkv, tables, heads, kv_heads, causal, sm_scale, plan.fwd,
                  interpret, window, bd)
    if named:
        # both as the kernel writes them: o lane-dense, what the output
        # projection reads; lse [b, h, 1, s], which XLA tiles T(1, 128)
        # in a stack of them (the unit dimension pads nothing)
        o = checkpoint_name(o, CHECKPOINT_NAMES[0])
        lse = checkpoint_name(lse, CHECKPOINT_NAMES[1])
    return o, (qkv, tables, o, lse)


def _flash_bwd(heads, kv_heads, causal, sm_scale, plan, interpret, window,
               named, bd, res, do):
    qkv, tables, o, lse = res
    # (the position tables are constants: the None beside the cotangent
    # of qkv is theirs)
    dq, delta = _dq(qkv, tables, do, o, lse, heads, kv_heads, causal,
                    sm_scale, plan.dq, interpret, window, bd)
    if len(qkv) == 3:
        dk, dv = _dkv(qkv, tables, do, lse, delta, heads, kv_heads, causal,
                      sm_scale, plan.dkv, interpret, window, bd=bd)
        return (dq, dk, dv), None
    # the cotangent of one array that holds q, k and v is one array: dq
    # is its first run as flash_dq returns it, flash_dkv writes dk into
    # the second in place, and dv is written over the last
    dqkv, dv = _dkv(qkv, tables, do, lse, delta, heads, kv_heads, causal,
                    sm_scale, plan.dkv, interpret, window, dqkv=dq, bd=bd)
    return (jax.lax.dynamic_update_slice_in_dim(
        dqkv, dv, dqkv.shape[-1] - dv.shape[-1], axis=2),), None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _blocks(heads, head_dim, seq, targets, block_q, block_k, group=1,
            least_heads=1):
    sizes = []
    for asked, target in zip((block_q, block_k), targets):
        size = seq if not asked and seq <= target else \
            _pick_block(seq, asked or target)
        if size is None:
            raise ValueError('flash_attention: seq %d not blockable; check '
                             'supports() first' % seq)
        sizes.append(size)
    per_block = _lane_block(heads, head_dim) // head_dim
    # (grouped kv heads: a step's query heads share one kv head)
    return Blocks(*sizes, _heads_per_step(heads if group == 1 else group,
                                          *sizes, per_block, least_heads))


# (Square tile, fewest heads a step) of each kernel under the
# block-diffusion mask. Measured on a v5e at SDAR's shape (32 query heads
# over 4 kv heads of 128, 2 x 2 x 8192 rows, B = 4, rotary inside;
# ``tools/flash_bd_bench.py``: the device's ms a call alone; my chip
# runs, PR 47; docs/design/kernels.md has the table). The forward at 256
# / 512 / 1024 / 2048 a side: 54.73 / 37.06 / 25.71 / 28.34 ms, 11.99 /
# 7.67 / 4.79 / 4.40 ps an element it multiplies: bound by what the
# online softmax does once a step whatever the keys, so an element costs
# less the more keys a step walks, until 2048's dead elements (100.7M a
# batch and head for 1024's 83.9M and 512's 75.5M) cost more than its
# steps save. At 1024 x 1024 the step budget gives ONE head a step and
# nothing fills a head's softmax with another's matmuls: two heads 23.21
# ms, four 22.18 (16 MB of f32 scores; eight compile for 44 s where four
# take 16). The backward pair pays for every dead element with matmuls:
# dq 29.45 / 25.38 / 31.10 ms and dkv 39.10 / 32.72 / 35.73 at 256 / 512
# / 1024, so 512 as in a full call. Tried and not kept: a crossed
# forward tile multiplied by its live 512-row sub-tiles alone, 75.5M
# elements again: 21.99 for 22.18 ms (25.65 for 25.71 at one head a
# step): the crossed tiles' time is their steps', not their elements'.
_BD_TARGETS = {'fwd': (1024, 4), 'dq': (512, 1), 'dkv': (512, 1)}


def _plan(shape, causal, block_q=None, block_k=None, window=None,
          kv_heads=None, block_diffusion=None):
    """The static plan of a call on ``h`` heads of ``d`` over ``s``
    positions (``shape``: [b, h, s, d]): for each kernel the block sizes
    (the arguments, else the kernel's targets, cut to divisors of ``s``)
    and the heads a grid step holds, in whole lane blocks (with
    ``kv_heads`` fewer than ``h``: query heads of one group). Under
    ``block_diffusion`` the tiles are square and divide a copy's ``s /
    2`` rows, each kernel's its own (``_BD_TARGETS``; ``o`` and ``lse``
    are by row, so the forward's need not be the backward pair's); tiles
    asked for set all three alike."""
    _, h, s, d = shape
    group = h // (kv_heads or h)
    if block_diffusion is not None:
        asked = block_q or block_k
        if block_q and block_k and block_q != block_k:
            raise ValueError('flash_attention: the block-diffusion mask '
                             'takes square tiles, not %d x %d'
                             % (block_q, block_k))
        return Plan(**{
            kernel: _blocks(h, d, s // 2, (tile, tile), asked, asked, group,
                            1 if asked else heads)
            for kernel, (tile, heads) in _BD_TARGETS.items()})
    if _band_form(window, s, group, block_q or block_k) == 'row':
        return Plan(**{kernel: _rows(h, d, s, window, *targets)
                       for kernel, targets in _ROW_TARGETS.items()})
    return Plan(**{kernel: _blocks(h, d, s, targets, block_q, block_k, group)
                   for kernel, targets in
                   _block_targets(s, causal, window).items()})


def _plan_tags(plan, seq, causal, window=None, block_diffusion=None):
    """The plan as the ``flash.plan`` event records it. Per kernel
    (the forward's keys have no prefix): blocks, heads a step, whether
    one inner block is the row, and per (batch, head) the tiles it
    computes on (``tile_q`` x ``tile_k``: the block, or under the causal
    one-pass the squares of the live row), those that hold an unmasked
    position and those the causal diagonal crosses. For a band call
    the tiles are the band's: the grid walks no others. Under the
    block-diffusion mask ``tiles`` are those of the whole ``2 L x 2 L``
    square: ``live_tiles / tiles`` is the quarter that is live and the
    three diagonals' excess."""
    tags = {'band_form': None if window is None else
            'row' if isinstance(plan.fwd, Rows) else 'tiles',
            'block_diffusion': block_diffusion}
    for prefix, blocks in zip(('', 'dq_', 'dkv_'), plan):
        transposed = prefix == 'dkv_'
        if isinstance(blocks, Rows):
            # a step holds ``rows`` of the outer operand and the run
            # round them; every sub-block is one tile against its own
            # part of the run, live, and crossed by the band's edges
            rows, sub, corner, g = blocks
            (bq, bk), (tq, tk) = ((n, n + 2 * corner) for n in (rows, sub))
            if transposed:
                bq, bk, tq, tk = bk, bq, tk, tq
            one_pass, tiles = True, (seq // sub,) * 3
        else:
            bq, bk, g = blocks
            one_pass = (bq if transposed else bk) == seq
            tq, tk = bq, bk
            if causal and one_pass:
                tq = tk = bk if transposed else bq
            if block_diffusion is not None:
                tiles = _bd_tile_counts(seq // 2 // bq)
            else:
                tiles = _tile_counts(seq, tq, tk, causal, window, transposed)
        tiles, live, masked = tiles
        tags.update({prefix + 'block_q': bq, prefix + 'block_k': bk,
                     prefix + 'heads_per_step': g,
                     prefix + 'one_pass': one_pass,
                     prefix + 'tile_q': tq, prefix + 'tile_k': tk,
                     prefix + 'tiles': tiles, prefix + 'live_tiles': live,
                     prefix + 'masked_tiles': masked})
    return tags


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, interpret=None, window=None,
                    block_diffusion=None):
    """Exact attention over [batch, heads, seq, head_dim] tensors.

    Differentiable (custom VJP, flash backward). Requires ``seq`` to
    split into uniform blocks (``supports()``); callers fall back to the
    jnp path otherwise. Block sizes default to measured per-kernel
    targets (``_block_targets``). ``interpret`` defaults to True on the
    CPU backend only (so the same kernel code runs on the CPU test
    mesh); any other backend compiles the kernel or fails.

    ``window = (left, right)`` (static) keeps, for query ``i``, the keys
    ``i - left .. i + right``: a band. The kernels then walk the band's
    tiles only and are named ``flash_fwd_band``, ``flash_dq_band`` and
    ``flash_dkv_band``; with ``window=None`` the call is what it is
    without the argument. Under ``causal=True`` the band is ``(left,
    0)`` and holds the mask. k and v may have fewer heads than q, a
    divisor of its count (grouped kv heads; ``supports``): query head
    ``i`` attends kv head ``i // group``.

    ``block_diffusion = B`` (static; with ``causal=False`` and no
    ``window``) is the block-diffusion training mask over the ``seq = 2
    L`` rows of a noised copy followed by the clean one, in blocks of
    ``B`` positions (the section "A block-diffusion call" above has the
    four rules); the kernels are named ``flash_fwd_bd``, ``flash_dq_bd``
    and ``flash_dkv_bd``.

    The kernels work on ``[batch, seq, heads * head_dim]``
    (:func:`flash_attention_merged`); this is that call between the
    transposes into its layout and out of it, for callers that hold
    ``[b, h, s, d]`` (ring and Ulysses attention, ``chip_smoke.py``).

    Each trace leaves one ``flash.plan`` point event in the loop ring
    (``telemetry.get().loop_records()``): the static plan of the call
    (``_plan_tags``).
    """
    b, h, s, d = q.shape
    o = _planned(tuple(jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, -1)
                       for x in (q, k, v)), None, h, k.shape[1], causal,
                 sm_scale, block_q, block_k, interpret, window, named=False,
                 block_diffusion=block_diffusion)
    return jnp.transpose(o.reshape(b, s, h, d), (0, 2, 1, 3))


def flash_attention_merged(qkv, heads, causal=True, sm_scale=None,
                           interpret=None, window=None, rotary=None,
                           kv_heads=None, block_diffusion=None):
    """:func:`flash_attention` in the kernels' own layout, which is the
    model's: ``qkv`` is the qkv projection's output
    ``[batch, seq, (heads + 2 * kv_heads) * head_dim]`` (q, k and v side
    by side, read where they lie) or a tuple of the three as
    ``[batch, seq, heads * head_dim]`` (k and v: ``kv_heads``) each, and
    the output is ``[batch, seq, heads * head_dim]``, what the output
    projection takes. Nothing is transposed, copied or padded on the way
    in or out. ``kv_heads`` defaults to ``heads``; with fewer (grouped
    kv heads) a grid step holds query heads of one group and reads the
    group's k and v block once for all of them, and ``flash_dkv`` adds
    a kv head's dk and dv up over its group inside the kernel: no copy
    of k or v repeated to the query heads exists.

    ``rotary = (cos, sin)`` puts rotary positions on q and k inside the
    kernels: the tables of :func:`rotary_tables`, ``[seq, lane block]``
    in f32. Every kernel then rotates the q and k blocks it loads, on
    the tile, as ``x cos + turn(x) sin`` in f32 rounded once to the
    operands' dtype, ``flash_dq`` and ``flash_dkv`` turn dq and dk back,
    and the cotangent is that of the unrotated ``qkv``: the call is
    ``flash_attention_merged`` of the rotated q and k, without their
    copies. With ``rotary=None`` nothing of it is on the path. The
    tables are read by row, so positions that repeat (``0 .. L - 1``
    twice under ``block_diffusion``) are tables made from such
    positions.

    The forward rule's residuals are named for a checkpoint policy
    (``CHECKPOINT_NAMES``): that output, and ``lse``. Under
    ``jax.checkpoint(block, policy=save_only_these_names(
    *CHECKPOINT_NAMES))`` the backward pass then recomputes q, k and v
    but runs no forward kernel again. :func:`saved_bytes` is what a call
    keeps. Without such a policy the names mean nothing."""
    if not isinstance(qkv, (tuple, list)):
        qkv = (qkv,)
    return _planned(tuple(qkv), rotary, heads, kv_heads or heads, causal,
                    sm_scale, None, None, interpret, window, named=True,
                    block_diffusion=block_diffusion)


def rotary_angles(positions, theta, head_dim):
    """``(cos, sin)`` of rotary positions, ``[len(positions), head_dim /
    2]`` in f32. ``theta`` is a base (``inv_freq_j = theta ** (-2j /
    head_dim)``) or a pair ``(inv_freq, factor)``: the ``head_dim / 2``
    frequencies themselves and a factor on ``cos`` and ``sin`` (YaRN:
    ``models/attention.rope_frequencies``)."""
    factor = None
    if isinstance(theta, tuple):
        inv_freq, factor = theta
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    else:
        inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (cos, sin) if factor is None else (cos * factor, sin * factor)


def rotary_tables(positions, theta, heads, head_dim):
    """``(cos, sin)`` of rotary positions for :func:`flash_attention_merged`
    on ``heads`` (local) heads of ``head_dim``: ``[len(positions), lanes
    of a lane block]`` in f32, the head's table (:func:`rotary_angles` of
    ``theta``; the rotate-half convention: both halves of a head carry
    the same angles) repeated over the heads of a lane block."""
    cos, sin = rotary_angles(positions, theta, head_dim)
    repeats = 2 * _lane_block(heads, head_dim) // head_dim
    return (jnp.concatenate([cos] * repeats, axis=-1),
            jnp.concatenate([sin] * repeats, axis=-1))


def saved_bytes(shape, dtype, v_dim=None):
    """Bytes that a :func:`flash_attention_merged` call on ``h`` heads
    of ``d`` (``shape``: [b, h, s, d]) in ``dtype`` names for the
    checkpoint: ``o`` and the f32 ``lse``. ``v_dim``: the width of a v
    head (and so of ``o``'s) where it is another than q's and k's
    (:func:`flash_attention_latent`)."""
    b, h, s, d = shape
    return b * h * s * ((v_dim or d) * jnp.dtype(dtype).itemsize + 4)


def _planned(qkv, tables, heads, kv_heads, causal, sm_scale, block_q, block_k,
             interpret, window, named, block_diffusion=None):
    block_diffusion = check_block_diffusion(block_diffusion, causal, window)
    window = check_window(window, causal)
    causal = causal and window is None     # a causal band holds the mask
    b, s, _ = qkv[0].shape
    d = _head_dim(qkv, heads, kv_heads)
    lanes = _lane_block(heads, d)
    if kv_heads != heads and (heads % kv_heads or lanes != d):
        raise ValueError('flash_attention: %d query heads over %d kv heads '
                         'of %d lanes is not supported (grouped kv heads '
                         'need a head to be a lane block); check supports() '
                         'first' % (heads, kv_heads, d))
    if tables is not None:
        tables = tuple(tables)
        if [(t.shape, t.dtype) for t in tables] != [((s, lanes),
                                                     jnp.float32)] * 2:
            raise ValueError(
                'flash_attention: rotary=(cos, sin) must be two f32 [%d, %d] '
                '(seq, lanes of a lane block: rotary_tables); got %s'
                % (s, lanes, [(t.shape, str(t.dtype)) for t in tables]))
    if len(qkv) == 1 and lanes % _LANES:
        # a run of columns narrower than the lanes cannot be blocked out
        # of a wider array (models of under 128 lanes in all)
        qkv = tuple(jnp.split(qkv[0], 3, axis=-1))
    if sm_scale is None:
        sm_scale = d ** -0.5
    sm_scale = float(sm_scale)
    if block_diffusion is not None:
        if not supports((b, heads, s, d), block_q or block_k or _LANES,
                        kv_heads=kv_heads, block_diffusion=block_diffusion):
            raise ValueError(
                'flash_attention: block_diffusion=%d over %d rows (two '
                'copies of %s) is not supported: a copy splits into tiles '
                'of whole blocks whose length is a power of two; check '
                'supports() first' % (block_diffusion, s, s / 2))
    plan = _plan((b, heads, s, d), causal, block_q, block_k, window,
                 kv_heads, block_diffusion)
    if interpret is None:
        interpret = _interpret_default()
    telemetry.get().loop_event(
        'flash.plan', seq=s, head_dim=d, causal=bool(causal),
        fold_scale=_is_pow2(sm_scale),
        window=None if window is None else list(window),
        layout='bsd', lane_block=lanes, heads_per_lane_block=lanes // d,
        rotary=tables is not None, kv_heads=kv_heads,
        **_plan_tags(plan, s, causal, window, block_diffusion))
    return _flash(qkv, tables, heads, kv_heads, causal, sm_scale, plan,
                  interpret, window, named, block_diffusion)


# ---------------------------------------------------------------------------
# latent attention: a q/k head in two parts, one rotary key for all heads
# ---------------------------------------------------------------------------
#
# ``flash_attention_latent`` (multi-head latent attention as DeepSeek-V2
# publishes it, on the training path: k and v are expanded from the
# latent before the call). A q/k head is two parts: ``nope`` lanes of
# its own (a multiple of 128: a lane block a head, for q and for k) and
# ``rope`` lanes (128 / n: 64 is two heads to a lane block, the layout
# the kernels have at head_dim 64) whose KEY is one for all heads, as a
# kv head is for its group. The score is the sum of the two
# contractions, the rotary part alone is rotated on the tile, v and the
# output are ``v`` lanes a head (a multiple of 128). Operands, read where
# the projections wrote them:
#
# * ``q [b, s, heads * (nope + rope)]``: for every ``per = 128 / rope``
#   heads in turn their nope parts and then their rope parts, which are
#   one lane block (:func:`latent_columns` has the order). A grid step
#   holds whole such groups, so q is ONE operand and dq ONE result of
#   its shape.
# * ``kv [b, s, heads * (nope + v)]``: all the heads' k_nope, then all
#   their v: two runs of columns of one array, an index map each, and
#   the cotangent one such array (``flash_dkv_mla`` writes dk_nope into
#   its first run, dv goes over the second).
# * ``c [b, s, >= 128]``, whose first ``rope`` columns are the rotary
#   key (the down-projection's output, the key first): the kernels fetch
#   its first lane block and copy the key onto the block's other heads'
#   lanes on the tile (:func:`_shared_key`); no copy of it repeated to
#   the heads exists in HBM. ``flash_dkv_mla`` walks all the heads of a
#   kv-block in its inner grid dimension and adds their dk_rope up in
#   VMEM, as a kv head's is over its group.
#
# One body a kernel: the online-softmax / accumulating form at any
# number of inner blocks (a row of one block is a walk of one step).

Latent = collections.namedtuple('Latent', 'nope rope v')


def _per_block(dims):
    return _LANES // dims.rope


def _group_lanes(dims):
    """Lanes of one group of q: ``per`` heads' nope parts and their one
    lane block of rope parts."""
    return _per_block(dims) * dims.nope + _LANES


def latent_group(heads, dims):
    """Heads whose parts lie together in q: those whose rope parts fill
    a lane block where the kernels can run (:func:`supports_latent`),
    else all of them (a tiny model's, which runs under XLA)."""
    dims = Latent(*dims)
    return _per_block(dims) if supports_latent((1, heads, _LANES, 0), dims) \
        else heads


def latent_columns(heads, dims):
    """For each column of the kernels' q ``[.., heads * (nope + rope)]``
    the column of the head-major ``[heads, nope | rope]`` layout (the
    published one) that it holds: ``q_kernel = q_published[...,
    latent_columns(heads, dims)]``."""
    dims = Latent(*dims)
    per, width = latent_group(heads, dims), dims.nope + dims.rope
    cols = []
    for first in range(0, heads, per):
        group = range(first, first + per)
        cols += [h * width + i for h in group for i in range(dims.nope)]
        cols += [h * width + dims.nope + i for h in group
                 for i in range(dims.rope)]
    return cols


def supports_latent(shape, dims):
    """Whether :func:`flash_attention_latent` can run for ``heads`` heads
    over ``s`` positions (``shape``: [b, heads, s, .]) at the head's
    parts ``dims = (nope, rope, v)``."""
    dims = Latent(*dims)
    _, h, s, _ = shape
    return (_pick_block(s, 128) is not None and dims.nope % _LANES == 0
            and dims.v % _LANES == 0 and 0 < dims.rope <= _LANES
            and _LANES % dims.rope == 0 and dims.rope % 2 == 0
            and h % _per_block(dims) == 0 and dims.nope % dims.v == 0)


def preferred_latent(shape, dims):
    """:func:`preferred` for a latent call: the same sequences."""
    s = shape[2]
    return (s >= MIN_KERNEL_SEQ and (s % _LANES == 0 or s <= 256)
            and supports_latent(shape, dims))


def _shared_key(kr_ref, tables, dims, rows=_ALL):
    """The one rotary key of a k-block, rotated, on every head's lanes
    of a lane block: ``[rows, 128]`` in the operand's dtype. The fetched
    block holds the key in its first ``rope`` lanes (what lies beside it
    is the latent's: overwritten here)."""
    key = kr_ref[0, rows, :]
    x = key.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    width = dims.rope
    while width < _LANES:
        x = jnp.where(lane < width, x, pltpu.roll(x, width, 1))
        width *= 2
    cos_ref, sin_ref = tables
    return _rotate(x, cos_ref[rows, :], sin_ref[rows, :],
                   dims.rope).astype(key.dtype)


def _rope_parts(q_ref, cols, tables, dims):
    """The rope parts of a lane block's heads (columns ``cols`` of the
    step's q), rotated: ``[bq, 128]`` in the operand's dtype."""
    x = q_ref[0, :, cols]
    cos_ref, sin_ref = tables
    return _rotate(x, cos_ref[...], sin_ref[...], dims.rope).astype(x.dtype)


def _q_lanes(g, dims):
    """Lanes of q that a step of ``g`` heads holds: whole groups."""
    return g // _per_block(dims) * _group_lanes(dims)


def _latent_heads(g, dims):
    """The heads of a step that holds ``g``: for each group of q its
    rope block's columns and its heads as ``(head of the step, columns
    of its nope part in q, mask of its rope lanes)``."""
    per, lanes, nope = _per_block(dims), _group_lanes(dims), dims.nope
    return [(slice(c * lanes + per * nope, (c + 1) * lanes),
             [(c * per + i,
               slice(c * lanes + i * nope, c * lanes + (i + 1) * nope),
               _head_lanes(_LANES, dims.rope, i)) for i in range(per)])
            for c in range(g // per)]


def _head_cols(h, width):
    return slice(h * width, (h + 1) * width)


def _latent_scores(qn, kn, qr, kr, sm_scale, mask, transposed=False):
    """Masked, scaled sum of the two contractions in f32: ``[bq, bk]``,
    or ``[bk, bq]`` for the transposed tile of ``flash_dkv_mla``. ``qr``
    has the lanes of the block's other heads zeroed."""
    if transposed:
        s = _dot(kn, qn, _NT) + _dot(kr, qr, _NT)
    else:
        s = _dot(qn, kn, _NT) + _dot(qr, kr, _NT)
    s = s * sm_scale
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _fwd_latent_kernel(q_ref, kn_ref, v_ref, kr_ref, cq_ref, sq_ref, ck_ref,
                       sk_ref, o_ref, lse_ref, acc_scr, m_scr, l_scr, *,
                       sm_scale, causal, bq, bk, nq, nk, g, dims):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def tile(mask):
        kr = _shared_key(kr_ref, (ck_ref, sk_ref), dims)
        for rope_cols, heads in _latent_heads(g, dims):
            qr = _rope_parts(q_ref, rope_cols, (cq_ref, sq_ref), dims)
            for h, nope_cols, keep in heads:
                s = _latent_scores(q_ref[0, :, nope_cols],
                                   kn_ref[0, :, _head_cols(h, dims.nope)],
                                   _only(qr, keep), kr, sm_scale, mask)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1,
                                                      keepdims=True)
                m_scr[h] = m_new
                v = v_ref[0, :, _head_cols(h, dims.v)]
                acc_scr[h] = acc_scr[h] * alpha + _dot(p.astype(v.dtype), v,
                                                       _NN)

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal)

    @pl.when(ki == nk - 1)
    def _emit():
        for h in range(g):
            l = l_scr[h]
            lse_ref[0, h] = _to_row(m_scr[h] + jnp.log(l))
            o_ref[0, :, _head_cols(h, dims.v)] = (acc_scr[h] / l).astype(
                o_ref.dtype)


def _dq_latent_kernel(q_ref, kn_ref, v_ref, kr_ref, do_ref, o_ref, lse_ref,
                      cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, delta_ref,
                      dq_scr, delta_scr, *, sm_scale, causal, bq, bk, nq, nk,
                      g, dims):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        for h in range(g):
            cols = _head_cols(h, dims.v)
            delta = jnp.sum(do_ref[0, :, cols].astype(jnp.float32)
                            * o_ref[0, :, cols].astype(jnp.float32),
                            axis=1, keepdims=True)
            delta_scr[h] = delta
            delta_ref[0, h] = _to_row(delta)

    def tile(mask):
        kr = _shared_key(kr_ref, (ck_ref, sk_ref), dims)
        for rope_cols, heads in _latent_heads(g, dims):
            qr = _rope_parts(q_ref, rope_cols, (cq_ref, sq_ref), dims)
            dqr = None
            for h, nope_cols, keep in heads:
                kn = kn_ref[0, :, _head_cols(h, dims.nope)]
                s = _latent_scores(q_ref[0, :, nope_cols], kn,
                                   _only(qr, keep), kr, sm_scale, mask)
                p = jnp.exp(s - _to_col(lse_ref[0, h]))
                dp = _dot(do_ref[0, :, _head_cols(h, dims.v)],
                          v_ref[0, :, _head_cols(h, dims.v)], _NT)
                ds = (p * (dp - delta_scr[h]) * sm_scale).astype(kn.dtype)
                dq_scr[:, nope_cols] = dq_scr[:, nope_cols] + _dot(ds, kn,
                                                                   _NN)
                dqr = _place(dqr, _dot(ds, kr, _NN), keep)
            dq_scr[:, rope_cols] = dq_scr[:, rope_cols] + dqr

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal)

    @pl.when(ki == nk - 1)
    def _emit():
        # the rope parts' gradient is w.r.t. the rotated q: turned back
        # from the f32 accumulator, before the one rounding of the store
        for rope_cols, heads in _latent_heads(g, dims):
            for _, nope_cols, _ in heads:
                dq_ref[0, :, nope_cols] = dq_scr[:, nope_cols].astype(
                    dq_ref.dtype)
            dq_ref[0, :, rope_cols] = _rotate(
                dq_scr[:, rope_cols], cq_ref[...], sq_ref[...], dims.rope,
                back=True).astype(dq_ref.dtype)


def _dkv_latent_kernel(q_ref, kn_ref, v_ref, kr_ref, do_ref, lse_ref,
                       delta_ref, cq_ref, sq_ref, ck_ref, sk_ref, dkn_ref,
                       dv_ref, dkr_ref, dkn_scr, dv_scr, dkr_scr, *, sm_scale,
                       causal, bq, bk, nq, nk, g, dims, head_steps):
    # The inner grid dimension walks the head groups (``g`` heads a
    # step) and under each the q-blocks: dk_nope and dv of a step's
    # heads add up over the q-blocks and are written when its last one
    # is done; dk_rope, one for all heads, adds up over every step of
    # the kv-block.
    ki, step = pl.program_id(2), pl.program_id(3)
    qi = step % nq

    @pl.when(step == 0)
    def _init_shared():
        dkr_scr[:] = jnp.zeros_like(dkr_scr)

    @pl.when(qi == 0)
    def _init():
        dkn_scr[:] = jnp.zeros_like(dkn_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(mask):
        kr = _shared_key(kr_ref, (ck_ref, sk_ref), dims)
        for rope_cols, heads in _latent_heads(g, dims):
            qr = _rope_parts(q_ref, rope_cols, (cq_ref, sq_ref), dims)
            for h, nope_cols, keep in heads:
                qn, mine = q_ref[0, :, nope_cols], _only(qr, keep)
                do = do_ref[0, :, _head_cols(h, dims.v)]
                s = _latent_scores(qn, kn_ref[0, :, _head_cols(h, dims.nope)],
                                   mine, kr, sm_scale, mask, transposed=True)
                p = jnp.exp(s - lse_ref[0, h])                # [bk, bq]
                cols = _head_cols(h, dims.v)
                dv_scr[:, cols] = dv_scr[:, cols] + _dot(p.astype(do.dtype),
                                                         do, _NN)
                dp = _dot(v_ref[0, :, cols], do, _NT)
                ds = (p * (dp - delta_ref[0, h]) * sm_scale).astype(qn.dtype)
                cols = _head_cols(h, dims.nope)
                dkn_scr[:, cols] = dkn_scr[:, cols] + _dot(ds, qn, _NN)
                dkr_scr[:] = dkr_scr[:] + _dot(ds, mine, _NN)

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        transposed=True)

    @pl.when(qi == nq - 1)
    def _emit():
        dkn_ref[0] = dkn_scr[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(step == head_steps * nq - 1)
    def _emit_shared():
        # a head's part lies on its own lanes of the block: added up
        # onto every head's lanes, then turned back as one key
        total, width = dkr_scr[:], _LANES // 2
        while width >= dims.rope:
            total = total + pltpu.roll(total, width, 1)
            width //= 2
        dkr_ref[0] = _rotate(total, ck_ref[...], sk_ref[...], dims.rope,
                             back=True).astype(dkr_ref.dtype)


def _latent_static(kernel, s, causal, sm_scale, blocks, dims, **more):
    bq, bk, g = blocks
    return functools.partial(kernel, sm_scale=sm_scale, causal=causal, bq=bq,
                             bk=bk, nq=s // bq, nk=s // bk, g=g, dims=dims,
                             **more)


def _latent_kv_specs(heads, dims, blocks, kv_row, group_of=_group_of):
    """Specs of k_nope and v (two runs of ``kv``) and of the rotary
    key's lane block of ``c``, by the row blocks ``kv_row``."""
    _, bk, g = blocks
    return [_rows_spec(bk, g * dims.nope, kv_row, 0, group_of),
            _rows_spec(bk, g * dims.v, kv_row, heads * dims.nope, group_of),
            pl.BlockSpec((1, bk, _LANES),
                         lambda b, h, i, j: (b, kv_row(i, j), 0))]


def _fwd_latent(q, kv, c, tables, heads, dims, causal, sm_scale, blocks,
                interpret):
    bq, bk, g = blocks
    b, s, _ = q.shape
    q_width = _q_lanes(g, dims)
    kv_row = _kv_row(causal, bq, bk)
    return pl.pallas_call(
        _latent_static(_fwd_latent_kernel, s, causal, sm_scale, blocks, dims),
        grid=(b, heads // g, s // bq, s // bk),
        in_specs=[_rows_spec(bq, q_width, _outer)]
        + _latent_kv_specs(heads, dims, blocks, kv_row)
        + _table_specs(_LANES, (bq, _outer), (bk, kv_row)),
        out_specs=[_rows_spec(bq, g * dims.v, _outer),
                   _stat_spec(g, bq, _outer)],
        out_shape=[jax.ShapeDtypeStruct((b, s, heads * dims.v), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, dims.v), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name='flash_fwd_mla',
    )(q, kv, kv, c, *tables, *tables)


def _dq_latent(q, kv, c, tables, do, o, lse, heads, dims, causal, sm_scale,
               blocks, interpret):
    """``(dq, delta)``, as :func:`_dq`."""
    bq, bk, g = blocks
    b, s, _ = q.shape
    q_width = _q_lanes(g, dims)
    kv_row = _kv_row(causal, bq, bk)
    q_spec, o_spec = (_rows_spec(bq, q_width, _outer),
                      _rows_spec(bq, g * dims.v, _outer))
    row_spec = _stat_spec(g, bq, _outer)
    return pl.pallas_call(
        _latent_static(_dq_latent_kernel, s, causal, sm_scale, blocks, dims),
        grid=(b, heads // g, s // bq, s // bk),
        in_specs=[q_spec] + _latent_kv_specs(heads, dims, blocks, kv_row)
        + [o_spec, o_spec, row_spec]
        + _table_specs(_LANES, (bq, _outer), (bk, kv_row)),
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, q_width), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name='flash_dq_mla',
    )(q, kv, kv, c, do, o, lse, *tables, *tables)


def _dkv_latent(q, kv, c, tables, do, lse, delta, heads, dims, causal,
                sm_scale, blocks, interpret):
    """``(dkv, dv, dk_rope)``: the array of ``kv``'s shape whose first
    run (dk_nope) is written, dv ``[b, s, heads * v]`` and the rotary
    key's gradient on every head's lanes of ``[b, s, 128]``."""
    bq, bk, g = blocks
    b, s, _ = q.shape
    nq = s // bq
    q_width = _q_lanes(g, dims)

    # (the dead causal tiles come first among a head group's q-blocks,
    # and ask for the first live one)
    def q_row(j, i):
        return jnp.maximum(i % nq, (j * bk) // bq) if causal else i % nq

    def q_of(h, i):
        return i // nq

    def head_cols(width, start=0):
        return pl.BlockSpec(
            (1, bk, g * width),
            lambda b, h, j, i: (b, j, start // (g * width) + i // nq))
    row_spec = _stat_spec(g, bq, q_row, q_of)
    shared = pl.BlockSpec((1, bk, _LANES), lambda b, h, j, i: (b, j, 0))
    return pl.pallas_call(
        _latent_static(_dkv_latent_kernel, s, causal, sm_scale, blocks, dims,
                       head_steps=heads // g),
        grid=(b, 1, s // bk, heads // g * nq),
        in_specs=[_rows_spec(bq, q_width, q_row, group_of=q_of),
                  head_cols(dims.nope), head_cols(dims.v, heads * dims.nope),
                  shared,
                  _rows_spec(bq, g * dims.v, q_row, group_of=q_of),
                  row_spec, row_spec]
        + _table_specs(_LANES, (bq, q_row), (bk, _outer)),
        out_specs=[head_cols(dims.nope), head_cols(dims.v), shared],
        out_shape=[jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   jax.ShapeDtypeStruct((b, s, heads * dims.v), kv.dtype),
                   jax.ShapeDtypeStruct((b, s, _LANES), c.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, g * dims.nope), jnp.float32),
                        pltpu.VMEM((bk, g * dims.v), jnp.float32),
                        pltpu.VMEM((bk, _LANES), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name='flash_dkv_mla',
    )(q, kv, kv, c, do, lse, delta, *tables, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_latent(q, kv, c, tables, heads, dims, causal, sm_scale, plan,
                  interpret, named):
    return _fwd_latent(q, kv, c, tables, heads, dims, causal, sm_scale,
                       plan.fwd, interpret)[0]


def _flash_latent_fwd(q, kv, c, tables, heads, dims, causal, sm_scale, plan,
                      interpret, named):
    o, lse = _fwd_latent(q, kv, c, tables, heads, dims, causal, sm_scale,
                         plan.fwd, interpret)
    if named:
        o = checkpoint_name(o, CHECKPOINT_NAMES[0])
        lse = checkpoint_name(lse, CHECKPOINT_NAMES[1])
    return o, (q, kv, c, tables, o, lse)


def _flash_latent_bwd(heads, dims, causal, sm_scale, plan, interpret, named,
                      res, do):
    q, kv, c, tables, o, lse = res
    dq, delta = _dq_latent(q, kv, c, tables, do, o, lse, heads, dims, causal,
                           sm_scale, plan.dq, interpret)
    dkv, dv, dkr = _dkv_latent(q, kv, c, tables, do, lse, delta, heads, dims,
                               causal, sm_scale, plan.dkv, interpret)
    dkv = jax.lax.dynamic_update_slice_in_dim(dkv, dv, heads * dims.nope,
                                              axis=2)
    # (the kernels read the key's columns of c and no other)
    dc = jnp.pad(dkr[..., :dims.rope],
                 ((0, 0), (0, 0), (0, c.shape[-1] - dims.rope)))
    return dq, dkv, dc, None


_flash_latent.defvjp(_flash_latent_fwd, _flash_latent_bwd)


def flash_attention_latent(q, kv, c, heads, dims, rotary, causal=True,
                           block_q=None, block_k=None, interpret=None,
                           sm_scale=None):
    """Exact attention of ``heads`` heads whose q/k head is ``dims.nope``
    lanes of its own beside ``dims.rope`` rotary lanes that share ONE
    key, and whose v head is ``dims.v`` wide (``dims = (nope, rope,
    v)``; :func:`supports_latent`), in the kernels' own layout (the
    section's comment above): ``q [b, s, heads * (nope + rope)]`` in
    :func:`latent_columns`' order, ``kv [b, s, heads * (nope + v)]``
    (every head's k_nope, then every head's v), ``c [b, s, >= 128]``
    whose first ``rope`` columns are the rotary key, ``rotary`` the
    tables of ``rotary_tables(positions, theta, heads, rope)``. Returns
    ``o [b, s, heads * v]``. ``sm_scale`` is the caller's (a Python
    number, static: a family whose YaRN factor multiplies the scores gives
    the product), ``(nope + rope) ** -0.5`` where it gives none; the
    kernels multiply the f32 scores by it, whatever its value. The rotary
    parts of q and the key are rotated on the tile (half-split pairs),
    their gradients turned back; the cotangents are
    those of ``q``, ``kv`` and ``c`` (zero beside the key's columns).
    The three calls are ``flash_fwd_mla``, ``flash_dq_mla``,
    ``flash_dkv_mla``; ``o`` and ``lse`` are named for a checkpoint
    policy as :func:`flash_attention_merged`'s. One ``flash.plan`` event
    a trace, with ``qk_dim``, ``v_dim``, ``rope_dim``, ``shared_rope_key``
    and ``sm_scale`` beside the plan."""
    dims = Latent(*dims)
    b, s, _ = q.shape
    shape = (b, heads, s, dims.nope + dims.rope)
    if not supports_latent(shape, dims) or c.shape[-1] < _LANES \
            or q.shape[-1] != heads * (dims.nope + dims.rope) \
            or kv.shape[-1] != heads * (dims.nope + dims.v):
        raise ValueError('flash_attention_latent: %d heads of %s over q %s, '
                         'kv %s, c %s is not supported; check '
                         'supports_latent() first'
                         % (heads, dims, q.shape, kv.shape, c.shape))
    tables = tuple(rotary)
    if [(t.shape, t.dtype) for t in tables] != [((s, _LANES),
                                                 jnp.float32)] * 2:
        raise ValueError(
            'flash_attention_latent: rotary=(cos, sin) must be two f32 [%d, '
            '%d] (rotary_tables at the rotary part\'s width); got %s'
            % (s, _LANES, [(t.shape, str(t.dtype)) for t in tables]))
    sm_scale = float((dims.nope + dims.rope) ** -0.5 if sm_scale is None
                     else sm_scale)
    per = _per_block(dims)
    plan = Plan(**{
        kernel: _latent_blocks(heads, s, targets, block_q, block_k, per)
        for kernel, targets in _block_targets(s, causal).items()})
    if interpret is None:
        interpret = _interpret_default()
    telemetry.get().loop_event(
        'flash.plan', seq=s, head_dim=dims.nope + dims.rope,
        causal=bool(causal), fold_scale=False, window=None, layout='bsd',
        lane_block=_LANES, heads_per_lane_block=per, rotary=True,
        kv_heads=heads, qk_dim=dims.nope + dims.rope, v_dim=dims.v,
        rope_dim=dims.rope, shared_rope_key=True, sm_scale=sm_scale,
        **_plan_tags(plan, s, causal))
    return _flash_latent(q, kv, c, tables, heads, dims, bool(causal),
                         sm_scale, plan, interpret, True)


def _latent_blocks(heads, seq, targets, block_q, block_k, per):
    sizes = [seq if not asked and seq <= target
             else _pick_block(seq, asked or target)
             for asked, target in zip((block_q, block_k), targets)]
    return Blocks(*sizes, _heads_per_step(heads, *sizes, per))
