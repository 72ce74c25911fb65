"""Pallas TPU flash attention: block-tiled exact attention, fwd + bwd.

The reference has no attention kernels at all (its models are TF graphs;
SURVEY.md §2.3 lists no TP/SP) — this is TPU-native greenfield, the block
primitive promised by parallel/ring_attention.py. Algorithm is the public
flash-attention-2 recipe: the score matrix is never materialized in HBM;
each (Q-block × KV-block) tile runs on the MXU, softmax statistics stay
in f32, and the backward pass recomputes P from the saved logsumexp
instead of storing it.

Layout: q/k/v are [batch, heads, seq, head_dim]. Three ``pallas_call``s
(``flash_fwd``, ``flash_dq``, ``flash_dkv``; the benchmark reads them by
these names) share one grid shape, (batch, heads / G, outer blocks,
inner blocks): a grid step holds G heads of one tile and walks them in
an unrolled loop, so the fixed cost of a step is paid once for G tiles
and the scheduler can fill one head's softmax with the next one's
matmuls. The inner dimension is sequential ("arbitrary") and carries
the VMEM accumulators; the rest are parallel.

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
* A **band call** (``window = (left, right)``: query i sees keys
  i - left .. i + right; ModernBERT's window layers) is planned from
  the band and not from the sequence: blocks as wide as the band
  reaches to one side (:func:`_band_targets`), an inner grid dimension
  as long as the longest run of inner blocks an outer block's band
  reaches (:func:`_band_inner_blocks`; three at 128 x 128 blocks and
  64 keys each side), index maps that walk that run and clamp the steps outside
  the sequence onto a block the pipeline already holds
  (:func:`_band_fetch`), and the band's mask only on tiles an edge of
  the band crosses. The three calls are then named ``flash_fwd_band``,
  ``flash_dq_band``, ``flash_dkv_band``. With ``window=None`` nothing
  of this is on the path.
* A softmax scale that is a power of two (head_dim 16, 64, 256) is
  folded into ``q`` ([bq, d]) instead of multiplying every [bq, bk]
  tile, which is exact in any binary float format; any other scale
  stays on the tile.

Where the time goes at head_dim 64 on a v5e (PERF.md §6, PR 25): QK^T
contracts over 64 and PV yields 64 columns, so every matmul fills half
of the 128 x 128 MXU, and q/k/v/o are lane-padded to 128 in HBM. The
backward pair runs within 5% of that half-filled-MXU bound at seq 512,
the forward within 20% of its (padded) HBM traffic.

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


# Targets of a band call at ModernBERT's reach (64 keys each side), from
# a sweep of {128, 256, 512}^2 at [4, 16, 8192, 64] on a v5e (my chip
# runs, PR 26; PERF.md §6): ms a call, forward / dq / dkv, 3.81 / 3.09 /
# 2.51 at 128 x 128 for all three against 3.54 / 2.39 / 2.35 here. A
# tile costs about 0.19 us a head whatever its size, and an element of
# it 7.6 ps, so tiles larger than the band pay in elements what they
# save in steps; each kernel settles elsewhere.
_BAND_TARGETS = {'fwd': (128, 512), 'dq': (256, 256), 'dkv': (128, 256)}


def _band_targets(window):
    """Block targets of a band call: the measured targets for a band
    that reaches up to a lane-wide block (128) to either side, scaled up
    by powers of two for a wider one, so that an outer block meets a
    few inner blocks and the tiles stay close to the band's width."""
    reach = max(window)
    scale = 1
    while scale * _LANES < reach:
        scale *= 2
    return {kernel: (scale * bq, scale * bk)
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


def _heads_per_step(heads, bq, bk):
    """Largest divisor of the (local) head count, at most
    ``_MAX_HEADS_PER_STEP``, whose tiles stay inside the step budget."""
    target = max(1, min(_MAX_HEADS_PER_STEP, _STEP_TILE_ELEMS // (bq * bk)))
    return max(g for g in range(1, target + 1) if heads % g == 0)


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


def check_window(window, causal=False):
    """``window`` as a pair of ints, or None; a band under a causal mask
    is refused, not guessed at."""
    if window is None:
        return None
    left, right = (int(w) for w in window)
    if left < 0 or right < 0:
        raise ValueError('flash_attention: window %r must be (left, right) '
                         'with neither negative' % (window,))
    if causal:
        raise ValueError('flash_attention: a window under a causal mask is '
                         'not supported; pass causal=False, or window='
                         '(left, 0) for a causal band')
    return left, right


def supports(shape, block=128, window=None):
    """Whether flash_attention can run for [B, H, S, D] (S divisible
    into >=8-row blocks), with or without a ``window``."""
    check_window(window)
    s = shape[2]
    return _pick_block(s, block) is not None


# Crossover with XLA's fused attention, on the model path
# (``MultiHeadAttention``: ``preferred``). Not re-measured since the
# kernels got faster (PERF.md §7): on the ledger (PR 25) BERT-large
# reaches 53.4% MFU at seq 128 under XLA's attention and 46.1% at seq
# 512 under these kernels (38.5% before PR 25); no cell sits between.
MIN_KERNEL_SEQ = 512


def preferred(shape, window=None):
    """True when the Pallas kernel is expected to beat XLA's fused
    attention for this [B, H, S, D] shape; the same sequences for a
    band call (``window``) as for a full one, where XLA's side is the
    blocked band of ``local_flash_attention``. The compiled kernels keep
    ``lse`` with the sequence along the lanes, so every block has to be
    lane-wide or the whole sequence (which it is up to the smallest
    block target, 256); a longer sequence that only splits into slivers
    is XLA's to win anyway."""
    s = shape[2]
    return (s >= MIN_KERNEL_SEQ and (s % _LANES == 0 or s <= 256)
            and supports(shape, window=window))


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
                        transposed=False, window=None):
    """Run ``tile(mask)`` for the kind of tile (qi, ki) is: not at all
    for a dead one, with the causal (or the band's) mask where the
    diagonal (an edge of the band) crosses it, with ``None`` otherwise.
    A kind the static grid does not contain is not emitted."""
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
# forward
# ---------------------------------------------------------------------------

def _inner_block(outer, j, size, inner_size, window, transposed=False):
    """The inner block of grid step ``j``: ``j`` itself, or for a band
    call the ``j``-th block the band reaches from ``outer`` (which may
    lie outside the sequence: a dead tile)."""
    if window is None:
        return j
    back, _ = _band_reach(window, transposed)
    return _band_first(outer, size, inner_size, back) + j


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                sm_scale, fold, causal, bq, bk, nq, nk, g, window, n_inner):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _inner_block(qi, j, bq, bk, window)

    def query(h):
        q = q_ref[0, h]                                       # [bq, D]
        return q * sm_scale if fold else q

    def rows(parts):
        # plain softmax over the live keys of the row, by ranges
        for h in range(g):
            q = query(h)
            ss = [_scores(q, k_ref[0, h, lo:hi], sm_scale, fold, mask)
                  for lo, hi, mask in parts]
            m = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=1, keepdims=True) for s in ss])
            ps = [jnp.exp(s - m) for s in ss]
            l = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
            acc = sum(_dot(p.astype(v_ref.dtype), v_ref[0, h, lo:hi], _NN)
                      for p, (lo, hi, _) in zip(ps, parts))
            o_ref[0, h] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, h] = _to_row(m + jnp.log(l))

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
        for h in range(g):
            v = v_ref[0, h]
            s = _scores(query(h), k_ref[0, h], sm_scale, fold, mask)
            m_prev = m_scr[h]                                 # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                            # [bq, bk]
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + _dot(p.astype(v.dtype), v,
                                                   _NN)
            m_scr[h] = m_new

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        window=window)

    @pl.when(j == n_inner - 1)
    def _emit():
        for h in range(g):
            l = l_scr[h]
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)
            lse_ref[0, h] = _to_row(m_scr[h] + jnp.log(l))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'parallel', 'parallel', 'arbitrary'),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _inner_blocks(s, blocks, window, transposed=False):
    """Length of the inner (sequential) grid dimension: every block of
    the inner operand, or the longest run of them a band reaches."""
    bq, bk, _ = blocks
    size, inner = (bk, bq) if transposed else (bq, bk)
    if window is None:
        return s // inner
    return _band_inner_blocks(s, size, inner, window, transposed)


def _static(kernel, s, causal, sm_scale, blocks, window, transposed=False):
    """``kernel`` with what a call fixes at trace time."""
    bq, bk, g = blocks
    return functools.partial(
        kernel, sm_scale=sm_scale, fold=_is_pow2(sm_scale), causal=causal,
        bq=bq, bk=bk, nq=s // bq, nk=s // bk, g=g, window=window,
        n_inner=_inner_blocks(s, blocks, window, transposed))


def _name(kernel, window):
    """The ``pallas_call`` name: a band call is told from a full one in
    a trace (``flash_fwd_band``), and the benchmark reads both."""
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


def _kv_index(causal, bq, bk, window=None, seq=None):
    """Index map of a K/V block on a (b, h, qi, ki) grid. A dead causal
    tile asks for the last live block of its row again, which the
    pipeline already holds, so it fetches nothing; a band call walks the
    band's blocks only (:func:`_band_fetch`)."""
    if window is not None:
        return lambda b, h, i, j: (
            b, h, _band_fetch(i, j, bq, bk, seq, window), 0)
    if not causal:
        return lambda b, h, i, j: (b, h, j, 0)
    return lambda b, h, i, j: (
        b, h, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0)


def _fwd(q, k, v, causal, sm_scale, blocks, interpret, window=None):
    b, h, s, d = q.shape
    bq, bk, g = blocks
    nq, nk = s // bq, s // bk
    kv_index = _kv_index(causal, bq, bk, window, s)
    scratch = [] if nk == 1 else [
        pltpu.VMEM((g, bq, d), jnp.float32),
        pltpu.VMEM((g, bq, 1), jnp.float32),
        pltpu.VMEM((g, bq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        _static(_fwd_kernel, s, causal, sm_scale, blocks, window),
        grid=(b, h // g, nq, _inner_blocks(s, blocks, window)),
        in_specs=[
            pl.BlockSpec((1, g, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, g, bk, d), kv_index),
            pl.BlockSpec((1, g, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, g, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, g, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_fwd', window),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, sm_scale, fold, causal, bq, bk, nq, nk, g, window,
               n_inner):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _inner_block(qi, j, bq, bk, window)

    def grad(h, parts):
        """dq of head ``h`` from the key ranges ``parts`` of the block."""
        q = q_ref[0, h]
        if fold:
            q = q * sm_scale
        do = do_ref[0, h]
        lse = _to_col(lse_ref[0, h])                          # [bq, 1]
        delta = _to_col(delta_ref[0, h])
        dq = 0.
        for lo, hi, mask in parts:
            k = k_ref[0, h, lo:hi]
            s = _scores(q, k, sm_scale, fold, mask)
            p = jnp.exp(s - lse)                              # [bq, keys]
            dp = _dot(do, v_ref[0, h, lo:hi], _NT)
            ds = p * (dp - delta)
            if not fold:
                ds = ds * sm_scale
            dq = dq + _dot(ds.astype(k.dtype), k, _NN)
        return dq

    def finish(dq):
        # a folded scale multiplied q, not the tile: give dq its factor
        return (dq * sm_scale if fold else dq).astype(dq_ref.dtype)

    def rows(parts):
        for h in range(g):
            dq_ref[0, h] = finish(grad(h, parts))

    if nk == 1:
        _for_the_live_row(rows, qi, nq, bq, bk, causal, window=window)
        return

    dq_scr, = scratch

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(mask):
        for h in range(g):
            dq_scr[h] = dq_scr[h] + grad(h, [(0, bk, mask)])

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        window=window)

    @pl.when(j == n_inner - 1)
    def _emit():
        for h in range(g):
            dq_ref[0, h] = finish(dq_scr[h])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch,
                sm_scale, fold, causal, bq, bk, nq, nk, g, window, n_inner):
    ki, j = pl.program_id(2), pl.program_id(3)
    qi = _inner_block(ki, j, bk, bq, window, transposed=True)

    def grads(h, parts):
        """(dk, dv) of head ``h`` from the query ranges ``parts`` of the
        block, on TRANSPOSED tiles [bk, queries]: lse and delta are rows
        of them, and both gradients plain matmuls."""
        k = k_ref[0, h]
        v = v_ref[0, h]
        dk = dv = 0.
        for lo, hi, mask in parts:
            q = q_ref[0, h, lo:hi]
            if fold:
                q = q * sm_scale       # dk = ds^T (q * scale) as well
            do = do_ref[0, h, lo:hi]
            s = _scores(k, q, sm_scale, fold, mask)           # [bk, queries]
            p = jnp.exp(s - lse_ref[0, h, :, lo:hi])
            dv = dv + _dot(p.astype(do.dtype), do, _NN)       # [bk, D]
            dp = _dot(v, do, _NT)
            ds = p * (dp - delta_ref[0, h, :, lo:hi])
            if not fold:
                ds = ds * sm_scale
            dk = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk, dv

    def rows(parts):
        for h in range(g):
            dk, dv = grads(h, parts)
            dk_ref[0, h] = dk.astype(dk_ref.dtype)
            dv_ref[0, h] = dv.astype(dv_ref.dtype)

    if nq == 1:
        _for_the_live_row(rows, ki, nk, bk, bq, causal, transposed=True,
                          window=window)
        return

    dk_scr, dv_scr = scratch

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(mask):
        for h in range(g):
            dk, dv = grads(h, [(0, bq, mask)])
            dk_scr[h] = dk_scr[h] + dk
            dv_scr[h] = dv_scr[h] + dv

    _for_each_tile_kind(tile, qi, ki, bq, bk, nq * bq, causal,
                        transposed=True, window=window)

    @pl.when(j == n_inner - 1)
    def _emit():
        for h in range(g):
            dk_ref[0, h] = dk_scr[h].astype(dk_ref.dtype)
            dv_ref[0, h] = dv_scr[h].astype(dv_ref.dtype)


def _dq(q, k, v, do, lse, delta, causal, sm_scale, blocks, interpret,
        window=None):
    b, h, s, d = q.shape
    bq, bk, g = blocks
    nq, nk = s // bq, s // bk
    q_spec = pl.BlockSpec((1, g, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, g, bk, d),
                           _kv_index(causal, bq, bk, window, s))
    row_spec = pl.BlockSpec((1, g, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    return pl.pallas_call(
        _static(_dq_kernel, s, causal, sm_scale, blocks, window),
        grid=(b, h // g, nq, _inner_blocks(s, blocks, window)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((g, bq, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_dq', window),
    )(q, k, v, do, lse, delta)


def _dkv(q, k, v, do, lse, delta, causal, sm_scale, blocks, interpret,
         window=None):
    b, h, s, d = q.shape
    bq, bk, g = blocks
    nq, nk = s // bq, s // bk
    # the grid iterates q-blocks innermost for each kv-block; the dead
    # causal tiles come first there, and ask for the first live q-block;
    # a band call walks the q-blocks its kv-block is seen from
    if window is not None:
        def q_row(j, i):
            return _band_fetch(j, i, bk, bq, s, window, transposed=True)
    elif causal:
        def q_row(j, i):
            return jnp.maximum(i, (j * bk) // bq)
    else:
        def q_row(j, i):
            return i
    q_spec = pl.BlockSpec(
        (1, g, bq, d), lambda b, h, j, i: (b, h, q_row(j, i), 0))
    kv_spec = pl.BlockSpec((1, g, bk, d), lambda b, h, j, i: (b, h, j, 0))
    row_spec = pl.BlockSpec(
        (1, g, 1, bq), lambda b, h, j, i: (b, h, 0, q_row(j, i)))
    return pl.pallas_call(
        _static(_dkv_kernel, s, causal, sm_scale, blocks, window,
                transposed=True),
        grid=(b, h // g, nk, _inner_blocks(s, blocks, window,
                                           transposed=True)),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
        ],
        scratch_shapes=[] if nq == 1 else [
            pltpu.VMEM((g, bk, d), jnp.float32),
            pltpu.VMEM((g, bk, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_name('flash_dkv', window),
    )(q, k, v, do, lse, delta)


def _bwd(q, k, v, do, lse, delta, causal, sm_scale, plan, interpret, window):
    args = (q, k, v, do, lse, delta, causal, sm_scale)
    dk, dv = _dkv(*args, plan.dkv, interpret, window)
    return _dq(*args, plan.dq, interpret, window), dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

# What the forward rule of a ``merged`` call names for a checkpoint
# policy (``jax.checkpoint_policies.save_only_these_names``): its output
# and its row statistics, all the backward needs besides q, k and v.
CHECKPOINT_NAMES = ('flash_o', 'flash_lse')


def _merge_heads(o):
    b, h, s, d = o.shape
    return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, h * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, plan, interpret, window=None,
           merged=False):
    o, _ = _fwd(q, k, v, causal, sm_scale, plan.fwd, interpret, window)
    return _merge_heads(o) if merged else o


def _flash_fwd(q, k, v, causal, sm_scale, plan, interpret, window, merged):
    o, lse = _fwd(q, k, v, causal, sm_scale, plan.fwd, interpret, window)
    if not merged:
        return o, (q, k, v, o, lse)
    # o is named in the layout it is kept in, heads x head_dim along the
    # lanes: [b, h, s, 64] is lane-padded to twice its bytes in HBM. lse
    # stays as the kernel writes it (XLA tiles the stack of [b, h, 1, s]
    # T(1, 128): the unit dimension pads nothing). o is named as its
    # BITS: on a floating-point residual that the forward pass uses too,
    # jax.checkpoint puts a reduce_precision, which XLA runs right after
    # the custom call as a pass of its own over the padded o (0.6 ms a
    # layer at [96, 16, 512, 64]: PERF.md §6, PR 27). The value is the
    # kernel's own rounding already, what the caller gets is a bitcast
    # of what is kept, and the barrier keeps that bitcast behind the
    # layout copy, where it fuses into the write to the stack.
    bits = jnp.dtype('uint%d' % (8 * o.dtype.itemsize))
    o = checkpoint_name(
        jax.lax.bitcast_convert_type(
            jax.lax.optimization_barrier(_merge_heads(o)), bits),
        CHECKPOINT_NAMES[0])
    lse = checkpoint_name(lse, CHECKPOINT_NAMES[1])
    return jax.lax.bitcast_convert_type(o, q.dtype), (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, plan, interpret, window, merged, res, do):
    q, k, v, o, lse = res
    # delta = rowsum(dO * O): tiny elementwise reduce, XLA fuses it
    if merged:
        b, h, s, d = q.shape
        do = do.reshape(b, s, h, d)
        # the barrier pins the reshape to the slice of the stack, a free
        # view: XLA else sinks it below the converts, and o in f32 becomes
        # a tensor in HBM between them and the reduce (0.6 ms a layer at
        # [96, 16, 512, 64])
        o = jax.lax.bitcast_convert_type(
            jax.lax.optimization_barrier(o.reshape(b, s, h, d)), q.dtype)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        delta = jnp.transpose(delta, (0, 2, 1))               # [B, H, S]
        do = jnp.transpose(do, (0, 2, 1, 3))
    else:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
    return _bwd(q, k, v, do, lse, delta[:, :, None, :], causal, sm_scale,
                plan, interpret, window)                      # [B, H, 1, S]


_flash.defvjp(_flash_fwd, _flash_bwd)


def _blocks(heads, seq, targets, block_q, block_k):
    sizes = []
    for asked, target in zip((block_q, block_k), targets):
        size = seq if not asked and seq <= target else \
            _pick_block(seq, asked or target)
        if size is None:
            raise ValueError('flash_attention: seq %d not blockable; check '
                             'supports() first' % seq)
        sizes.append(size)
    return Blocks(*sizes, _heads_per_step(heads, *sizes))


def _plan(shape, causal, block_q=None, block_k=None, window=None):
    """The static plan of a call on [b, h, s, d] operands: for each
    kernel the block sizes (the arguments, else the kernel's targets,
    cut to divisors of ``s``) and the heads a grid step holds."""
    _, h, s, _ = shape
    return Plan(**{kernel: _blocks(h, s, targets, block_q, block_k)
                   for kernel, targets in
                   _block_targets(s, causal, window).items()})


def _plan_tags(plan, seq, causal, window=None):
    """The plan as the ``flash.plan`` event records it. Per kernel
    (the forward's keys have no prefix): blocks, heads a step, whether
    one inner block is the row, and per (batch, head) the tiles it
    computes on (``tile_q`` x ``tile_k``: the block, or under the causal
    one-pass the squares of the live row), those that hold an unmasked
    position and those the causal diagonal crosses. For a band call
    the tiles are the band's: the grid walks no others."""
    tags = {}
    for prefix, (bq, bk, g) in zip(('', 'dq_', 'dkv_'), plan):
        transposed = prefix == 'dkv_'
        one_pass = (bq if transposed else bk) == seq
        tq, tk = bq, bk
        if causal and one_pass:
            tq = tk = bk if transposed else bq
        tiles, live, masked = _tile_counts(seq, tq, tk, causal, window,
                                           transposed)
        tags.update({prefix + 'block_q': bq, prefix + 'block_k': bk,
                     prefix + 'heads_per_step': g,
                     prefix + 'one_pass': one_pass,
                     prefix + 'tile_q': tq, prefix + 'tile_k': tk,
                     prefix + 'tiles': tiles, prefix + 'live_tiles': live,
                     prefix + 'masked_tiles': masked})
    return tags


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, interpret=None, window=None):
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
    without the argument. A window under ``causal=True`` is an error.

    Each trace leaves one ``flash.plan`` point event in the loop ring
    (``telemetry.get().loop_records()``): the static plan of the call
    (``_plan_tags``).
    """
    return _planned(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    window, merged=False)


def flash_attention_merged(q, k, v, causal=True, sm_scale=None,
                           interpret=None, window=None):
    """:func:`flash_attention` for a model's block: the same call on
    ``[batch, heads, seq, head_dim]`` operands, with the output as
    ``[batch, seq, heads * head_dim]``, what the output projection
    takes, and the forward rule's residuals named for a checkpoint
    policy (``CHECKPOINT_NAMES``): that output, and ``lse``. Under ``jax.checkpoint(block,
    policy=save_only_these_names(*CHECKPOINT_NAMES))`` the backward
    pass then recomputes q, k and v but runs no forward kernel again;
    the backward rule takes ``do`` in the output's layout and computes
    ``delta`` from the two merged tensors. :func:`saved_bytes` is what a
    call keeps. Without such a policy the names mean nothing."""
    return _planned(q, k, v, causal, sm_scale, None, None, interpret, window,
                    merged=True)


def saved_bytes(shape, dtype):
    """Bytes that a :func:`flash_attention_merged` call on
    ``[b, h, s, d]`` operands of ``dtype`` names for the checkpoint:
    ``o`` and the f32 ``lse``."""
    b, h, s, d = shape
    return b * h * s * (d * jnp.dtype(dtype).itemsize + 4)


def _planned(q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
             merged):
    window = check_window(window, causal)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    sm_scale = float(sm_scale)
    plan = _plan(q.shape, causal, block_q, block_k, window)
    if interpret is None:
        interpret = _interpret_default()
    telemetry.get().loop_event(
        'flash.plan', seq=q.shape[2], head_dim=q.shape[3],
        causal=bool(causal), fold_scale=_is_pow2(sm_scale),
        window=None if window is None else list(window),
        **_plan_tags(plan, q.shape[2], causal, window))
    return _flash(q, k, v, causal, sm_scale, plan, interpret, window, merged)
