"""Pallas TPU kernels for a manifold-constrained hyper-connection
(``models/hyper_connections.py``): a connection's two halves, before and
after its sublayer, forward and backward, each ONE pass over the streams
where they lie (``x [rows, n dim]``, stream ``i`` the lanes ``i dim .. (i
+ 1) dim``): no transpose of the streams, no ``[.., n, dim]`` array and no
f32 copy of activation size in HBM.

The equations are the module's (``HyperConnection.coefficients``, ``read``
and ``write`` in ``jax.numpy``: the form the kernels are held to in the
tests and the one every other shape keeps): the ``phi`` product in the
streams' dtype with f32 accumulation, every coefficient, every
Sinkhorn-Knopp round and every sum in f32, each output rounded once. A
round multiplies by the exact f32 reciprocal of its ``n`` sums where the
``jax.numpy`` form divides ``n n`` numbers.

**The kernels** (named in a trace). A grid step holds a block of ``rows``
rows by all ``n dim`` lanes in VMEM; its body walks passes of ``sub`` rows
over the 128-lane blocks (:func:`_loop`: loops, ``unroll`` steps of them
unrolled when the kernel is lowered, so a body is traced once however
wide a stream is and however many rounds there are). Per-token numbers
are made with the TOKENS ON THE LANES (a coefficient is ``[1, rows]``; a
round adds and multiplies whole rows of them) and applied with the tokens
on the sublanes: one ``[128, rows]`` f32 transpose a block and direction,
through VMEM scratch.

``hc_enter_fwd``: ``x phi`` on the MXU (``phi`` padded to 128 columns: ``[rows,
128]`` f32, transposed once) and ``sum x^2`` on the first walk; then
``rsqrt``, gates, biases, ``sigmoid``, ``2 sigmoid``, the clamp, ``exp``
and the rounds; on the second walk over the block already held ``u =
H_pre x``, summed in f32 and rounded once. Out: ``u [rows, dim]`` and the
held ``H_post | H_res`` as ``[n (n + 1), rows]`` f32.

``hc_leave_fwd``: ``x``, ``y`` and the held coefficients in, ``x' = H_res x
+ H_post^T y`` out.

``hc_leave_bwd``: ``x``, ``y``, ``dx'`` and the coefficients in; ``dy``,
the ``H_res^T dx'`` part of ``dx`` and ``dH_post | dH_res`` (tokens on the
lanes) out. The products' sums over the lanes are taken a pass at a time
and placed in their coefficient's column of one ``[rows, 128]`` slab.

``hc_enter_bwd``: ``x``, ``du``, ``dH_post | dH_res`` and the part of ``dx``
that ``hc_leave_bwd`` left in (:func:`enter` hands the streams through as
its third output, which :func:`leave` reads, so that cotangent arrives
HERE and is added on the block: one more read, where XLA's add of two
cotangents is a pass of its own). It makes the logits and EVERY round
again, keeps each round's two normalised matrices and its reciprocals in
VMEM (``iters x (2 n n + 2 n) x [1, rows]`` f32: 0.8 MB at 256 rows) and
differentiates through every one of them, last to first: the forward keeps
nothing but its inputs and the ``n (n + 1)`` coefficients, and the
gradient is that of all ``iters`` rounds, not a fixed point's. Then ``dx``
(through the read, the norm and the product: ``dz phi^T`` on the MXU,
``dz = dlogits inv gate``), ``d phi`` (``dz^T x`` on the MXU, f32, into
one block that stays in VMEM over the whole grid, which is why that grid
axis is ``arbitrary``) and the lanes of ``d alpha`` and ``d bias``, which
XLA adds.

:func:`supports` says which shapes the kernels take; :func:`enter` raises
on any other: the caller keeps its ``jax.numpy`` form for those. On the
CPU backend the kernels run in Pallas interpret mode; every other backend
compiles them.
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a grid step holds, rows a pass of the body computes, and the steps
# of a walk or of the rounds that are unrolled when a kernel is lowered
# (`tools/hc_bench.py` on a v5e: runs of 4 cost the four calls 14% more
# time, runs of 28 save 1% and double the lowering; all four are bound by
# their DMAs at 650 GB/s here).
ROWS = 256
SUB = 64
UNROLL = 14
_LANES = 128
_SUBLANES = 8
# A pass's rows are whole tiles of a bf16 operand; a block's rows whole
# [128, 128] transposes.
_ROW_TILE = 16
_VMEM_CAP_BYTES = 100 << 20

Plan = collections.namedtuple('Plan',
                              'block_rows sub_rows unroll vmem_limit_bytes')
# what a call is compiled for (static, hashable)
_How = collections.namedtuple('_How',
                              'streams iters clamp eps plan interpret')


def _interpret_default():
    return jax.default_backend() == 'cpu'


def _up(n, k):
    return -(-n // k) * k


def _vmem_bytes(block, streams, dim, itemsize, iters):
    """What the largest of the four calls holds in VMEM at ``block`` rows
    (every operand block twice: the pipeline's two buffers)."""
    wide, one = block * streams * dim * itemsize, block * dim * itemsize
    table = streams * dim * _LANES * itemsize
    coeffs = _LANES * block * 4
    leave_bwd = 2 * (3 * wide + 2 * one) + 3 * coeffs
    enter_bwd = (2 * (3 * wide + one + 2 * table)
                 + 2 * _up(streams * (streams + 2), 32) * streams * dim * 4
                 + iters * _up(2 * streams * (streams + 1), _SUBLANES)
                 * block * 4 + 4 * coeffs)
    return max(leave_bwd, enter_bwd)


def plan(rows, streams, dim, dtype, iters=20):
    """How the kernels would walk ``[rows, streams * dim]`` of ``dtype``
    (a :class:`Plan`), or ``None`` where they do not take the shape."""
    dtype = jnp.dtype(dtype)
    if (dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            or streams < 2 or streams * (streams + 2) > _LANES
            or dim <= 0 or dim % _LANES or rows <= 0 or rows % _LANES):
        return None
    # the most rows up to ROWS, in whole transposes, that divide the rows
    # and fit
    room = _VMEM_CAP_BYTES - (8 << 20)
    fits = [block for block in range(_LANES, max(ROWS, _LANES) + 1, _LANES)
            if rows % block == 0 and _vmem_bytes(
                block, streams, dim, dtype.itemsize, iters) <= room]
    if not fits:
        return None
    block = fits[-1]
    sub = min(SUB, block)
    if block % sub or sub % _ROW_TILE:
        return None
    need = _vmem_bytes(block, streams, dim, dtype.itemsize, iters)
    return Plan(block, sub, UNROLL,
                min(_VMEM_CAP_BYTES, _up(need + need // 4 + (8 << 20),
                                         1 << 20)))


def supports(rows, streams, dim, dtype):
    """Whether :func:`enter` and :func:`leave` have kernels for the
    shape: streams of whole 128-lane blocks, rows that split into whole
    row blocks of whole ``(16, 128)`` tiles (and whole ``[128, 128]``
    transposes), a block that fits VMEM."""
    return plan(rows, streams, dim, dtype) is not None


# ---------------------------------------------------------------------------
# the kernels' parts
# ---------------------------------------------------------------------------

def _block(k):
    """The ``k``-th block of 128 lanes."""
    return pl.ds(pl.multiple_of(k * _LANES, _LANES), _LANES)


def _loop(steps, body, carry, unroll):
    """``carry = body(k, carry)`` for ``k`` in ``range(steps)``: a loop
    and not Python's, so that a body is traced once (``kernels/qk_norm.
    _walk``), in runs of the largest divisor of ``steps`` up to ``unroll``
    that are unrolled when the kernel is lowered (Mosaic unrolls a loop
    whole or not at all)."""
    run = max(u for u in range(1, min(unroll, steps) + 1) if steps % u == 0)
    if run == steps:
        return jax.lax.fori_loop(0, steps, body, carry, unroll=True)

    def a_run(g, carry):
        return jax.lax.fori_loop(
            0, run, lambda t, carry: body(g * run + t, carry), carry,
            unroll=True)
    return jax.lax.fori_loop(0, steps // run, a_run, carry)


def _for_each_pass(rows, sub, body):
    """``body(r)`` for every pass ``r`` of ``sub`` of the ``rows`` rows."""
    def a_pass(m, _):
        body(pl.ds(pl.multiple_of(m * sub, sub), sub))
    jax.lax.fori_loop(0, rows // sub, a_pass, None)


def _f32(ref, rows, lanes):
    return ref[rows, lanes].astype(jnp.float32)


def _row(ref, j):
    """Row ``j`` of a tokens-on-the-lanes matrix: ``[1, rows]``."""
    return ref[j:j + 1, :]


def _column(slab, j):
    """Column ``j`` of a tokens-on-the-sublanes slab, ``[sub, 1]``: it
    multiplies every lane of its rows."""
    return slab[:, j:j + 1]


def _into_columns(sub, sums):
    """A ``[sub, 128]`` slab whose column ``j`` is the sum over the lanes
    of ``acc`` for each ``(j, acc)`` of ``sums``, zero elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, _LANES), 1)
    slab = jnp.zeros((sub, _LANES), jnp.float32)
    for j, acc in sums:
        slab = jnp.where(lane == j, jnp.sum(acc, axis=-1, keepdims=True),
                         slab)
    return slab


def _product(x_ref, phi_ref, unroll):
    """``x phi`` for the block, ``[rows, 128]`` f32: a 128-lane block of
    the streams against its rows of ``phi`` a step."""
    def one(k, acc):
        at = _block(k)
        return acc + jnp.dot(x_ref[:, at], phi_ref[at, :],
                             preferred_element_type=jnp.float32)
    return _loop(x_ref.shape[1] // _LANES, one,
                 jnp.zeros((x_ref.shape[0], _LANES), jnp.float32), unroll)


def _group(j, n):
    """Which of the three gates column ``j`` of ``phi`` is under."""
    return min(j // n, 2)


def _coefficients(zt_ref, squares, alpha_ref, bias_ref, how, lanes):
    """From ``x phi`` (tokens on the lanes, ``zt_ref``) and ``sum x^2 [1,
    rows]``: ``inv = rsqrt(mean x^2 + eps)``, the logits, ``H_pre``,
    ``H_post`` (lists of ``n``) and the clamped logits of the stream mix
    (``n n``, row-major)."""
    n = how.streams
    inv = jax.lax.rsqrt(squares * (1.0 / lanes) + how.eps)
    gates = [inv * alpha_ref[g] for g in range(3)]
    logits = [_row(zt_ref, j) * gates[_group(j, n)] + bias_ref[j]
              for j in range(n * (n + 2))]
    pre = [jax.nn.sigmoid(l) for l in logits[:n]]
    post = [2.0 * jax.nn.sigmoid(l) for l in logits[n:2 * n]]
    return inv, logits, pre, post


def _a_round(m, n, eps):
    """One Sinkhorn-Knopp round of ``m`` (``n n`` rows ``[1, rows]``,
    row-major ``(i, j)``): every column (over ``i``) by its sum + eps,
    then every row (over ``j``). Returns the two normalised matrices and
    the two sets of reciprocals."""
    inv_a = [1.0 / (sum(m[i * n + j] for i in range(n)) + eps)
             for j in range(n)]
    a = [m[i * n + j] * inv_a[j] for i in range(n) for j in range(n)]
    inv_b = [1.0 / (sum(a[i * n + j] for j in range(n)) + eps)
             for i in range(n)]
    out = [a[i * n + j] * inv_b[i] for i in range(n) for j in range(n)]
    return a, out, inv_a, inv_b


def _a_round_back(g, a, out, inv_a, inv_b, n):
    """The cotangent of a round's input from that of its output ``g``:
    through ``out = a inv_b`` (rows), then ``a = m inv_a`` (columns)."""
    rows = [sum(g[i * n + j] * out[i * n + j] for j in range(n))
            for i in range(n)]
    da = [inv_b[i] * (g[i * n + j] - rows[i])
          for i in range(n) for j in range(n)]
    cols = [sum(da[i * n + j] * a[i * n + j] for i in range(n))
            for j in range(n)]
    return [inv_a[j] * (da[i * n + j] - cols[j])
            for i in range(n) for j in range(n)]


def _sinkhorn(logits, how, keep_ref=None):
    """``H_res`` (``n n`` rows) from the stream mix's logits: the clamp,
    ``exp`` and ``iters`` rounds; with ``keep_ref [iters, 2 n n + 2 n,
    rows]`` every round's matrices and reciprocals are kept there."""
    n = how.streams
    m0 = [jnp.exp(jnp.clip(l, *how.clamp)) for l in logits]

    def a_round(t, m):
        a, out, inv_a, inv_b = _a_round(m, n, how.eps)
        if keep_ref is not None:
            for k, row in enumerate(a + out + inv_a + inv_b):
                keep_ref[t, k:k + 1, :] = row
        return tuple(out)
    return m0, list(_loop(how.iters, a_round, tuple(m0), how.plan.unroll))


def _sinkhorn_back(g, m0, logits, how, keep_ref):
    """The cotangent of the stream mix's logits from ``H_res``'s, through
    every kept round, the ``exp`` and the clamp."""
    n = how.streams
    nn = n * n

    def a_round(t, g):
        t = how.iters - 1 - t
        kept = [keep_ref[t, k:k + 1, :] for k in range(2 * nn + 2 * n)]
        return tuple(_a_round_back(
            g, kept[:nn], kept[nn:2 * nn], kept[2 * nn:2 * nn + n],
            kept[2 * nn + n:], n))
    g = _loop(how.iters, a_round, tuple(g), how.plan.unroll)
    low, high = how.clamp
    return [jnp.where((l >= low) & (l <= high), d * m, 0.0)
            for l, d, m in zip(logits, g, m0)]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _enter_fwd_kernel(alpha_ref, bias_ref, x_ref, phi_ref, u_ref, held_ref,
                      zt_ref, slab_ref, *, how):
    n, sub, unroll = how.streams, how.plan.sub_rows, how.plan.unroll
    rows, lanes = x_ref.shape
    blocks = lanes // n // _LANES
    zt_ref[...] = _product(x_ref, phi_ref, unroll).T

    def sums(r):
        def one(k, acc):
            x = _f32(x_ref, r, _block(k))
            return acc + x * x
        acc = _loop(n * blocks, one, jnp.zeros((sub, _LANES), jnp.float32),
                    unroll)
        slab_ref[r, :] = _into_columns(sub, [(0, acc)])
    _for_each_pass(rows, sub, sums)
    squares = slab_ref[...].T[0:1, :]

    _, logits, pre, post = _coefficients(zt_ref, squares, alpha_ref,
                                         bias_ref, how, lanes)
    _, res = _sinkhorn(logits[2 * n:], how)
    for k, row in enumerate(post + res):
        held_ref[k:k + 1, :] = row
    for i in range(n):
        zt_ref[i:i + 1, :] = pre[i]
    slab_ref[...] = zt_ref[...].T

    def read(r):
        slab = slab_ref[r, :]
        pre = [_column(slab, i) for i in range(n)]

        def one(k, _):
            u_ref[r, _block(k)] = sum(
                pre[i] * _f32(x_ref, r, _block(i * blocks + k))
                for i in range(n)).astype(u_ref.dtype)
        _loop(blocks, one, None, unroll)
    _for_each_pass(rows, sub, read)


def _held_columns(held_ref, t_ref, slab_ref):
    """The held coefficients of the block with the tokens on the
    sublanes: ``slab_ref [rows, 128]``, ``H_post`` in its first ``n``
    columns and ``H_res`` behind them."""
    t_ref[0:held_ref.shape[0], :] = held_ref[...]
    slab_ref[...] = t_ref[...].T


def _leave_fwd_kernel(x_ref, y_ref, held_ref, out_ref, t_ref, slab_ref, *,
                      how):
    n, sub, unroll = how.streams, how.plan.sub_rows, how.plan.unroll
    rows, lanes = x_ref.shape
    blocks = lanes // n // _LANES
    _held_columns(held_ref, t_ref, slab_ref)

    def write(r):
        slab = slab_ref[r, :]
        for i in range(n):
            post = _column(slab, i)
            res = [_column(slab, n + i * n + j) for j in range(n)]

            def one(k, _):
                out_ref[r, _block(i * blocks + k)] = (
                    sum(res[j] * _f32(x_ref, r, _block(j * blocks + k))
                        for j in range(n))
                    + post * _f32(y_ref, r, _block(k))).astype(out_ref.dtype)
            _loop(blocks, one, None, unroll)
    _for_each_pass(rows, sub, write)


def _leave_bwd_kernel(x_ref, y_ref, g_ref, held_ref, dy_ref, dx_ref,
                      dheld_ref, t_ref, slab_ref, sums_ref, *, how):
    n, sub, unroll = how.streams, how.plan.sub_rows, how.plan.unroll
    rows, lanes = x_ref.shape
    blocks = lanes // n // _LANES
    _held_columns(held_ref, t_ref, slab_ref)
    zeros = jnp.zeros((sub, _LANES), jnp.float32)

    def back(r):
        slab = slab_ref[r, :]
        # dH_post[i] = sum dx'_i y, dH_res[i, j] = sum dx'_i x_j
        sums = []
        for i in range(n):
            def one(k, acc):
                g = _f32(g_ref, r, _block(i * blocks + k))
                return tuple(
                    [acc[j] + g * _f32(x_ref, r, _block(j * blocks + k))
                     for j in range(n)]
                    + [acc[n] + g * _f32(y_ref, r, _block(k))])
            acc = _loop(blocks, one, (zeros,) * (n + 1), unroll)
            sums += [(i, acc[n])] + [(n + i * n + j, acc[j])
                                     for j in range(n)]
        sums_ref[r, :] = _into_columns(sub, sums)
        post = [_column(slab, i) for i in range(n)]

        def to_y(k, _):
            dy_ref[r, _block(k)] = sum(
                post[i] * _f32(g_ref, r, _block(i * blocks + k))
                for i in range(n)).astype(dy_ref.dtype)
        _loop(blocks, to_y, None, unroll)
        for j in range(n):
            res = [_column(slab, n + i * n + j) for i in range(n)]

            def to_x(k, _):
                dx_ref[r, _block(j * blocks + k)] = sum(
                    res[i] * _f32(g_ref, r, _block(i * blocks + k))
                    for i in range(n)).astype(dx_ref.dtype)
            _loop(blocks, to_x, None, unroll)
    _for_each_pass(rows, sub, back)
    t_ref[...] = sums_ref[...].T
    dheld_ref[...] = t_ref[0:dheld_ref.shape[0], :]


def _enter_bwd_kernel(alpha_ref, bias_ref, x_ref, du_ref, dheld_ref, part_ref,
                      phi_ref, phit_ref, dx_ref, dphit_ref, small_ref,
                      zt_ref, gt_ref, slab_ref, dz_ref, keep_ref, *, how):
    n, sub, unroll = how.streams, how.plan.sub_rows, how.plan.unroll
    rows, lanes = x_ref.shape
    blocks = lanes // n // _LANES
    cols = n * (n + 2)
    padded = dphit_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)
        small_ref[...] = jnp.zeros_like(small_ref)

    zt_ref[...] = _product(x_ref, phi_ref, unroll).T
    zeros = jnp.zeros((sub, _LANES), jnp.float32)

    # sum x^2 (column n) and dH_pre[i] = sum du x_i (column i)
    def sums(r):
        squares, found = zeros, []
        for i in range(n):
            def one(k, acc):
                x = _f32(x_ref, r, _block(i * blocks + k))
                return (acc[0] + x * x,
                        acc[1] + x * _f32(du_ref, r, _block(k)))
            squares, dot = _loop(blocks, one, (squares, zeros), unroll)
            found.append((i, dot))
        slab_ref[r, :] = _into_columns(sub, found + [(n, squares)])
    _for_each_pass(rows, sub, sums)
    gt_ref[...] = slab_ref[...].T

    # the coefficients and every round again, the tokens on the lanes
    inv, logits, pre, post = _coefficients(zt_ref, _row(gt_ref, n), alpha_ref,
                                           bias_ref, how, lanes)
    m0, _ = _sinkhorn(logits[2 * n:], how, keep_ref)
    d_res = [_row(dheld_ref, n + k) for k in range(n * n)]
    d_logits = (
        [_row(gt_ref, i) * pre[i] * (1.0 - pre[i]) for i in range(n)]
        + [_row(dheld_ref, i) * post[i] * (1.0 - 0.5 * post[i])
           for i in range(n)]
        + _sinkhorn_back(d_res, m0, logits[2 * n:], how, keep_ref))
    # logits = z inv gate + bias
    z = [_row(zt_ref, j) for j in range(cols)]
    d_inv = 0.0
    for g in range(3):
        through = sum(d_logits[j] * z[j] for j in range(cols)
                      if _group(j, n) == g)
        small_ref[cols + g:cols + g + 1, :] += through * inv
        d_inv = d_inv + through * alpha_ref[g]
    for j in range(cols):
        small_ref[j:j + 1, :] += d_logits[j]
        zt_ref[j:j + 1, :] = d_logits[j] * (inv * alpha_ref[_group(j, n)])
    # inv = rsqrt(sum x^2 / lanes + eps): d(sum x^2), twice, for 2 x
    for i in range(n):
        gt_ref[padded + i:padded + i + 1, :] = pre[i]
    gt_ref[padded + n:padded + n + 1, :] = d_inv * inv * inv * inv * (
        -1.0 / lanes)
    dz_ref[...] = zt_ref[...].T.astype(dz_ref.dtype)
    slab_ref[...] = gt_ref[...].T

    # d phi^T += dz^T x (z = x phi: its cotangent is the logits' by the
    # norm and the gate)
    d_z = zt_ref[0:padded, :].astype(x_ref.dtype)

    def to_phi(k, _):
        at = _block(k)
        dphit_ref[:, at] += jnp.dot(d_z, x_ref[:, at],
                                    preferred_element_type=jnp.float32)
    _loop(n * blocks, to_phi, None, unroll)

    def to_x(r):
        slab = slab_ref[r, :]
        dz = dz_ref[r, :]
        norm = _column(slab, padded + n)
        for i in range(n):
            pre = _column(slab, padded + i)

            def one(k, _):
                at = _block(i * blocks + k)
                dx_ref[r, at] = (
                    _f32(part_ref, r, at) + norm * _f32(x_ref, r, at)
                    + pre * _f32(du_ref, r, _block(k))
                    + jnp.dot(dz, phit_ref[:, at],
                              preferred_element_type=jnp.float32)
                ).astype(dx_ref.dtype)
            _loop(blocks, one, None, unroll)
    _for_each_pass(rows, sub, to_x)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _call(kernel, name, how, rows, lanes, ins, outs, out_shape, scratch=(),
          semantics='parallel', in_place=None):
    """The ``pallas_call`` ``name`` of ``kernel`` over the row blocks of
    ``[rows, lanes]`` streams: ``ins`` and ``outs`` name their block
    specs; ``in_place`` an operand and the result written over it (a
    cotangent of the streams' size that dies in the call: a grid step has
    read its block before it writes it)."""
    n, block = how.streams, how.plan.block_rows
    nh = n * (n + 1)
    specs = {
        'smem': pl.BlockSpec(memory_space=pltpu.SMEM),
        'wide': pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        'one': pl.BlockSpec((block, lanes // n), lambda i: (i, 0)),
        'held': pl.BlockSpec((nh, block), lambda i: (0, i)),
        'phi': pl.BlockSpec((lanes, _LANES), lambda i: (0, 0)),
        'phit': pl.BlockSpec((_LANES, lanes), lambda i: (0, 0)),
        'dphit': pl.BlockSpec((_padded_cols(n), lanes), lambda i: (0, 0)),
        'small': pl.BlockSpec((_padded_cols(n), block), lambda i: (0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, how=how), grid=(rows // block,),
        in_specs=[specs[kind] for kind in ins],
        out_specs=[specs[kind] for kind in outs], out_shape=out_shape,
        scratch_shapes=list(scratch),
        input_output_aliases=dict([in_place] if in_place else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(semantics,),
            vmem_limit_bytes=how.plan.vmem_limit_bytes),
        interpret=how.interpret, name=name)


def _padded_cols(n):
    """Rows of the ``d phi^T`` block and of ``d alpha | d bias``'s: the
    ``n (n + 2)`` columns of ``phi`` and the three gates, in whole tiles
    of either dtype."""
    return _up(n * (n + 2) + 3, 2 * _ROW_TILE)


def _tokens_on_lanes(block):
    return pltpu.VMEM((_LANES, block), jnp.float32)


def _tokens_on_sublanes(block, dtype=jnp.float32):
    return pltpu.VMEM((block, _LANES), dtype)


def _padded_phi(phi):
    """``phi [lanes, n (n + 2)]`` with zero columns up to 128."""
    return jnp.pad(phi, ((0, 0), (0, _LANES - phi.shape[1])))


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# (jitted, as `kernels/qk_norm.py`'s calls are: under one `jit` of its own
# a call is traced once a process and shape, whatever the number of sites)
@functools.partial(jax.jit, static_argnames=('how',))
def _enter_fwd_call(x, phi, alpha, bias, how):
    rows, lanes = x.shape
    n, block = how.streams, how.plan.block_rows
    return _call(
        _enter_fwd_kernel, 'hc_enter_fwd', how, rows, lanes,
        ('smem', 'smem', 'wide', 'phi'), ('one', 'held'),
        [_struct((rows, lanes // n), x.dtype),
         _struct((n * (n + 1), rows), jnp.float32)],
        [_tokens_on_lanes(block), _tokens_on_sublanes(block)],
    )(alpha, bias, x, _padded_phi(phi))


@functools.partial(jax.jit, static_argnames=('how',))
def _enter_bwd_call(x, phi, alpha, bias, du, dheld, part, how):
    rows, lanes = x.shape
    n, block = how.streams, how.plan.block_rows
    cols, padded = n * (n + 2), _padded_cols(n)
    table = _padded_phi(phi)
    dx, dphit, small = _call(
        _enter_bwd_kernel, 'hc_enter_bwd', how, rows, lanes,
        ('smem', 'smem', 'wide', 'one', 'held', 'wide', 'phi', 'phit'),
        ('wide', 'dphit', 'small'),
        [_struct(x.shape, x.dtype), _struct((padded, lanes), jnp.float32),
         _struct((padded, block), jnp.float32)],
        [_tokens_on_lanes(block), _tokens_on_lanes(block),
         _tokens_on_sublanes(block), _tokens_on_sublanes(block, x.dtype),
         pltpu.VMEM((how.iters, _up(2 * n * (n + 1), _SUBLANES), block),
                    jnp.float32)],
        semantics='arbitrary', in_place=(5, 0),
    )(alpha, bias, x, du, dheld, part, table, table.T)
    lanes_sum = small.sum(axis=1)
    return (dx, dphit[:cols].T.astype(phi.dtype), lanes_sum[cols:cols + 3],
            lanes_sum[:cols])


@functools.partial(jax.jit, static_argnames=('how',))
def _leave_fwd_call(x, y, held, how):
    rows, lanes = x.shape
    block = how.plan.block_rows
    out, = _call(
        _leave_fwd_kernel, 'hc_leave_fwd', how, rows, lanes,
        ('wide', 'one', 'held'), ('wide',), [_struct(x.shape, x.dtype)],
        [_tokens_on_lanes(block), _tokens_on_sublanes(block)])(x, y, held)
    return out


@functools.partial(jax.jit, static_argnames=('how',))
def _leave_bwd_call(x, y, held, g, how):
    rows, lanes = x.shape
    block = how.plan.block_rows
    return _call(
        _leave_bwd_kernel, 'hc_leave_bwd', how, rows, lanes,
        ('wide', 'one', 'wide', 'held'), ('one', 'wide', 'held'),
        [_struct(y.shape, y.dtype), _struct(x.shape, x.dtype),
         _struct(held.shape, jnp.float32)],
        [_tokens_on_lanes(block), _tokens_on_sublanes(block),
         _tokens_on_sublanes(block)], in_place=(2, 1))(x, y, g, held)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _enter(x, phi, alpha, bias, how):
    u, held = _enter_fwd_call(x, phi, alpha, bias, how)
    return u, held, x


def _enter_fwd(x, phi, alpha, bias, how):
    return _enter(x, phi, alpha, bias, how), (x, phi, alpha, bias)


def _enter_bwd(how, kept, cotangents):
    x, phi, alpha, bias = kept
    du, dheld, part = cotangents
    return _enter_bwd_call(x, phi, alpha, bias, du.astype(x.dtype),
                           dheld.astype(jnp.float32), part.astype(x.dtype),
                           how)


_enter.defvjp(_enter_fwd, _enter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _leave(x, y, held, how):
    return _leave_fwd_call(x, y, held, how)


def _leave_fwd(x, y, held, how):
    return _leave(x, y, held, how), (x, y, held)


def _leave_bwd(how, kept, g):
    x, y, held = kept
    dy, dx, dheld = _leave_bwd_call(x, y, held, g.astype(x.dtype), how)
    return dx, dy, dheld


_leave.defvjp(_leave_fwd, _leave_bwd)


def _how(x, streams, iters, clamp, eps, interpret):
    lanes = x.shape[-1]
    rows = x.size // lanes
    found = None if lanes % streams else plan(rows, streams, lanes // streams,
                                              x.dtype, iters)
    if found is None:
        raise ValueError(
            'hyper_connections has no kernels for %d streams of %s %s: ask '
            'supports() first' % (streams, x.shape, x.dtype))
    if interpret is None:
        interpret = _interpret_default()
    return rows, _How(streams, int(iters), tuple(float(c) for c in clamp),
                      float(eps), found, bool(interpret))


def enter(x, phi, alpha, bias, streams, iters, clamp, eps, interpret=None):
    """``(u, held, x)`` for the streams ``x [..., n dim]`` (module
    docstring; ``HyperConnection.coefficients`` and ``read`` through the
    kernels): what the sublayer reads, ``[..., dim]`` in ``x``'s dtype;
    ``H_post | H_res`` as ``[n (n + 1), rows]`` f32, the tokens on the
    lanes; and the streams themselves, for :func:`leave` to read: their
    cotangent from there is added in ``hc_enter_bwd``. ``phi [n dim, n (n
    + 2)]`` is taken in ``x``'s dtype. A shape the kernels do not take
    (:func:`supports`) raises."""
    rows, how = _how(x, streams, iters, clamp, eps, interpret)
    u, held, through = _enter(
        x.reshape(rows, -1), phi.astype(x.dtype), alpha.astype(jnp.float32),
        bias.astype(jnp.float32), how)
    return (u.reshape(x.shape[:-1] + (-1,)), held, through.reshape(x.shape))


def leave(x, y, held, streams, iters, interpret=None):
    """``x' = H_res x + H_post^T y`` for the streams ``x [..., n dim]``
    as :func:`enter` handed them through, the sublayer's output ``y [...,
    dim]`` and :func:`enter`'s ``held``, in ``x``'s dtype and shape
    (``iters``: :func:`enter`'s, for the same plan)."""
    rows, how = _how(x, streams, iters, (0.0, 0.0), 0.0, interpret)
    return _leave(x.reshape(rows, -1), y.astype(x.dtype).reshape(rows, -1),
                  held, how).reshape(x.shape)
