"""Pallas TPU kernels for the chunked scan of a selective state-space
layer: Mamba-2's SSD form, forward and backward under one
``jax.custom_vjp``.

The recurrence, a head at a time (``x_t [P]``, ``B_t``, ``C_t [N]``, a
step size ``dt_t > 0`` and a decay ``a_t = exp(dt_t A)``, ``A < 0``;
heads in ``groups`` share their B and C; the state ``H [P, N]`` is zero
before the sequence)::

    H_t = a_t H_{t-1} + dt_t x_t B_t^T        y_t = H_t C_t

(:func:`ssd_reference` runs exactly that, a position at a time.) In
chunks of ``Q =`` :data:`CHUNK` positions, with ``l_t`` the running sum
of ``log a`` inside a chunk (f32; ``H0`` the state entering the chunk)::

    Y  = ((C B^T) o L) (dt x) + exp(l) (C H0^T)     L_ts = exp(l_t - l_s), s <= t
    H1 = exp(l_Q) H0 + (exp(l_Q - l) dt x)^T B

Every exponent is of a difference that is not positive, so nothing
overflows however strong the decay (a factored ``exp(l_t) exp(-l_s)``
would at ``dt A`` of -1.6 a step).

**The kernels** (named in a trace): ``ssd_fwd`` walks grid ``(batch,
group, chunk)`` with the chunks innermost and the group's states carried
in VMEM (f32, ``[heads a group * P, N]``); a step reads the chunk's ``x``
(the group's heads side by side on the lanes, as the projection wrote
them), ``B``, ``C`` once for the group, and writes ``y`` and the state
that ENTERED the chunk (f32, what the backward reads). ``ssd_bwd`` walks
the chunks in reverse carrying ``dH``, recomputes ``L`` and ``C B^T`` of
the chunk, and writes ``dx``, ``dB``, ``dC`` (summed over the group's
heads in the step), the direct part of ``d dt`` and ``d l``. Products
run on the MXU in the operands' dtype (bf16 in a model) and accumulate
in f32; ``l``, ``L``, the carried state and every sum are f32. Two heads
of 64 lanes are one 128-lane block: a head's part of a product is taken
with the other head's lanes MASKED to zero, never by slicing half a lane
block (``kernels/flash_attention.py`` found the slice form 15-25%
slower), which costs the MXU nothing: a 64-wide operand fills the same
passes as a 128-wide one.

What a head and position have one number of (``dt``, ``l``) comes in two
layouts made by XLA, ``[b, groups, s, heads a group]`` (a column a head:
it scales rows) and ``[b, groups, heads a group, s]`` (a row a head):
``L`` needs ``l`` both ways and the kernel transposes nothing. The sums
``log a -> l`` and ``d l -> d log a`` (a running sum inside a chunk and
its transpose) are XLA's, outside the kernels, as is ``la = dt A``:
``A``'s gradient comes by ordinary autodiff of that product.

:func:`supports` says which shapes the kernels take (heads of 64 lanes
in pairs, states of 128, whole chunks); every other shape, and the
tests' second witness, is :func:`ssd_chunked`, the same chunked form in
``jax.numpy``. On the CPU backend the kernels run in Pallas interpret
mode; every other backend compiles them.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
HEAD_DIM = 64
STATE = 128
_LANES = 128
_VMEM_LIMIT_BYTES = 64 << 20


def _interpret_default():
    return jax.default_backend() == 'cpu'


def supports(seq, heads, groups, head_dim, state):
    """Whether :func:`ssd_scan` runs the kernels for ``x [batch, seq,
    heads * head_dim]`` with ``groups`` groups of ``state`` lanes."""
    return (head_dim == HEAD_DIM and state == STATE and groups >= 1
            and heads % groups == 0 and (heads // groups) % 2 == 0
            and seq % CHUNK == 0)


# ---------------------------------------------------------------------------
# the recurrence itself, and the chunked form in jax.numpy
# ---------------------------------------------------------------------------

def ssd_reference(x, dt, a, b, c, heads, groups):
    """``y`` of the recurrence a position at a time, in f32: ``x [B, S,
    heads * P]``, ``dt [B, S, heads]``, ``a [heads]``, ``b`` and ``c [B,
    S, groups * N]``; head ``h`` uses group ``h // (heads // groups)``."""
    bsz, s, _ = x.shape
    n = b.shape[-1] // groups
    x = x.astype(jnp.float32).reshape(bsz, s, heads, -1)
    rep = heads // groups
    b = jnp.repeat(b.astype(jnp.float32).reshape(bsz, s, groups, n), rep, 2)
    c = jnp.repeat(c.astype(jnp.float32).reshape(bsz, s, groups, n), rep, 2)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32))

    def step(h, args):
        x_t, dt_t, a_t, b_t, c_t = args            # [B, heads, ...]
        h = a_t[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] \
            * b_t[..., None, :]
        return h, jnp.einsum('bhpn,bhn->bhp', h, c_t)

    h0 = jnp.zeros((bsz, heads, x.shape[-1], n), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.swapaxes(t, 0, 1) for t in (x, dt, decay, b, c)))
    return jnp.swapaxes(y, 0, 1).reshape(bsz, s, -1)


def _chunk_sums(la, chunk):
    """``l``: the running sum of ``la [B, S, heads]`` inside each chunk."""
    bsz, s, h = la.shape
    return jnp.cumsum(la.reshape(bsz, s // chunk, chunk, h), axis=2).reshape(
        bsz, s, h)


def ssd_chunked(x, dt, a, b, c, heads, groups, chunk=CHUNK):
    """The chunked form (module docstring) in ``jax.numpy``, products in
    ``x``'s dtype accumulated in f32; any head width and state size, a
    ``seq`` that ``chunk`` divides (or one shorter chunk)."""
    bsz, s, _ = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError('seq %d is not whole chunks of %d' % (s, chunk))
    n, nc, rep = b.shape[-1] // groups, s // chunk, heads // groups
    op = x.dtype
    f32 = jnp.float32
    dt = dt.astype(f32)
    l = _chunk_sums(dt * a.astype(f32), chunk).reshape(bsz, nc, chunk, heads)
    xd = (x.astype(f32).reshape(bsz, nc, chunk, heads, -1)
          * dt.reshape(bsz, nc, chunk, heads, 1))
    b = b.reshape(bsz, nc, chunk, groups, n)
    c = c.reshape(bsz, nc, chunk, groups, n)
    g = jnp.einsum('bctgn,bcsgn->bcgts', c, b, preferred_element_type=f32)
    diff = l[:, :, :, None, :] - l[:, :, None, :, :]          # [b,c,t,s,h]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    big_l = jnp.where(causal, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    m = jnp.repeat(g, rep, axis=2).transpose(0, 1, 3, 4, 2) * big_l
    y = jnp.einsum('bctsh,bcshp->bcthp', m.astype(op), xd.astype(op),
                   preferred_element_type=f32)
    last = l[:, :, -1:, :]                                    # [b,c,1,h]
    xs = (xd * jnp.exp(last - l)[..., None]).astype(op)
    b_h = jnp.repeat(b, rep, axis=3)
    c_h = jnp.repeat(c, rep, axis=3)
    s_c = jnp.einsum('bcshp,bcshn->bchpn', xs, b_h,
                     preferred_element_type=f32)
    decay = jnp.exp(last[:, :, 0, :])                         # [b,c,h]

    def carry(h, args):
        s_i, d_i = args
        return d_i[..., None, None] * h + s_i, h
    h0 = jnp.zeros((bsz, heads, xd.shape[-1], n), f32)
    _, entering = jax.lax.scan(carry, h0, (jnp.swapaxes(s_c, 0, 1),
                                           jnp.swapaxes(decay, 0, 1)))
    entering = jnp.swapaxes(entering, 0, 1)                   # [b,c,h,p,n]
    y = y + jnp.exp(l)[..., None] * jnp.einsum(
        'bcthn,bchpn->bcthp', c_h, entering.astype(op),
        preferred_element_type=f32)
    return y.reshape(bsz, s, -1).astype(op)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


def _chunk_terms(lc_ref, dt_ref, ja, jb):
    """What a pair of heads ``(ja, jb)`` shares in a chunk: the lane
    mask of the first, ``dt``, ``exp(l)`` and ``exp(l_Q - l)`` with a
    head's numbers on its own 64 lanes (``[Q, 128]``), and the two
    ``exp(l_Q)`` (``[1, 1]``)."""
    q = lc_ref.shape[0]
    first = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) < HEAD_DIM

    def halves(of):
        return jnp.where(first, of(ja), of(jb))
    dt = halves(lambda j: dt_ref[:, j:j + 1])
    e = jnp.exp(halves(lambda j: lc_ref[:, j:j + 1]))
    w = jnp.exp(halves(
        lambda j: lc_ref[q - 1:q, j:j + 1] - lc_ref[:, j:j + 1]))
    ends = tuple(jnp.exp(lc_ref[q - 1:q, j:j + 1]) for j in (ja, jb))
    return first, dt, e, w, ends


def _causal(q):
    """``[Q, Q]``: whether position ``s`` (a column) is at or before
    ``t`` (a row)."""
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _decay_matrix(lc_ref, lr_ref, j, causal):
    """``L [Q, Q]`` of head ``j`` in the chunk: ``exp(l_t - l_s)`` for
    ``s <= t``, else 0."""
    diff = lc_ref[:, j:j + 1] - lr_ref[j:j + 1, :]
    return jnp.where(causal, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)


def _rows_of_first():
    return jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0) < HEAD_DIM


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, lc_ref, lr_ref, y_ref, st_ref,
                h_ref, *, hpg):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)
    st_ref[...] = h_ref[...]
    op = x_ref.dtype
    b, c = b_ref[...], c_ref[...]
    g = _dot(c, b, _NT)                                        # [Q, Q] f32
    causal = _causal(x_ref.shape[0])
    for p in range(hpg // 2):
        ja, jb = 2 * p, 2 * p + 1
        lanes = slice(p * _LANES, (p + 1) * _LANES)
        first, dt, e, w, ends = _chunk_terms(lc_ref, dt_ref, ja, jb)
        xd = x_ref[:, lanes].astype(jnp.float32) * dt
        h0 = h_ref[lanes, :]
        y = e * _dot(c, h0.astype(op), _NT)
        for j, mine in ((ja, first), (jb, jnp.logical_not(first))):
            m = g * _decay_matrix(lc_ref, lr_ref, j, causal)
            y = y + _dot(m.astype(op), jnp.where(mine, xd, 0.0).astype(op),
                         _NN)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        decay = jnp.where(_rows_of_first(), ends[0], ends[1])  # [128, 1]
        h_ref[lanes, :] = decay * h0 + _dot((xd * w).astype(op), b, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, lc_ref, lr_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dlc_ref, dlr_ref, dh_ref, *,
                hpg):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
    f32 = jnp.float32
    op = x_ref.dtype
    q = x_ref.shape[0]
    b, c = b_ref[...], c_ref[...]
    g = _dot(c, b, _NT)
    causal = _causal(q)
    d_g = jnp.zeros((q, q), f32)
    d_b = jnp.zeros(b.shape, f32)
    d_c = jnp.zeros(c.shape, f32)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, hpg), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hpg, 1), 0)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    ddt = jnp.zeros((q, hpg), f32)
    dl_cols = jnp.zeros((q, hpg), f32)
    dl_rows = jnp.zeros((hpg, q), f32)

    def total(v):                                              # -> [1, 1]
        return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    for p in range(hpg // 2):
        ja, jb = 2 * p, 2 * p + 1
        lanes = slice(p * _LANES, (p + 1) * _LANES)
        first, dt, e, w, ends = _chunk_terms(lc_ref, dt_ref, ja, jb)
        x = x_ref[:, lanes].astype(f32)
        dy = dy_ref[:, lanes].astype(f32)
        xd = x * dt
        h0 = st_ref[lanes, :]
        dh1 = dh_ref[lanes, :]
        # through the carried state: H1 = exp(l_Q) H0 + (w xd)^T B
        d_b = d_b + _dot((xd * w).astype(op), dh1.astype(op), _NN)
        dxs = _dot(b, dh1.astype(op), _NT)                     # [Q, 128]
        dxd = w * dxs
        dw = dxs * xd * w                   # d w_s x w_s, lane by lane
        # through what the entering state adds: exp(l) (C H0^T)
        z = _dot(c, h0.astype(op), _NT)
        inter = dy * e * z                  # d l_t, lane by lane
        dz = (e * dy).astype(op)
        d_c = d_c + _dot(dz, h0.astype(op), _NN)
        rows = _rows_of_first()
        dh_ref[lanes, :] = _dot(dz, c, _TN) \
            + jnp.where(rows, ends[0], ends[1]) * dh1
        carried = dh1 * h0
        for j, mine, end, its_rows in (
                (ja, first, ends[0], rows),
                (jb, jnp.logical_not(first), ends[1],
                 jnp.logical_not(rows))):
            big_l = _decay_matrix(lc_ref, lr_ref, j, causal)
            m = g * big_l
            dyj = jnp.where(mine, dy, 0.0).astype(op)
            d_m = _dot(dyj, jnp.where(mine, xd, 0.0).astype(op), _NT)
            dxd = dxd + _dot(m.astype(op), dyj, _TN)
            d_g = d_g + d_m * big_l
            through_l = d_m * m
            # d l of this head: as a column (what scales rows t) and, for
            # the columns s of L, as a row
            dw_j = jnp.sum(jnp.where(mine, dw, 0.0), axis=1, keepdims=True)
            col = jnp.sum(through_l, axis=1, keepdims=True) - dw_j \
                + jnp.sum(jnp.where(mine, inter, 0.0), axis=1, keepdims=True) \
                + jnp.where(is_last, total(dw_j) + end * total(
                    jnp.where(its_rows, carried, 0.0)), 0.0)
            dl_cols = jnp.where(head_lane == j, col, dl_cols)
            dl_rows = jnp.where(head_row == j,
                                -jnp.sum(through_l, axis=0, keepdims=True),
                                dl_rows)
            ddt = jnp.where(
                head_lane == j,
                jnp.sum(jnp.where(mine, dxd * x, 0.0), axis=1, keepdims=True),
                ddt)
        dx_ref[:, lanes] = (dt * dxd).astype(dx_ref.dtype)
    d_g = d_g.astype(op)
    db_ref[...] = (d_b + _dot(d_g, c, _TN)).astype(db_ref.dtype)
    dc_ref[...] = (d_c + _dot(d_g, b, _NN)).astype(dc_ref.dtype)
    ddt_ref[...] = ddt
    dlc_ref[...] = dl_cols
    dlr_ref[...] = dl_rows


def _specs(hpg, state, reverse, chunks):
    """Block specs of the operands both kernels share, by name; grid
    ``(batch, group, chunk)``, the chunks walked backwards if
    ``reverse``."""
    def at(i):
        return chunks - 1 - i if reverse else i
    width = hpg * HEAD_DIM
    return {
        'x': pl.BlockSpec((None, CHUNK, width),
                          lambda bi, g, i: (bi, at(i), g)),
        'bc': pl.BlockSpec((None, CHUNK, state),
                           lambda bi, g, i: (bi, at(i), g)),
        'col': pl.BlockSpec((None, None, CHUNK, hpg),
                            lambda bi, g, i: (bi, g, at(i), 0)),
        'row': pl.BlockSpec((None, None, hpg, CHUNK),
                            lambda bi, g, i: (bi, g, 0, at(i))),
        'state': pl.BlockSpec((None, None, width, state),
                              lambda bi, g, i: (bi, at(i), g, 0)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _layouts(per_head, groups):
    """``per_head [B, S, heads]`` as a column a head, ``[B, groups, S,
    heads a group]``, and as a row a head."""
    bsz, s, heads = per_head.shape
    by_group = per_head.reshape(bsz, s, groups, heads // groups)
    return (jnp.transpose(by_group, (0, 2, 1, 3)),
            jnp.transpose(by_group, (0, 2, 3, 1)))


def _plan(x, b, dt, la, groups, reverse):
    """What both calls make of their operands: heads a group, the
    chunks, the block specs, and ``dt`` and ``l`` in their layouts."""
    hpg, chunks = dt.shape[-1] // groups, x.shape[1] // CHUNK
    spec = _specs(hpg, b.shape[-1] // groups, reverse, chunks)
    l_col, l_row = _layouts(_chunk_sums(la, CHUNK), groups)
    return hpg, chunks, spec, (_layouts(dt, groups)[0], l_col, l_row)


def _forward_call(x, b, c, dt, la, groups, interpret):
    bsz, _, width = x.shape
    hpg, chunks, spec, per_head = _plan(x, b, dt, la, groups, False)
    state = b.shape[-1] // groups
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hpg=hpg),
        grid=(bsz, groups, chunks),
        in_specs=[spec['x'], spec['bc'], spec['bc'], spec['col'],
                  spec['col'], spec['row']],
        out_specs=[spec['x'], spec['state']],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, chunks, width, state),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hpg * HEAD_DIM, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name='ssd_fwd',
    )(x, b, c, *per_head)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, b, c, dt, la, groups, interpret):
    return _forward_call(x, b, c, dt, la, groups, interpret)[0]


def _ssd_fwd(x, b, c, dt, la, groups, interpret):
    y, states = _forward_call(x, b, c, dt, la, groups, interpret)
    return y, (x, b, c, dt, la, states)


def _ssd_bwd(groups, interpret, res, dy):
    x, b, c, dt, la, states = res
    bsz, s, _ = x.shape
    heads = dt.shape[-1]
    hpg, chunks, spec, per_head = _plan(x, b, dt, la, groups, True)
    col = jax.ShapeDtypeStruct(per_head[0].shape, jnp.float32)
    dx, db, dc, ddt, dl_col, dl_row = pl.pallas_call(
        functools.partial(_bwd_kernel, hpg=hpg),
        grid=(bsz, groups, chunks),
        in_specs=[spec['x'], spec['bc'], spec['bc'], spec['col'],
                  spec['col'], spec['row'], spec['state'], spec['x']],
        out_specs=[spec['x'], spec['bc'], spec['bc'], spec['col'],
                   spec['col'], spec['row']],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), col, col,
                   jax.ShapeDtypeStruct(per_head[2].shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(states.shape[2:], jnp.float32)],
        compiler_params=_params(), interpret=interpret, name='ssd_bwd',
    )(x, b, c, *per_head, states, dy.astype(x.dtype))

    def per_head(cols):
        return jnp.transpose(cols, (0, 2, 1, 3)).reshape(bsz, s, heads)
    dl = per_head(dl_col) + jnp.transpose(dl_row, (0, 3, 1, 2)).reshape(
        bsz, s, heads)
    # l is the running sum of la inside a chunk: its transpose
    dl = dl.reshape(bsz, chunks, CHUNK, heads)
    d_la = jnp.flip(jnp.cumsum(jnp.flip(dl, 2), axis=2), 2).reshape(
        bsz, s, heads)
    return dx, db, dc, per_head(ddt).astype(dt.dtype), d_la.astype(la.dtype)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, heads, groups, interpret=None):
    """``y [B, S, heads * P]`` of the scan (module docstring), in ``x``'s
    dtype: ``x [B, S, heads * P]``, ``dt [B, S, heads]`` (f32, positive),
    ``a [heads]`` (f32, negative), ``b`` and ``c [B, S, groups * N]`` (in
    ``x``'s dtype). The kernels where :func:`supports` says so, else
    :func:`ssd_chunked`."""
    _, s, width = x.shape
    if not supports(s, heads, groups, width // heads, b.shape[-1] // groups):
        return ssd_chunked(x, dt, a, b, c, heads, groups)
    if interpret is None:
        interpret = _interpret_default()
    dt = dt.astype(jnp.float32)
    return _ssd(x, b.astype(x.dtype), c.astype(x.dtype), dt,
                dt * a.astype(jnp.float32), groups, interpret)
