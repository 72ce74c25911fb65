"""Gradient compressors wrapping the all-reduce collective.

Parity with reference ``autodist/kernel/synchronization/compressor.py``:
``NoneCompressor`` (:146-166), ``HorovodCompressor`` (fp16 cast, :169-201),
``HorovodCompressorEF`` (error feedback, :120-143 + :204-205). PowerSGD is
commented out in the reference (:208-284); here it is implemented for real
as a low-rank compressor (round-robin power iteration), and
``Int8RingCompressor`` adds a quantized-collective tier the reference
never had (int8 wire with per-block f32 scales, EQuARX-style —
``AUTODIST_QUANT_BLOCK`` elements per scale), since low-precision +
low-rank collectives are where TPU ICI bandwidth wins come from.

A compressor transforms the *local* gradient before the collective and
inverse-transforms after; persistent state (error-feedback residual,
PowerSGD ``q`` matrix) lives in the session's aux-state pytree, threaded
through the jitted step.
"""
import jax
import jax.numpy as jnp

from autodist_tpu.const import AXIS_DATA

_REGISTRY = {}


def register(cls):
    _REGISTRY[cls.__name__] = cls
    return cls


def create(name, var_name):
    """Factory by proto enum name (reference Compressor.create)."""
    if name not in _REGISTRY:
        raise ValueError('Unknown compressor %r (have %s)' %
                         (name, sorted(_REGISTRY)))
    return _REGISTRY[name](var_name)


class Compressor:
    """Base: ``reduce(grad, env, reduce_fn) -> averaged gradient``."""

    def __init__(self, var_name):
        self.var_name = var_name

    def init_state(self, var_value):
        """Aux-state pytree for this compressor ({} if stateless)."""
        return {}

    def reduce(self, grad, env, reduce_fn):
        raise NotImplementedError


@register
class NoneCompressor(Compressor):
    """Straight all-reduce."""

    def reduce(self, grad, env, reduce_fn):
        return reduce_fn(grad)


@register
class HorovodCompressor(Compressor):
    """Cast to bfloat16 for the wire, cast back after.

    The reference casts fp32→fp16 (compressor.py:169-201); bfloat16 is the
    TPU-native low-precision wire format (no loss-scaling needed).
    """

    def reduce(self, grad, env, reduce_fn):
        orig = grad.dtype
        if orig == jnp.float32:
            return reduce_fn(grad.astype(jnp.bfloat16)).astype(orig)
        return reduce_fn(grad)


@register
class HorovodCompressorEF(Compressor):
    """Low-precision all-reduce with error feedback.

    The quantization residual is carried to the next step and added back
    before compression (compressor.py:120-143), making the compression
    unbiased over time.
    """

    def init_state(self, var_value):
        import numpy as np
        if var_value.dtype != np.float32:
            # reduce() falls through to the plain collective for
            # non-f32 grads: a residual would be dead HBM per var (and
            # the simulator's memory estimate would count it)
            return {}
        return {'residual': jnp.zeros(var_value.shape, jnp.float32)}

    def reduce(self, grad, env, reduce_fn):
        key = 'compressor/%s' % self.var_name
        if grad.dtype != jnp.float32:
            return reduce_fn(grad)
        residual = env.aux_state[key]['residual']
        compensated = grad + residual
        compressed = compensated.astype(jnp.bfloat16)
        env.aux_updates[key] = {
            'residual': compensated - compressed.astype(jnp.float32)}
        return reduce_fn(compressed).astype(jnp.float32)


def quant_block_size():
    """Elements per int8 quantization block (``AUTODIST_QUANT_BLOCK``).

    One f32 scale per block: EQuARX-style block quantization bounds an
    outlier's damage to its own block instead of the whole tensor (or,
    on the bucketed sync path, the whole multi-variable bucket)."""
    from autodist_tpu.const import ENV
    return ENV.AUTODIST_QUANT_BLOCK.val


def _quantize_int8_blocks(x, block):
    """Symmetric per-BLOCK int8 quantization of a flat f32 vector.

    Pads to a block multiple and returns ``(q [nb, block] int8,
    scales [nb] f32)``; the pad region quantizes to zeros and is
    sliced off by :func:`_dequantize_int8_blocks`."""
    flat = jnp.ravel(x).astype(jnp.float32)
    nb = -(-flat.size // block)
    flat = jnp.pad(flat, (0, nb * block - flat.size))
    blocks = flat.reshape(nb, block)
    scales = jnp.max(jnp.abs(blocks), axis=1) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(blocks / scales[:, None]),
                 -127, 127).astype(jnp.int8)
    return q, scales


def _dequantize_int8_blocks(q, scales, size):
    """Inverse of :func:`_quantize_int8_blocks` (flat f32, pad removed)."""
    return (q.astype(jnp.float32) *
            scales[:, None]).reshape(-1)[:size]


def block_roundtrip(x, block=None):
    """What a block-quantized int8 wire actually carries for ``x``:
    dequantize(quantize(x)), same shape. The error-feedback residual is
    ``x - block_roundtrip(x)`` — exactly the mass the wire dropped."""
    block = block or quant_block_size()
    q, scales = _quantize_int8_blocks(x, block)
    return _dequantize_int8_blocks(q, scales, jnp.ravel(x).size) \
        .reshape(x.shape)


def int8_ring_all_reduce(x, axis_name, block=None):
    """Bandwidth-optimal int8-wire all-reduce (sum), block-quantized.

    Ring reduce-scatter with per-hop requantization — each hop ships one
    int8 chunk (+ one f32 scale per ``block`` elements) instead of f32
    data, a ~4x wire saving — followed by an int8 all-gather of the
    fully-reduced chunks. Per-hop requantization keeps the growing
    partial sums in range (the EQuARX recipe), and per-BLOCK scales
    bound an outlier's quantization damage to its own block; callers
    carry an error-feedback residual for unbiasedness.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    block = block or quant_block_size()
    shape = x.shape
    flat = jnp.ravel(x).astype(jnp.float32)
    m = -(-flat.size // n)
    flat = jnp.pad(flat, (0, m * n - flat.size))
    chunks = flat.reshape(n, m)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 hops device i owns the full sum of
    # chunk (i+1) % n
    cur = jax.lax.dynamic_index_in_dim(chunks, me, 0, keepdims=False)
    for step in range(n - 1):
        q, scales = _quantize_int8_blocks(cur, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        scales = jax.lax.ppermute(scales, axis_name, perm)
        idx = (me - step - 1) % n
        cur = _dequantize_int8_blocks(q, scales, m) + \
            jax.lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False)

    q, scales = _quantize_int8_blocks(cur, block)
    all_q = jax.lax.all_gather(q, axis_name)        # [n, nb, block] int8
    all_s = jax.lax.all_gather(scales, axis_name)   # [n, nb]
    full = (all_q.astype(jnp.float32) *
            all_s[:, :, None]).reshape(n, -1)[:, :m]
    # device row j holds chunk (j+1)%n -> chunk c sits at row (c-1)%n
    full = full[jnp.asarray([(c - 1) % n for c in range(n)])]
    return full.reshape(-1)[:x.size].reshape(shape)


def int8_grouped_ring_all_reduce(x, axis_name, groups, block=None):
    """Block-quantized int8 ring all-reduce (sum) over INDEPENDENT
    equal-size groups of axis positions.

    Same wire recipe as :func:`int8_ring_all_reduce` (per-hop
    requantization, per-block f32 scales), but the ring cycles run
    within each group concurrently — the union of the per-group cycles
    is one valid ppermute permutation, so all groups reduce in the
    same ``k-1`` hops. This is the inter-node (DCN) phase of the
    hierarchical schedule: ``groups`` then holds one same-chunk-rank
    representative per node.
    """
    k = len(groups[0])
    if k == 1:
        return x
    block = block or quant_block_size()
    shape = x.shape
    flat = jnp.ravel(x).astype(jnp.float32)
    m = -(-flat.size // k)
    flat = jnp.pad(flat, (0, m * k - flat.size))
    chunks = flat.reshape(k, m)
    n_axis = sum(len(g) for g in groups)
    ranks = [0] * n_axis
    for grp in groups:
        for i, pos in enumerate(grp):
            ranks[pos] = i
    me = jnp.asarray(ranks)[jax.lax.axis_index(axis_name)]
    perm = [(grp[i], grp[(i + 1) % k])
            for grp in groups for i in range(k)]

    cur = jax.lax.dynamic_index_in_dim(chunks, me, 0, keepdims=False)
    for step in range(k - 1):
        q, scales = _quantize_int8_blocks(cur, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        scales = jax.lax.ppermute(scales, axis_name, perm)
        idx = (me - step - 1) % k
        cur = _dequantize_int8_blocks(q, scales, m) + \
            jax.lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False)

    q, scales = _quantize_int8_blocks(cur, block)
    all_q = jax.lax.all_gather(q, axis_name,
                               axis_index_groups=groups)
    all_s = jax.lax.all_gather(scales, axis_name,
                               axis_index_groups=groups)
    full = (all_q.astype(jnp.float32) *
            all_s[:, :, None]).reshape(k, -1)[:, :m]
    # group row j holds chunk (j+1)%k -> chunk c sits at row (c-1)%k
    full = full[jnp.asarray([(c - 1) % k for c in range(k)])]
    return full.reshape(-1)[:x.size].reshape(shape)


def int8_hierarchical_all_reduce(x, axis_name, node_groups, block=None):
    """Two-level int8-wire all-reduce (sum): quantize once, requantize
    at the tier boundary.

    The caller has already block-roundtripped the bucket once (the
    "quantize once" of the error-feedback contract); the intra-node
    phases then ride plain f32 grouped collectives on the cheap ICI
    tier, and only the tier BOUNDARY requantizes: each node's partial
    chunk sum rides the int8 ring across nodes (per-hop requant, the
    DCN tier the quantization exists to relieve), and the reduced
    chunks all-gather back within each node at f32.
    """
    k = len(node_groups)
    g = len(node_groups[0])
    if k <= 1 or g <= 1:
        return int8_ring_all_reduce(x, axis_name, block=block)
    shape = x.shape
    flat = jnp.ravel(x).astype(jnp.float32)
    m = -(-flat.size // g) * g
    flat = jnp.pad(flat, (0, m - flat.size))
    cur = jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                               tiled=True,
                               axis_index_groups=node_groups)
    inter = [[grp[r] for grp in node_groups] for r in range(g)]
    cur = int8_grouped_ring_all_reduce(cur, axis_name, inter,
                                       block=block)
    out = jax.lax.all_gather(cur, axis_name, tiled=True,
                             axis_index_groups=node_groups)
    return out[:x.size].reshape(shape)


def int8_bucket_fusable(compressor, dtype, size):
    """THE bucket-fusion predicate for the int8 tier, shared by
    ``plan.sync_gradients`` (runtime emission) and
    ``plan.static_collective_schedule`` (what the simulator prices) so
    the two can never drift. True only for f32 tensors at or above
    ``MIN_SIZE``: smaller tensors have no error-feedback residual
    (``init_state``) and must keep the plain lossless collective —
    riding a quantized bucket uncompensated would put a systematic,
    never-corrected bias on exactly the small, sensitive parameters
    (biases, norm scales)."""
    import numpy as np
    return (type(compressor) is Int8RingCompressor and
            np.dtype(dtype) == np.float32 and
            size >= Int8RingCompressor.MIN_SIZE)


@register
class Int8RingCompressor(Compressor):
    """Int8-wire quantized all-reduce with error feedback.

    The reference's compressor tier stops at fp16 casts; this is the
    quantized-collective extension (SURVEY.md §7 stage 4): gradients ride
    the ring as int8 + per-block f32 scales (``AUTODIST_QUANT_BLOCK``
    elements each — ~4x fewer wire bytes than f32, an outlier only
    poisons its own block), and the quantization error is carried to the
    next step, keeping training unbiased over time. Tensors below
    MIN_SIZE (or non-f32) fall through to the plain collective — no wire
    saving to be had there.

    Same-group f32 variables under this compressor are additionally
    BUCKET-fusable (``plan.sync_gradients``): the packed bucket is
    quantized as one vector with per-block scales and ONE collective,
    with each member's error-feedback residual carried separately in
    aux-state — see :meth:`~autodist_tpu.parallel.plan.ExecutionPlan.
    sync_gradients`.
    """

    MIN_SIZE = 128

    def init_state(self, var_value):
        import numpy as np
        if var_value.dtype != np.float32 or \
                np.prod(var_value.shape, dtype=int) < self.MIN_SIZE:
            return {}
        return {'residual': jnp.zeros(var_value.shape, jnp.float32)}

    def reduce(self, grad, env, reduce_fn):
        if grad.dtype != jnp.float32 or grad.size < self.MIN_SIZE:
            return reduce_fn(grad)
        key = 'compressor/%s' % self.var_name
        residual = env.aux_state[key]['residual']
        compensated = grad + residual
        transmitted = block_roundtrip(compensated)
        env.aux_updates[key] = {'residual': compensated - transmitted}
        n = jax.lax.axis_size(AXIS_DATA)
        return int8_ring_all_reduce(transmitted, AXIS_DATA) / n


@register
class PowerSGDCompressor(Compressor):
    """Rank-``r`` PowerSGD (arXiv:1905.13727) with error feedback.

    The gradient matrix ``M (n×m)`` is approximated as ``P Qᵀ`` where
    ``P = M Q`` is all-reduced (and orthogonalized) and ``Q = Mᵀ P`` is
    all-reduced; only ``P``/``Q`` cross the wire. Falls back to plain
    all-reduce for rank<2 tensors.
    """

    RANK = 2

    def init_state(self, var_value):
        if var_value.ndim < 2:
            return {}
        n = int(var_value.shape[0])
        m = 1
        for d in var_value.shape[1:]:
            m *= int(d)
        # Deterministic init (stable across processes — crc32, not the
        # salted builtin hash); orthogonalized on first use.
        import zlib
        import numpy as np
        rng = np.random.RandomState(
            zlib.crc32(self.var_name.encode()) % (2 ** 31))
        q = rng.standard_normal((m, self.RANK)).astype('float32')
        return {'q': jnp.asarray(q),
                'residual': jnp.zeros((n, m), jnp.float32)}

    @staticmethod
    def _orthogonalize(m):
        q, _ = jnp.linalg.qr(m)
        return q

    def reduce(self, grad, env, reduce_fn):
        if grad.ndim < 2:
            return reduce_fn(grad)
        key = 'compressor/%s' % self.var_name
        state = env.aux_state[key]
        shape = grad.shape
        mat = grad.reshape(shape[0], -1) + state['residual']
        q = state['q']
        p = reduce_fn(mat @ q)
        p = self._orthogonalize(p)
        new_q = reduce_fn(mat.T @ p)
        approx = p @ new_q.T
        env.aux_updates[key] = {'q': new_q, 'residual': mat - approx}
        return approx.reshape(shape)
