"""Multi-head attention with tensor- and sequence-parallel execution.

Heads shard over the ``model`` mesh axis (Megatron column/row split via
the logical ``heads`` axis); the sequence dimension shards over ``seq``
when the step runs in explicit (shard_map) mode, in which case the module
switches to ring attention (parallel/ring_attention.py). The reference
has neither TP nor SP (SURVEY.md §2.3) — these are the TPU-native
extension axes of the strategy space.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from autodist_tpu import telemetry
from autodist_tpu.const import AXIS_DATA, AXIS_SEQUENCE
from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.kernels import qk_norm
from autodist_tpu.models.core import (Dense, Module, RMSNorm, constrain,
                                      group_mean)
from autodist_tpu.parallel.axes import (active_manual_axes, ctx_option,
                                        current_mesh, live_mesh_axis,
                                        manual_axis, shard_map,
                                        unsharded_execution)
from autodist_tpu.parallel.ring_attention import (local_flash_attention,
                                                  ring_attention)
from autodist_tpu.parallel.ulysses import ulysses_attention


def rope_frequencies(theta, head_dim, yarn=None):
    """What a layer kind's rotary positions are made from: a base
    ``theta`` alone, or with ``yarn`` (a mapping with ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``
    and ``attention_factor``) the pair ``(inv_freq, factor)`` of YaRN as
    the public ``transformers`` ``rope_type: yarn`` computes it: the
    base's frequencies, divided by ``factor`` where a frequency turns
    fewer than ``beta_slow`` times in the original length, left as they
    are where it turns more than ``beta_fast`` times, a linear ramp over
    the pair index between, at every length; ``cos`` and ``sin`` are
    multiplied by ``attention_factor``. Either is what
    ``fa.rotary_tables`` and :func:`rotary` take."""
    if yarn is None:
        return theta
    pairs = np.arange(head_dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * pairs / head_dim)
    inter = extra / yarn['factor']
    original = yarn['original_max_position_embeddings']

    def pair_of(turns):
        return head_dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(yarn['beta_fast'])), 0)
    high = min(math.ceil(pair_of(yarn['beta_slow'])), head_dim - 1)
    if low == high:
        high += 0.001    # as transformers: no division by zero
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    return (tuple(float(f) for f in inv_freq.astype(np.float32)),
            float(yarn['attention_factor']))


@jax.named_scope('rotary')
def rotary(x, positions, theta, heads=None):
    """Rotary position embedding over all of the head dim of
    ``x [b, h, s, d]``, or with ``heads`` given of each head of the
    merged ``x [b, s, heads * d]``; rotate-half convention (``x1, x2``
    the two halves: ``x * cos + cat(-x2, x1) * sin`` with ``inv_freq_j =
    theta ** (-2j / d)``, or the frequencies and factor of
    :func:`rope_frequencies`), computed in f32 at positions ``[s]``.

    ``cat(-x2, x1)`` is taken as ``x @ R`` with R the signed permutation
    that moves each half onto the other: on the MXU, exact in any float
    format (one term a column). Written with split, negate and
    concatenate on a head dim of 64 it costs XLA a dozen lane-shuffling
    passes over f32 copies of q and k, a quarter of a ModernBERT step at
    seq 8192 (PERF.md §6, PR 26). On the merged layout R is that
    permutation once a head down the diagonal and ``cos``, ``sin``
    repeat a head: nothing reshapes the lanes into heads."""
    d = x.shape[-1] // (heads or 1)
    half = d // 2
    cos, sin = fa.rotary_angles(positions, theta, d)
    cos = jnp.concatenate([cos] * 2, axis=-1)                 # [s, d]
    sin = jnp.concatenate([sin] * 2, axis=-1)
    at = jnp.arange(d)
    turn = (jnp.where(at[:, None] == at[None, :] - half, 1.0, 0.0)
            - jnp.where(at[:, None] == at[None, :] + half, 1.0, 0.0))
    if heads:
        cos, sin = jnp.tile(cos, (1, heads)), jnp.tile(sin, (1, heads))
        turn = jnp.kron(jnp.eye(heads), turn)
    turned = jnp.einsum('...d,de->...e', x, turn.astype(x.dtype),
                        precision=jax.lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos
            + turned.astype(jnp.float32) * sin).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def head_rms_norm(x, scale, d, eps):
    """``x [..., lanes]`` with each of its first ``len(scale) / d`` heads
    of ``d`` lanes RMS-normalised over its own lanes and multiplied by
    its lanes of ``scale`` (f32), the lanes behind them passed through;
    computed in f32, in ``x``'s dtype and layout. The backward is written
    out over the same runs of lanes, so that neither pass reshapes the
    lanes into heads."""
    return _head_rms_norm(x, scale, d, eps)[0]


def _head_rms_norm(x, scale, d, eps):
    normed = scale.shape[0]
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(group_mean(jnp.square(x32), normed // d, d) + eps)
    lane = jnp.arange(x.shape[-1])
    w = jnp.pad(scale, (0, x.shape[-1] - normed))
    y = jnp.where(lane < normed, x32 * inv * w, x32)
    return y.astype(x.dtype), (x, scale)


def _head_rms_norm_bwd(d, eps, res, dy):
    x, scale = res
    normed = scale.shape[0]
    x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
    inv = jax.lax.rsqrt(group_mean(jnp.square(x32), normed // d, d) + eps)
    unit = x32 * inv
    lane = jnp.arange(x.shape[-1])
    u = dy32 * jnp.pad(scale, (0, x.shape[-1] - normed))
    dx = inv * (u - unit * group_mean(u * unit, normed // d, d))
    dx = jnp.where(lane < normed, dx, dy32)
    rows = tuple(range(x.ndim - 1))
    return dx.astype(x.dtype), jnp.sum(dy32 * unit, axis=rows)[:normed]


head_rms_norm.defvjp(lambda x, scale, d, eps: _head_rms_norm(x, scale, d, eps),
                     _head_rms_norm_bwd)


class MultiHeadAttention(Module):
    """Causal (or full) self-attention; [batch, seq, embed] in/out.

    ``num_kv_heads`` (grouped kv heads): query head ``i`` attends kv
    head ``i // (num_heads // num_kv_heads)``. The projection writes
    ``[b, s, (num_heads + 2 * num_kv_heads) * head_dim]``, q, k and v
    side by side, and the flash kernels read the three runs where they
    lie: no copy of k or v repeated to the query heads exists on that
    path (the XLA and sequence-parallel paths, for short sequences and
    tiny models, repeat them).

    ``rope_theta`` (a base; with ``rope_yarn`` YaRN's frequencies and
    factor, :func:`rope_frequencies`) puts rotary positions on q and k:
    inside the flash kernels where they run (``position_tables``:
    ``cos`` and ``sin`` as two more operands, q and k rotated on the
    tile), by :func:`rotary` on every other path. ``window`` (keys each
    side, or ``(left, right)``) keeps a band of the scores; under
    ``causal`` the band is ``(left, 0)``. It is handed to every
    attention path that takes one: the flash kernels, their
    nested-manual route under dp/tp, and the XLA path. The
    sequence-parallel paths take none and raise.

    ``qk_norm`` (Qwen3's ``q_norm`` / ``k_norm``): an RMSNorm over the
    ``head_dim`` lanes of every q head and every k head, one weight of
    ``head_dim`` for q and one for k that the heads share, between the
    projection and the rotation, scope ``qk_norm``: where attention
    takes the flash kernels and ``kernels/qk_norm.py`` takes the shape
    (heads of whole 128-lane blocks, rows in whole blocks) its kernel
    pair on the projection's output where it lies, :func:`head_rms_norm`
    under XLA on every other path; which, the point event
    ``qk_norm.plan`` of a trace says.

    ``block_diffusion = B`` (with ``causal=False``, no ``window``): ``x``
    is the ``2 L`` rows of a noised copy of a sequence followed by the
    clean one, row ``i`` of either at position ``i`` (:meth:`positions`),
    under the block-diffusion mask in blocks of ``B``
    (``kernels/flash_attention.py``: the kernels ``flash_*_bd``; on the
    XLA path the same mask as a boolean array). The sequence-parallel
    paths raise."""

    def __init__(self, dim, num_heads, head_dim=None, causal=True,
                 dtype=jnp.float32, rope_theta=None, window=None,
                 num_kv_heads=None, rope_yarn=None, qk_norm=False,
                 norm_eps=1e-6, block_diffusion=None):
        self.dim = dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads:
            raise ValueError('%d query heads do not divide over %d kv heads'
                             % (num_heads, self.num_kv_heads))
        self.head_dim = head_dim or dim // num_heads
        self.causal = causal
        self.dtype = dtype
        # what the position tables are made from, hashable: a model makes
        # one pair of tables for the layers that share it
        self.rope = None if rope_theta is None else rope_frequencies(
            rope_theta, self.head_dim, rope_yarn)
        if isinstance(window, int):
            window = (window, window)
        self.block_diffusion = fa.check_block_diffusion(block_diffusion,
                                                        causal, window)
        self.window = fa.check_window(window, causal)
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        # the runs of q, k and v in the projection's output
        self._runs = (h * d, (h + kv) * d)
        # qkv fused: column-parallel over heads; out: row-parallel back.
        self.wqkv = Dense(dim, (h + 2 * kv) * d, 'embed', 'heads',
                          use_bias=False, dtype=dtype)
        self.wo = Dense(h * d, dim, 'heads', 'embed',
                        use_bias=False, dtype=dtype)
        self.q_norm = self.k_norm = None
        if qk_norm:
            self.q_norm, self.k_norm = (
                RMSNorm(d, axis_name=None, eps=norm_eps, dtype=dtype)
                for _ in range(2))

    def param_defs(self):
        d = {'qkv': self.wqkv, 'out': self.wo}
        if self.q_norm is not None:
            d.update(q_norm=self.q_norm, k_norm=self.k_norm)
        return d

    def positions(self, s):
        """The position of each of ``s`` rows in the current trace, the
        one place the rotary tables of the kernels and the XLA path's
        rotation take them from: row ``i`` at ``i`` (offset by the
        shard's start under sequence parallelism), under
        ``block_diffusion`` ``0 .. s / 2 - 1`` twice."""
        pos = jnp.arange(s)
        seq_axis = manual_axis(AXIS_SEQUENCE)
        if seq_axis is not None:
            pos = pos + jax.lax.axis_index(seq_axis) * s
        if self.block_diffusion is not None:
            pos = pos % (s // 2)
        return pos

    @jax.named_scope('qk_norm')
    def _qk_normed(self, params, qkv, local=None):
        """The projection's output with every q head and every k head
        RMS-normalised over its own lanes (v as it is), in the same
        layout: ``kernels/qk_norm.py``'s kernels where attention takes
        the flash kernels (``local``: what :meth:`kernel_shape` said) and
        they take a device's shape, :func:`head_rms_norm` in
        ``jax.numpy`` otherwise; f32 inside either way. Under a mesh the
        kernels run on each device's rows in a manual region, as
        :meth:`_kernel_attention`'s; where heads are sharded, on a
        shard's q heads and its k heads, two runs of their own."""
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        eps = self.q_norm.eps
        scales = tuple(params[name]['scale'].astype(jnp.float32)
                       for name in ('q_norm', 'k_norm'))
        mesh = None if unsharded_execution() else current_mesh()
        heads_axis = live_mesh_axis('heads') if mesh is not None else None
        how = None
        if local is not None:
            rows, here = local[0] * local[2], local[1]
            kv_here = here * kv // h
            # (heads of all, normed heads) of each operand a device holds
            runs = ((here, here), (kv_here, kv_here)) if heads_axis else \
                ((here + 2 * kv_here, here + kv_here),)
            plans = [qk_norm.plan(rows, heads * d, normed, d)
                     for heads, normed in runs]
            how = None if None in plans else plans[0]
        telemetry.get().loop_event(
            'qk_norm.plan', path='pallas' if how else 'xla', heads=h,
            kv_heads=kv, head_dim=d,
            block_rows=how.block_rows if how else None,
            tile_lanes=how.tile if how else None,
            passes_v='copy' if how else None)

        def normed_by(form, h, kv):
            """``form`` on ``(qkv,)``, or on ``(q, k)`` apart, of ``h``
            and ``kv`` heads."""
            def normed(operands, q_scale, k_scale):
                lanes = (jnp.tile(q_scale, h), jnp.tile(k_scale, kv))
                if len(operands) == 1:
                    lanes = (jnp.concatenate(lanes),)
                return tuple(form(x, scale, d, eps)
                             for x, scale in zip(operands, lanes))
            return normed
        if how is None:
            return normed_by(head_rms_norm, h, kv)((qkv,), *scales)[0]
        kernels = normed_by(qk_norm.head_norm, here, kv_here)
        if mesh is None:
            return kernels((qkv,), *scales)[0]
        operands = tuple(jnp.split(qkv, self._runs, axis=-1)) \
            if heads_axis else (qkv,)
        q_k, v = operands[:2], operands[2:]
        data = AXIS_DATA if mesh.shape.get(AXIS_DATA, 1) > 1 else None
        specs = (P(data, None, heads_axis),) * len(q_k)
        q_k = shard_map(kernels, mesh, (specs, P(), P()), specs)(q_k, *scales)
        return jnp.concatenate(q_k + v, axis=-1) if v else q_k[0]

    def apply(self, params, x, tables=None):
        """``tables``: what ``position_tables`` gives for ``x``, where a
        caller made it once for many layers; made here otherwise."""
        b, s, _ = x.shape
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        qkv = self.wqkv.apply(params['qkv'], x)     # [b, s, (h + 2 kv) d]
        local = self.kernel_shape((b, h, s, d))
        if self.q_norm is not None:
            qkv = self._qk_normed(params, qkv, local)
        if local is not None:
            # long device-local sequences: the Pallas flash kernels
            # (the [s, s] score matrix never reaches HBM), in the layout
            # the two projections have
            if tables is None:
                tables = self.position_tables((b, h, s, d))
            return self.wo.apply(
                params['out'], self._kernel_attention(qkv, local[1], tables))
        if kv == h:
            # (three equal runs as one reshape, as before grouped kv
            # heads: GSPMD carries a `heads` sharding of the fused columns
            # through it, where a split at the runs' bounds made XLA:CPU
            # abort under pp x tp)
            qkv = qkv.reshape(b, s, 3, h, d)
            q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3))
                       for i in range(3))                 # [b, h, s, d]
        else:
            q, k, v = (jnp.transpose(t.reshape(b, s, -1, d), (0, 2, 1, 3))
                       for t in jnp.split(qkv, self._runs, axis=-1))

        seq_axis = manual_axis(AXIS_SEQUENCE)
        if self.rope is not None:
            # global positions, as the position table's (transformer.py)
            pos = self.positions(s)
            q = rotary(q, pos, self.rope)
            k = rotary(k, pos, self.rope)
        if kv != h:
            # the paths below know one head count (short sequences, tiny
            # models): each kv head once for every query head of its group
            k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
        window = self.window
        # a band under a causal mask holds the mask: (left, 0)
        causal = self.causal and window is None
        if seq_axis is not None:
            if self.block_diffusion is not None:
                raise ValueError(
                    'block-diffusion attention under sequence parallelism: '
                    'ring_attention and ulysses_attention know the causal '
                    'mask only, and a shard would hold rows of one copy; '
                    'use sp=1')
            if window is not None:
                raise ValueError(
                    'attention window %r under sequence parallelism: '
                    'ring_attention and ulysses_attention take no window '
                    'yet; use sp=1 for a model with window layers'
                    % (window,))
            if ctx_option('sp_mode', 'ring') == 'ulysses':
                o = ulysses_attention(q, k, v, seq_axis, causal=causal)
            else:
                o = ring_attention(q, k, v, seq_axis, causal=causal)
        else:
            mask = None if self.block_diffusion is None else \
                fa.block_diffusion_mask(s, self.block_diffusion)
            o = local_flash_attention(q, k, v, causal=causal, window=window,
                                      mask=mask)
            o = constrain(o, ('batch', 'heads', 'seq', 'kv'))
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, h * d)
        return self.wo.apply(params['out'], o)

    def position_tables(self, shape):
        """The rotary positions' ``(cos, sin)`` as the flash kernels take
        them (``fa.rotary_tables``), for q, k, v of ``[b, h, s, d]``
        ``shape`` in the current trace; None without rotary positions
        or where attention takes another path than the kernels. They
        depend on no parameter and no layer: a model makes them once a
        step for each rotary base and hands them to its layers."""
        local = self.kernel_shape(shape) if self.rope is not None else None
        if local is None:
            return None
        with jax.named_scope('rotary'):
            return fa.rotary_tables(self.positions(shape[2]), self.rope,
                                    local[1], self.head_dim)

    def _kernel_attention(self, qkv, local_heads, tables):
        """``flash_attention_merged`` on the projection's output
        ``qkv [b, s, (h + 2 kv) * d]`` (``local_heads`` of the query
        heads on a device) and the rotary positions' ``tables`` (or None),
        ``[b, s, h * d]`` out, named with ``lse`` for the block's
        checkpoint policy. The kernels read q, k and v where the
        projection wrote them, and rotate q and k on the tile; the three
        are taken apart only where heads are sharded over a mesh axis (a
        shard's heads are a contiguous run of each of the three, not of
        ``qkv``).

        Under a dp/tp GSPMD mesh the call is made on local (batch, head)
        shards, in a nested manual region: GSPMD alone cannot partition
        an opaque pallas_call. The region is manual over EVERY mesh
        axis, size-1 ones included: Mosaic refuses to lower a kernel
        while any axis of the mesh is still automatic (found on the
        first four-chip run: interpret mode on the CPU mesh never goes
        through that check). Axes the spec does not name see replicated
        operands, which is what attention inputs are over
        pipe/seq/expert."""
        mesh = None if unsharded_execution() else current_mesh()
        heads_axis = live_mesh_axis('heads') if mesh is not None else None
        operands = tuple(jnp.split(qkv, self._runs, axis=-1)) \
            if heads_axis else (qkv,)
        kv_heads = local_heads * self.num_kv_heads // self.num_heads

        def attend(operands, tables):
            return fa.flash_attention_merged(
                operands, local_heads, causal=self.causal,
                window=self.window, rotary=tables, kv_heads=kv_heads,
                block_diffusion=self.block_diffusion)

        if mesh is None:
            return attend(operands, tables)
        data = AXIS_DATA if mesh.shape.get(AXIS_DATA, 1) > 1 else None
        spec = P(data, None, heads_axis)
        # every shard sees the whole tables: positions are not sharded
        return shard_map(attend, mesh, ((spec,) * len(operands),
                                        tables and (P(), P())),
                         spec)(operands, tables)

    def kernel_shape(self, shape):
        """The per-device ``[b, h, s, d]`` that the flash kernels run on
        for q, k, v of ``shape`` in the current trace, or None where
        attention takes another path: sequence-parallel (ring, Ulysses:
        they own their kernel calls), or XLA's below the kernels'
        crossover. A block whose attention returns a shape here keeps
        ``fa.saved_bytes`` of it through its checkpoint."""
        if manual_axis(AXIS_SEQUENCE) is not None:
            return None
        if unsharded_execution():
            return shape if self._kernel_preferred(shape) else None
        return self._tp_manual_shape(shape)

    def _kernel_preferred(self, local):
        """``fa.preferred`` for a device's ``[b, h, s, d]`` of q, with
        its share of the kv heads."""
        kv_heads, rest = divmod(local[1] * self.num_kv_heads, self.num_heads)
        return not rest and fa.preferred(local, self.window, kv_heads,
                                         self.block_diffusion)

    # -- nested-manual flash under dp/tp GSPMD -----------------------------
    def _tp_manual_shape(self, shape):
        """Per-shard [b, h, s, d] when the nested-manual flash path
        applies, else None. Conditions: no manual region already active
        (ring/Ulysses and the pipeline own their shard_maps), a mesh
        with a live data and/or heads axis, batch/head dims divisible,
        and the per-shard shape past the kernel crossover. Mesh axes
        OTHER than data/heads (pipe, seq, expert) may be live: attention
        inputs are not sharded over them, so they must not drop
        long-seq attention to the jnp path."""
        if active_manual_axes():
            return None
        mesh = current_mesh()
        if mesh is None:
            return None
        heads_axis = live_mesh_axis('heads')
        dp = mesh.shape.get(AXIS_DATA, 1)
        tp = mesh.shape[heads_axis] if heads_axis else 1
        if dp * tp <= 1 or shape[0] % dp or shape[1] % tp:
            return None
        local = (shape[0] // dp, shape[1] // tp, shape[2], shape[3])
        return local if self._kernel_preferred(local) else None


class LatentAttention(Module):
    """Multi-head latent attention as DeepSeek-V2 and V3 publish it, on
    the training path: [batch, seq, embed] in/out.

    ``q = x W_q`` in ``num_heads`` heads of ``nope_dim + rope_dim``, or
    with ``q_rank`` (V3's ``q_lora_rank``) ``q = RMSNorm(x W_qa) W_qb``
    through a down-projection of that width; ``c = x W_kva`` is the
    rotary key (``rope_dim``: ONE for all heads) and the latent
    (``rank``); ``RMSNorm(latent) W_kvb`` gives every head its ``k_nope
    [nope_dim]`` and ``v [v_dim]``. Head n: ``softmax((q_nope_n
    k_nope_n^T + rot(q_rope_n) rot(k_rope)^T) scale) v_n`` with ``scale =
    (nope_dim + rope_dim) ** -0.5``, then ``W_o [num_heads * v_dim,
    dim]``. ``rope_yarn`` (:func:`rope_frequencies`' mapping) puts YaRN's
    frequencies and factor on the rotary lanes, and its ``score_factor``,
    where it has one, multiplies ``scale`` (the family's ``mscale ** 2``).

    The columns of the three projections are laid out for the flash
    kernels, which read their outputs where they lie
    (``fa.flash_attention_latent``): ``W_q``'s in ``fa.latent_columns``'
    order (for every lane block's heads their nope parts, then their
    rope parts), ``W_kva``'s the rotary key first and the latent after
    it, ``W_kvb``'s every head's k_nope and then every head's v. Rotary
    pairs are the two halves of the rope part (rotate-half); a published
    checkpoint's interleaved pairs and head-major columns are a fixed
    permutation of these (``benchmark/models/kanana2.py:
    to_reference_params``). Below the kernels' crossover (short
    sequences, the CPU tests' tiny shapes) and under a mesh that shards
    the heads the same equations run under XLA, scores ``[b, h, s, s]``
    and all. The sequence-parallel paths take no latent attention and
    raise."""

    def __init__(self, dim, num_heads, rank, nope_dim, rope_dim, v_dim,
                 causal=True, dtype=jnp.float32, rope_theta=10000.0,
                 norm_eps=1e-6, q_rank=None, rope_yarn=None):
        self.dim, self.num_heads = dim, num_heads
        self.dims = fa.Latent(nope_dim, rope_dim, v_dim)
        self.head_dim, self.v_dim = nope_dim + rope_dim, v_dim
        self.causal, self.dtype = causal, dtype
        self.rope = rope_frequencies(rope_theta, rope_dim, rope_yarn)
        self.sm_scale = self.head_dim ** -0.5 * float(
            (rope_yarn or {}).get('score_factor', 1.0))
        h = num_heads
        self.wq = Dense(q_rank or dim, h * self.head_dim,
                        None if q_rank else 'embed', 'heads',
                        use_bias=False, dtype=dtype)
        self.wq_a = self.q_norm = None
        if q_rank:
            self.wq_a = Dense(dim, q_rank, 'embed', None, use_bias=False,
                              dtype=dtype)
            self.q_norm = RMSNorm(q_rank, axis_name=None, eps=norm_eps,
                                  dtype=dtype)
        self.wkv_a = Dense(dim, rope_dim + rank, 'embed', None,
                           use_bias=False, dtype=dtype)
        self.kv_norm = RMSNorm(rank, axis_name=None, eps=norm_eps,
                               dtype=dtype)
        self.wkv_b = Dense(rank, h * (nope_dim + v_dim), None, 'heads',
                           use_bias=False, dtype=dtype)
        self.wo = Dense(h * v_dim, dim, 'heads', 'embed', use_bias=False,
                        dtype=dtype)

    def param_defs(self):
        d = {'q': self.wq, 'kv_a': self.wkv_a, 'kv_norm': self.kv_norm,
             'kv_b': self.wkv_b, 'out': self.wo}
        if self.wq_a is not None:
            d.update(q_a=self.wq_a, q_norm=self.q_norm)
        return d

    def apply(self, params, x, tables=None):
        b, s, _ = x.shape
        if manual_axis(AXIS_SEQUENCE) is not None:
            raise ValueError('latent attention under sequence parallelism: '
                             'ring_attention and ulysses_attention take one '
                             'head width; use sp=1')
        nope, rope, _ = self.dims
        with jax.named_scope('mla_latent'):
            q = x if self.wq_a is None else self.q_norm.apply(
                params['q_norm'], self.wq_a.apply(params['q_a'], x))
            q = self.wq.apply(params['q'], q)
            c = self.wkv_a.apply(params['kv_a'], x)   # [k_rope | latent]
            kv = self.wkv_b.apply(params['kv_b'], self.kv_norm.apply(
                params['kv_norm'], c[..., rope:]))
        shape = (b, self.num_heads, s, self.head_dim)
        if self.kernel_shape(shape) is not None:
            if tables is None:
                tables = self.position_tables(shape)
            o = self._kernel_attention(q, kv, c, tables)
        else:
            o = self._xla_attention(q, kv, c[..., :rope])
        return self.wo.apply(params['out'], o)

    def _kernel_attention(self, q, kv, c, tables):
        """The flash kernels on the projections' outputs; under a
        data-parallel mesh on each device's batch, in a manual region
        (as ``MultiHeadAttention._kernel_attention``)."""
        def attend(q, kv, c, tables):
            return fa.flash_attention_latent(q, kv, c, self.num_heads,
                                             self.dims, tables,
                                             causal=self.causal,
                                             sm_scale=self.sm_scale)
        mesh = None if unsharded_execution() else current_mesh()
        if mesh is None:
            return attend(q, kv, c, tables)
        spec = P(AXIS_DATA, None, None)
        return shard_map(attend, mesh, (spec, spec, spec, (P(), P())),
                         spec)(q, kv, c, tables)

    def _xla_attention(self, q, kv, k_rope):
        """The same equations under XLA, from the kernels' layout."""
        b, s, _ = q.shape
        h, (nope, rope, v_dim) = self.num_heads, self.dims
        per = fa.latent_group(h, self.dims)
        q = q.reshape(b, s, h // per, per * (nope + rope))
        q_nope = q[..., :per * nope].reshape(b, s, h, nope)
        q_rope = q[..., per * nope:].reshape(b, s, h, rope)
        k_nope = kv[..., :h * nope].reshape(b, s, h, nope)
        v = kv[..., h * nope:].reshape(b, s, h, v_dim)
        pos = jnp.arange(s)
        by_head = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
        q_rope = rotary(by_head(q_rope), pos, self.rope)
        k_rope = rotary(k_rope[:, None], pos, self.rope)
        # (the sum of the two contractions as one over nope + rope lanes,
        # the one key repeated to the heads: short sequences, tiny models)
        q = jnp.concatenate([by_head(q_nope), q_rope], -1)
        k = jnp.concatenate(
            [by_head(k_nope), jnp.broadcast_to(k_rope, q_rope.shape)], -1)
        o = local_flash_attention(q, k, by_head(v), causal=self.causal,
                                  sm_scale=self.sm_scale)
        return by_head(o).reshape(b, s, h * v_dim)

    def position_tables(self, shape):
        """The rotary positions' ``(cos, sin)`` as the kernels take them
        for the rope part (``fa.rotary_tables`` at ``rope_dim``), or
        None where attention takes the XLA path."""
        if self.kernel_shape(shape) is None:
            return None
        with jax.named_scope('rotary'):
            return fa.rotary_tables(jnp.arange(shape[2]), self.rope,
                                    self.num_heads, self.dims.rope)

    def kernel_shape(self, shape):
        """The per-device ``[b, h, s, nope + rope]`` the flash kernels
        run on, or None where attention runs under XLA: short
        sequences, or a mesh that shards anything but the batch."""
        if manual_axis(AXIS_SEQUENCE) is not None:
            return None
        if not unsharded_execution():
            mesh = current_mesh()
            dp = mesh.shape.get(AXIS_DATA, 1)
            others = [a for a, n in mesh.shape.items()
                      if n > 1 and a != AXIS_DATA]
            if active_manual_axes() or others or shape[0] % dp:
                return None
            shape = (shape[0] // dp,) + tuple(shape[1:])
        return shape if fa.preferred_latent(shape, self.dims) else None
