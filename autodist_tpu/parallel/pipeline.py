"""Pipeline parallelism: GPipe and 1F1B microbatch schedules over the
``pipe`` axis.

Absent from the reference (SURVEY.md §2.3: PP = No). TPU-native design:
the repeated transformer blocks are parameter-stacked along a leading
``stage`` axis which shards over the ``pipe`` mesh axis; inside a manual
shard_map region each pipe rank scans its local layer shard, and
activations hop stage-to-stage with ``ppermute``.

Two schedules:

- :func:`gpipe` — fill/drain schedule, differentiated by autodiff's
  reverse scan. Simple and composes with anything, but the backward
  starts only after every microbatch's forward: all ``M`` microbatches'
  residuals are live at the fwd/bwd boundary (the GPipe memory profile).
- :func:`one_f_one_b` — a REAL 1F1B: a ``jax.custom_vjp`` with a
  hand-written interleaved backward. The head/loss folds into the last
  stage (``tail_fn``) and the embedding into the first (``head_fn``).
  Two variants of the backward (``variant=``, default ``'auto'``):

  * ``'remat'`` — the forward saves NO activations; the backward
    re-runs the forward chain and interleaves one recompute-vjp per
    step. A rank's live working set is a circular stash of at most
    ``2(pp-1)+1`` microbatch activations — bounded by the pipe depth,
    independent of ``M``; no full-batch ``[B, s, d]`` activation,
    logits slab, or input cotangent ever materializes. Cost: a step is
    ~3 forward + 1 backward block passes.
  * ``'stash'`` — the forward stashes each microbatch's stack INPUT
    (one boundary activation per microbatch: a single ``[B, ...]``
    hidden slab per rank, still far below GPipe's per-layer
    residuals), and the backward skips the chain re-forward — one
    vjp-internal recompute only, ~2 forward + 1 backward passes.
  * ``'auto'`` — ``'stash'`` while the stash fits
    ``AUTODIST_PP_STASH_LIMIT_MB`` (default 2048) per rank, else
    ``'remat'``: trade the memory bound for the faster step whenever
    memory allows.

Delivery is collective-clean: microbatch inputs ride a backward-rotating
ppermute relay register (owner ``j % pp`` sits that many backward hops
from stage 0; every rank injects its next owned microbatch each ``pp``
steps) — one mb-sized hop per link per step, replacing the round-3
masked-``psum`` delivery that moved ~pp× the bytes. ``M % pp`` may be
ragged: residency slots are padded and masked.

Fill/drain efficiency: rank r holds a *valid* microbatch only for
schedule steps t in [r, r+M); outside that window block compute is
skipped via ``lax.cond`` (a real XLA conditional — ``rank``/``t`` are
runtime values inside the manual region), so the inherent bubble
(fraction (pp-1)/(M+pp-1)) idles instead of burning FLOPs.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax



def _ceil_div(a, b):
    return -(-a // b)


def _local_stack_fn(block_fn):
    """(params_stack, h) -> (h, summed aux) over this rank's layers."""
    def local_stack(stacked_params, h):
        def body(c, p):
            h, aux = c
            h, a = block_fn(p, h)
            return (h, aux + a.astype(jnp.float32)), None
        (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                               stacked_params)
        return h, aux
    return local_stack


def _own_slices(arr_mb, rank, pp, share, M):
    """Round-robin residency: this rank's owned microbatches (padded to
    ``share`` slots; slots past M alias the last valid one and are
    masked by schedule validity)."""
    idx = jnp.clip(jnp.arange(share) * pp + rank, 0, M - 1)
    return jnp.take(arr_mb, idx, axis=0)


def _inject(own, reg, t, share, pp):
    """Relay injection: at steps t % pp == 0 every rank loads its next
    owned microbatch into the rotating register."""
    slot = jnp.clip(t // pp, 0, share - 1)
    fresh = lax.dynamic_index_in_dim(own, slot, 0, keepdims=False)
    return jnp.where(jnp.equal(jnp.mod(t, pp), 0), fresh, reg)


def _back_rotation(pp):
    """Full backward rotation (toward stage 0): one relay hop/step."""
    return [(i, (i - 1) % pp) for i in range(pp)]


def _scatter_own(own_out, rank, pp, share, mb, B):
    """Per-rank [B, ...] layout of this rank's owned outputs (zeros on
    other ranks' rows): ``psum`` of this across the pipe axis is the
    reassembled batch. Used so the cross-rank collection happens
    OUTSIDE the fused schedule's custom_vjp — the trailing psum's own
    transpose then delivers the full output cotangent to every rank's
    hand-written backward regardless of the boundary's
    replicated-output cotangent convention (a custom_vjp that
    all_gathers internally silently received 1/pp-scaled cotangents
    under shard_map check_vma=False)."""
    buf = jnp.zeros((share, pp) + own_out.shape[1:], own_out.dtype)
    buf = lax.dynamic_update_index_in_dim(
        buf, own_out, rank, 1)
    out = buf.reshape((share * pp * mb,) + own_out.shape[2:])
    return out[:B]


def gpipe(block_fn, stacked_params, x, axis_name, microbatches):
    """Run a stage-sharded layer stack as a GPipe pipeline.

    Must be called inside a shard_map region manual over ``axis_name``.

    Args:
        block_fn: ``block_fn(layer_params, h) -> (h, aux)`` single-block
            apply; ``aux`` is a scalar auxiliary loss contribution (e.g.
            MoE router balance) summed over layers.
        stacked_params: pytree with local leading dim = layers_per_stage.
        x: [batch, ...] full activation batch (replicated over the pipe
            axis — every rank holds it; only rank 0's copy is consumed).
        axis_name: the pipe mesh axis.
        microbatches: M, the microbatch count (batch must divide by M).

    Returns:
        ``(out, aux)``: [batch, ...] final activations and the scalar aux
        loss (mean over microbatches, summed over all stages' layers),
        both replicated over the pipe axis.

    MoE note: under pipelining the router's balance statistics are
    computed per MICROBATCH (each microbatch is a routing group, the
    GShard grouping — same principle as per-seq-shard groups under SP),
    so for microbatches > 1 the aux term is the mean of per-group losses
    rather than one full-batch statistic. The two coincide at
    microbatches=1 (pinned by test_moe_aux_loss_kept_under_pipelining);
    beyond that the objective is the grouped one, by design.
    """
    pp = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B = x.shape[0]
    M = int(microbatches)
    assert B % M == 0, 'batch %d not divisible by microbatches %d' % (B, M)
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])
    stack = _local_stack_fn(block_fn)

    if pp == 1:
        return stack(stacked_params, x)

    fwd_perm = [(i, i + 1) for i in range(pp - 1)]

    def step(carry, t):
        state, buf, aux_acc = carry
        # stage 0 consumes microbatch t (clamped in the drain phase);
        # other stages consume what the previous stage sent
        mb_idx = jnp.clip(t, 0, M - 1)
        first_in = lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
        inp = jnp.where(rank == 0, first_in, state)
        # rank r holds valid work only for t in [r, r+M): skip the block
        # compute in the fill/drain bubble instead of processing garbage
        valid = jnp.logical_and(t >= rank, t < rank + M)
        out, aux = lax.cond(
            valid, lambda h: stack(stacked_params, h),
            lambda h: (h, jnp.zeros((), jnp.float32)), inp)
        aux_acc = aux_acc + aux
        # last stage records microbatch t-(pp-1) once the pipe is full
        out_idx = jnp.clip(t - (pp - 1), 0, M - 1)
        ready = jnp.logical_and(rank == pp - 1, t >= pp - 1)
        prev = lax.dynamic_index_in_dim(buf, out_idx, 0, keepdims=False)
        buf = lax.dynamic_update_index_in_dim(
            buf, jnp.where(ready, out, prev), out_idx, 0)
        nxt = lax.ppermute(out, axis_name, fwd_perm)
        return (nxt, buf, aux_acc), None

    state = jnp.zeros((mb,) + x.shape[1:], x.dtype)
    buf = jnp.zeros((M, mb) + x.shape[1:], x.dtype)
    (_, buf, aux_acc), _ = lax.scan(
        step, (state, buf, jnp.zeros((), jnp.float32)),
        jnp.arange(M + pp - 1))
    out = buf.reshape(B, *x.shape[1:])
    # broadcast the last stage's result to every rank (the head/loss run
    # replicated over pipe): mask + psum
    out = lax.psum(
        jnp.where(rank == pp - 1, out, jnp.zeros_like(out)), axis_name)
    # aux: every stage accumulated its local layers' contribution for the
    # M valid microbatches; sum stages, average microbatches
    aux = lax.psum(aux_acc, axis_name) / M
    return out, aux


def one_f_one_b(block_fn, stacked_params, x, axis_name, microbatches,
                tail_fn=None, extra=None, tail_params=None,
                head_fn=None, head_params=None, variant='auto'):
    """1F1B schedule with per-rank microbatch residency.

    Same fill/steady/drain forward timing as :func:`gpipe` (the forward
    bubble is inherent); the memory contract differs — full-batch
    activations never live across the schedule. It is a custom-vjp with
    a hand-written interleaved backward (see the module docstring for the
    ``variant`` trade: ``'remat'`` bounds each rank's live activations
    at a ``2(pp-1)+1``-slot circular stash, ``'stash'`` saves one
    boundary activation per microbatch and skips the chain re-forward,
    ``'auto'`` picks ``'stash'`` while it fits
    ``AUTODIST_PP_STASH_LIMIT_MB``). Fold the head + loss into
    ``tail_fn(tail_params, h, extra_mb)`` (runs on the last stage per
    microbatch) and the embedding into ``head_fn(head_params, x_mb)``
    (first stage) so the region's inputs/outputs are token-sized, not
    activation-sized. Gradients flow to ``stacked_params`` (local stage
    shard), ``tail_params`` and ``head_params`` (replicated via psum),
    and to a floating ``x``. ``M % pp`` may be ragged.

    Inputs ride a backward-rotating ppermute relay (one mb hop per link
    per step); only the small per-microbatch tail outputs use masked
    psum delivery to their owner rank.
    """
    pp = jax.lax.axis_size(axis_name)
    M = int(microbatches)
    if tail_fn is not None and tail_params is None:
        raise ValueError(
            '1F1B needs the param-explicit tail convention: pass '
            'tail_params with tail_fn(tail_params, h, extra_mb) — a '
            'closure-style tail_fn(h, extra) would silently lose its '
            'parameter gradients in the hand-written backward')
    if pp == 1:
        if head_fn is not None:
            x = head_fn(head_params, x)
        h, aux = _local_stack_fn(block_fn)(stacked_params, x)
        if tail_fn is not None:
            h = tail_fn(tail_params, h, extra)
        return h, aux
    return _fused_1f1b(block_fn, stacked_params, x, axis_name, M,
                       tail_fn, extra, tail_params, head_fn,
                       head_params, variant)


def _fused_1f1b(block_fn, stacked_params, x, axis_name, M, tail_fn,
                extra, tail_params, head_fn, head_params,
                variant='auto'):
    """Custom-vjp 1F1B (see :func:`one_f_one_b`).

    ``variant='remat'``: forward saves NO activations; the backward
    re-runs the forward chain and interleaves one recompute-vjp per
    step, stash bounded at ``2(pp-1)+1`` microbatches per rank.
    ``variant='stash'``: forward saves each microbatch's stack-input
    boundary activation ([M, mb, ...] per rank — one full-batch hidden
    slab); the backward indexes the stash directly (no chain
    re-forward, no relay), paying only the vjp-internal recompute.
    ``'auto'`` resolves to 'stash' while the stash fits
    ``AUTODIST_PP_STASH_LIMIT_MB`` per rank."""
    pp = jax.lax.axis_size(axis_name)
    B = x.shape[0]
    assert B % M == 0, 'batch %d not divisible by microbatches %d' % (B, M)
    mb = B // M
    share = _ceil_div(M, pp)
    stack = _local_stack_fn(block_fn)
    if tail_params is None:
        tail_params = {}
    if head_params is None:
        head_params = {}
    if tail_fn is None:
        tail_fn = lambda tp, h, e: h           # noqa: E731
    have_head = head_fn is not None
    if head_fn is None:
        head_fn = lambda hp, v: v              # noqa: E731
    # extra always present internally (dummy keeps the schedule uniform)
    have_extra = extra is not None
    if not have_extra:
        extra = jnp.zeros((B, 1), jnp.int32)
    elif jnp.issubdtype(jnp.asarray(extra).dtype, jnp.inexact):
        # the hand-written backward does not propagate d(extra) (the
        # tail cotangent for it is discarded); int targets — the lm/
        # classification case — have no cotangent, but a float extra
        # (soft labels, distillation targets) would silently train with
        # d(extra)=0. Refuse rather than train on wrong gradients.
        raise ValueError(
            'fused 1F1B does not backpropagate into a floating-point '
            '`extra` stream; use integer targets')
    x_differentiable = jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)

    if variant not in ('auto', 'remat', 'stash'):
        raise ValueError("unknown 1F1B variant %r; it is one of 'auto', "
                         "'remat', 'stash'" % (variant,))
    if variant == 'auto':
        from autodist_tpu.const import ENV
        probe = jax.eval_shape(
            lambda v: head_fn(head_params, v),
            jax.ShapeDtypeStruct((mb,) + x.shape[1:],
                                 jnp.asarray(x).dtype))
        stash_bytes = M * int(np.prod(probe.shape)) * probe.dtype.itemsize
        limit = ENV.AUTODIST_PP_STASH_LIMIT_MB.val * (1 << 20)
        variant = 'stash' if stash_bytes <= limit else 'remat'

    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    rev_perm = [(i, i - 1) for i in range(1, pp)]
    back_rot = _back_rotation(pp)

    def zero_ct(v):
        """Cotangent for a possibly-integer primal leaf."""
        v = jnp.asarray(v)
        if jnp.issubdtype(v.dtype, jnp.inexact):
            return jnp.zeros_like(v)
        return np.zeros(v.shape, jax.dtypes.float0)

    def run_forward(sp, tp, hp, x_, e_, with_stash=False):
        rank = lax.axis_index(axis_name)
        xs = x_.reshape(M, mb, *x_.shape[1:])
        es = e_.reshape(M, mb, *e_.shape[1:])
        own_x = _own_slices(xs, rank, pp, share, M)
        own_e = _own_slices(es, rank, pp, share, M)
        zero_x = jnp.zeros_like(own_x[0])
        zero_e = jnp.zeros_like(own_e[0])
        h_shape = jax.eval_shape(lambda v: head_fn(hp, v), zero_x)
        zero_h = jnp.zeros(h_shape.shape, h_shape.dtype)
        out_shape = jax.eval_shape(lambda h, e: tail_fn(tp, h, e),
                                   zero_h, zero_e)
        zero_out = jnp.zeros(out_shape.shape, out_shape.dtype)

        def step(carry, t):
            reg_x, reg_e, state_h, state_e, own_out, aux_acc, stash = \
                carry
            reg_x = _inject(own_x, reg_x, t, share, pp)
            reg_e = _inject(own_e, reg_e, t, share, pp)
            # first stage embeds its incoming microbatch (head folded
            # in). head/tail run UNCONDITIONALLY and mask after: a
            # rank-divergent cond around code with sharding constraints
            # deadlocks when the partitioner inserts resharding
            # collectives in one branch only (found by the 8-device
            # dp4xpp2 dryrun); only the bare block stack may sit under
            # the validity cond.
            inp_h = jnp.where(rank == 0, head_fn(hp, reg_x), state_h)
            inp_e = jnp.where(rank == 0, reg_e, state_e)
            valid = jnp.logical_and(t >= rank, t - rank < M)
            if with_stash:
                # stash-variant: keep this microbatch's stack INPUT for
                # the backward (j = t - rank is the microbatch this
                # rank processes at step t)
                j_w = jnp.clip(t - rank, 0, M - 1)
                prev_s = lax.dynamic_index_in_dim(stash, j_w, 0,
                                                  keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(valid, inp_h, prev_s), j_w, 0)
            h, aux = lax.cond(
                valid, lambda v: stack(sp, v),
                lambda v: (v, jnp.zeros((), jnp.float32)), inp_h)
            aux_acc = aux_acc + aux
            j = t - (pp - 1)
            is_out = jnp.logical_and(rank == pp - 1,
                                     jnp.logical_and(j >= 0, j < M))
            out_val = tail_fn(tp, h, inp_e)
            done = lax.psum(jnp.where(is_out, out_val, zero_out),
                            axis_name)
            take = jnp.logical_and(jnp.logical_and(j >= 0, j < M),
                                   jnp.mod(j, pp) == rank)
            slot_out = jnp.clip(j // pp, 0, share - 1)
            prev = lax.dynamic_index_in_dim(own_out, slot_out, 0,
                                            keepdims=False)
            own_out = lax.dynamic_update_index_in_dim(
                own_out, jnp.where(take, done, prev), slot_out, 0)
            nxt_h = lax.ppermute(h, axis_name, fwd_perm)
            nxt_e = lax.ppermute(inp_e, axis_name, fwd_perm)
            reg_x = lax.ppermute(reg_x, axis_name, back_rot)
            reg_e = lax.ppermute(reg_e, axis_name, back_rot)
            return (reg_x, reg_e, nxt_h, nxt_e, own_out, aux_acc,
                    stash), None

        own_out = jnp.zeros((share,) + zero_out.shape, zero_out.dtype)
        stash0 = jnp.zeros((M,) + zero_h.shape, zero_h.dtype) \
            if with_stash else jnp.zeros((1, 1))
        carry0 = (zero_x, zero_e, zero_h, zero_e, own_out,
                  jnp.zeros((), jnp.float32), stash0)
        (_, _, _, _, own_out, aux_acc, stash), _ = lax.scan(
            step, carry0, jnp.arange(M + pp - 1))
        # PER-RANK partials: the cross-rank psum happens OUTSIDE the
        # custom_vjp (see _scatter_own)
        out_part = _scatter_own(own_out, rank, pp, share, mb, B)
        if with_stash:
            return out_part, aux_acc, stash
        return out_part, aux_acc

    def run_backward(sp, tp, hp, x_, e_, ct_out, ct_aux):
        """Interleaved recompute-forward + backward schedule.

        Timing (step u): chain-fwd of microbatch j=u-r at rank r
        (received inputs stashed, circular, 2(pp-1)+1 slots);
        tail-vjp of j=u-(pp-1) at the last rank the step its chain
        output appears; stack-vjp of j=u-2(pp-1)+r at rank r, with the
        activation cotangent hopping one rank backward per step. The
        stash entry written at chain-fwd step j+r is consumed at
        stack-vjp step j+2(pp-1)-r — retention <= 2(pp-1), so the
        circular buffer never overwrites a live slot.
        """
        rank = lax.axis_index(axis_name)
        S = 2 * (pp - 1) + 1
        T = M + 2 * (pp - 1)
        xs = x_.reshape(M, mb, *x_.shape[1:])
        es = e_.reshape(M, mb, *e_.shape[1:])
        own_x = _own_slices(xs, rank, pp, share, M)
        own_e = _own_slices(es, rank, pp, share, M)
        cts = ct_out.reshape(M, mb, *ct_out.shape[1:])
        zero_x = jnp.zeros_like(own_x[0])
        zero_e = jnp.zeros_like(own_e[0])
        h_shape = jax.eval_shape(lambda v: head_fn(hp, v), zero_x)
        zero_h = jnp.zeros(h_shape.shape, h_shape.dtype)
        # the caller-side `psum(aux_part)/M` transpose already applied
        # the 1/M: the incoming ct IS the per-(microbatch, rank) aux
        # cotangent
        ct_aux_mb = ct_aux.astype(jnp.float32)

        def stack_fwd(v):
            return stack(sp, v)[0]

        g_sp0 = jax.tree.map(jnp.zeros_like, sp)
        g_tp0 = jax.tree.map(jnp.zeros_like, tp)
        g_hp0 = jax.tree.map(jnp.zeros_like, hp)
        dx0 = jnp.zeros((M,) + zero_x.shape, zero_x.dtype) \
            if x_differentiable else None

        def step(carry, u):
            (reg_x, reg_e, state_h, state_e, stash_x, stash_h,
             ct_reg, g_sp, g_tp, g_hp, dx_buf) = carry
            # ---- recompute-forward chain (identical to run_forward) --
            reg_x = _inject(own_x, reg_x, u, share, pp)
            reg_e = _inject(own_e, reg_e, u, share, pp)
            inp_h = jnp.where(rank == 0, head_fn(hp, reg_x), state_h)
            inp_e = jnp.where(rank == 0, reg_e, state_e)
            valid_f = jnp.logical_and(u >= rank, u - rank < M)
            h = lax.cond(valid_f, stack_fwd, lambda v: v, inp_h)
            # stash this step's received input (rank 0: the raw/token
            # microbatch; others: the incoming activation). The slot
            # being overwritten was consumed at step u-1 (see docstring)
            slot_w = jnp.mod(u, S)
            if have_head:
                # pre-head inputs stashed only when a head exists (for
                # its re-vjp); without one, stash_h already holds rank
                # 0's raw input — a second activation-sized stash would
                # double the advertised pipe-depth bound
                stash_x = lax.dynamic_update_index_in_dim(
                    stash_x, reg_x, slot_w, 0)
            stash_h = lax.dynamic_update_index_in_dim(
                stash_h, inp_h, slot_w, 0)
            # ---- tail vjp at the last rank, same step as chain out ---
            # run UNCONDITIONALLY with a masked cotangent (J^T*0 = 0 on
            # off ranks/steps): a rank-divergent cond around the tail's
            # sharding constraints deadlocks (see run_forward note)
            j_t = u - (pp - 1)
            valid_t = jnp.logical_and(rank == pp - 1,
                                      jnp.logical_and(j_t >= 0, j_t < M))
            ct_mb = lax.dynamic_index_in_dim(
                cts, jnp.clip(j_t, 0, M - 1), 0, keepdims=False)
            ct_mb = jnp.where(valid_t, ct_mb, jnp.zeros_like(ct_mb))
            _, tail_vjp_fn = jax.vjp(
                lambda tp_, h_, e_in: tail_fn(tp_, h_, e_in),
                tp, h, inp_e)
            d_tp, ct_h_tail = tail_vjp_fn(ct_mb)[:2]
            g_tp = jax.tree.map(jnp.add, g_tp, d_tp)
            # ---- stack vjp (the 1F1B backward of microbatch j_b) -----
            j_b = u - 2 * (pp - 1) + rank
            valid_b = jnp.logical_and(j_b >= 0, j_b < M)
            ct_in = jnp.where(rank == pp - 1, ct_h_tail, ct_reg)
            slot_r = jnp.mod(u - 2 * (pp - 1) + 2 * rank, S)
            h_in_b = lax.dynamic_index_in_dim(stash_h, slot_r, 0,
                                              keepdims=False)

            if have_head:
                # Rank 0's stashed input is pre-head (tokens);
                # recompute the head UNCONDITIONALLY on every rank
                # (uniform program — the head's sharding constraints
                # must not sit in rank-divergent control flow) and
                # select the effective stack input.
                x_in_b = lax.dynamic_index_in_dim(stash_x, slot_r, 0,
                                                  keepdims=False)
                head_out_b, head_vjp_fn = jax.vjp(
                    lambda hp_, xv: head_fn(hp_, xv), hp, x_in_b)
                h_eff = jnp.where(rank == 0, head_out_b, h_in_b)
            else:
                h_eff = h_in_b   # rank 0 stashed the raw input itself

            def stack_vjp(args):
                hv, ct = args
                _, vjp_fn = jax.vjp(
                    lambda sp_, h_: stack(sp_, h_), sp, hv)
                return vjp_fn((ct, ct_aux_mb))

            d_sp, d_h = lax.cond(
                valid_b, stack_vjp,
                lambda args: (g_sp0, jnp.zeros_like(args[0])),
                (h_eff, ct_in))
            # head backward with a rank/validity-masked cotangent
            # (J^T*0 = 0 elsewhere) — uniform across ranks
            ct_head = jnp.where(
                jnp.logical_and(valid_b, rank == 0), d_h,
                jnp.zeros_like(d_h))
            if have_head:
                d_hp, d_x = head_vjp_fn(ct_head)
            else:
                d_hp, d_x = g_hp0, ct_head
            ct_prev = d_h
            if x_differentiable:
                take_dx = jnp.logical_and(valid_b, rank == 0)
                slot_dx = jnp.clip(j_b, 0, M - 1)
                prev_dx = lax.dynamic_index_in_dim(dx_buf, slot_dx, 0,
                                                   keepdims=False)
                dx_buf = lax.dynamic_update_index_in_dim(
                    dx_buf, jnp.where(take_dx, d_x, prev_dx),
                    slot_dx, 0)
            g_sp = jax.tree.map(jnp.add, g_sp, d_sp)
            g_hp = jax.tree.map(jnp.add, g_hp, d_hp)
            # ---- rotations -------------------------------------------
            ct_reg = lax.ppermute(ct_prev, axis_name, rev_perm)
            state_h = lax.ppermute(h, axis_name, fwd_perm)
            state_e = lax.ppermute(inp_e, axis_name, fwd_perm)
            reg_x = lax.ppermute(reg_x, axis_name, back_rot)
            reg_e = lax.ppermute(reg_e, axis_name, back_rot)
            return (reg_x, reg_e, state_h, state_e, stash_x, stash_h,
                    ct_reg, g_sp, g_tp, g_hp, dx_buf), None

        stash_x = jnp.zeros((S,) + zero_x.shape, zero_x.dtype) \
            if have_head else jnp.zeros((1, 1))
        stash_h = jnp.zeros((S,) + zero_h.shape, zero_h.dtype)
        carry0 = (zero_x, zero_e, zero_h, zero_e, stash_x, stash_h,
                  jnp.zeros_like(zero_h), g_sp0, g_tp0, g_hp0, dx0)
        carry, _ = lax.scan(step, carry0, jnp.arange(T))
        (_, _, _, _, _, _, _, g_sp, g_tp, g_hp, dx_buf) = carry
        # Cotangents are returned as PER-RANK PARTIALS — tail/head
        # params and x are replicated primals, and the transpose of
        # replication is a sum: the shard_map boundary psums the
        # per-rank returns itself. (Psumming here too double-counted;
        # the direct no-head test pins the 1x scaling.)
        if x_differentiable:
            dx = jnp.where(rank == 0, dx_buf, jnp.zeros_like(dx_buf))
            dx = dx.reshape(x_.shape).astype(x_.dtype)
        else:
            dx = zero_ct(x_)
        return g_sp, g_tp, g_hp, dx, zero_ct(e_)

    def run_backward_stash(sp, tp, hp, x_, e_, stash, ct_out, ct_aux):
        """Stash-variant backward: no chain re-forward, no relay of
        inputs — every rank indexes its saved stack-input stash and the
        primal streams directly.  Rank r runs microbatch j's stack-vjp
        at step ``u = j + (pp-1-r)``; the input cotangent it produces
        is exactly what rank r-1 needs one step later (one rev-ppermute
        hop per step).  Tail/head/stack vjps run UNCONDITIONALLY with
        masked cotangents (J^T·0 = 0): rank-divergent conds around
        sharding-constrained code deadlock (see run_forward note), so
        the (pp-1)/(M+pp-1) bubble burns compute on zeros instead."""
        rank = lax.axis_index(axis_name)
        xs = x_.reshape(M, mb, *x_.shape[1:])
        es = e_.reshape(M, mb, *e_.shape[1:])
        cts = ct_out.reshape(M, mb, *ct_out.shape[1:])
        ct_aux_mb = ct_aux.astype(jnp.float32)

        g_sp0 = jax.tree.map(jnp.zeros_like, sp)
        g_tp0 = jax.tree.map(jnp.zeros_like, tp)
        g_hp0 = jax.tree.map(jnp.zeros_like, hp)
        zero_x = jnp.zeros((mb,) + x_.shape[1:], x_.dtype)
        dx0 = jnp.zeros((M,) + zero_x.shape, zero_x.dtype) \
            if x_differentiable else None

        def step(carry, u):
            ct_reg, g_sp, g_tp, g_hp, dx_buf = carry
            j = u - (pp - 1 - rank)
            valid = jnp.logical_and(j >= 0, j < M)
            jc = jnp.clip(j, 0, M - 1)
            h_in = lax.dynamic_index_in_dim(stash, jc, 0,
                                            keepdims=False)
            inp_e = lax.dynamic_index_in_dim(es, jc, 0, keepdims=False)
            # ONE stack recompute, inside the vjp (the stash variant's
            # whole point: no second, chain-level recompute)
            (h_out, _), stack_vjp_fn = jax.vjp(
                lambda sp_, h_: stack(sp_, h_), sp, h_in)
            # tail vjp at the last rank, cotangent masked elsewhere
            ct_mb = lax.dynamic_index_in_dim(cts, jc, 0, keepdims=False)
            ct_mb = jnp.where(
                jnp.logical_and(valid, rank == pp - 1), ct_mb,
                jnp.zeros_like(ct_mb))
            _, tail_vjp_fn = jax.vjp(
                lambda tp_, h_, e_in: tail_fn(tp_, h_, e_in),
                tp, h_out, inp_e)
            d_tp, ct_h_tail = tail_vjp_fn(ct_mb)[:2]
            g_tp = jax.tree.map(jnp.add, g_tp, d_tp)
            ct_h = jnp.where(rank == pp - 1, ct_h_tail, ct_reg)
            ct_h = jnp.where(valid, ct_h, jnp.zeros_like(ct_h))
            d_sp, d_h_in = stack_vjp_fn(
                (ct_h, jnp.where(valid, ct_aux_mb, 0.0)))
            g_sp = jax.tree.map(jnp.add, g_sp, d_sp)
            # head vjp at rank 0 (embed recompute from the token primal)
            x_in = lax.dynamic_index_in_dim(xs, jc, 0, keepdims=False)
            _, head_vjp_fn = jax.vjp(
                lambda hp_, xv: head_fn(hp_, xv), hp, x_in)
            ct_head = jnp.where(
                jnp.logical_and(valid, rank == 0), d_h_in,
                jnp.zeros_like(d_h_in))
            d_hp, d_x = head_vjp_fn(ct_head)
            g_hp = jax.tree.map(jnp.add, g_hp, d_hp)
            if x_differentiable:
                take_dx = jnp.logical_and(valid, rank == 0)
                prev_dx = lax.dynamic_index_in_dim(dx_buf, jc, 0,
                                                   keepdims=False)
                dx_buf = lax.dynamic_update_index_in_dim(
                    dx_buf, jnp.where(take_dx, d_x, prev_dx), jc, 0)
            ct_reg = lax.ppermute(d_h_in, axis_name, rev_perm)
            return (ct_reg, g_sp, g_tp, g_hp, dx_buf), None

        h_probe = stash[0]
        carry0 = (jnp.zeros_like(h_probe), g_sp0, g_tp0, g_hp0, dx0)
        carry, _ = lax.scan(step, carry0, jnp.arange(M + pp - 1))
        _, g_sp, g_tp, g_hp, dx_buf = carry
        # PER-RANK PARTIALS, same convention as the remat backward: the
        # shard_map boundary psums replicated primals' cotangents
        if x_differentiable:
            dx = jnp.where(rank == 0, dx_buf, jnp.zeros_like(dx_buf))
            dx = dx.reshape(x_.shape).astype(x_.dtype)
        else:
            dx = zero_ct(x_)
        return g_sp, g_tp, g_hp, dx, zero_ct(e_)

    if variant == 'stash':
        @jax.custom_vjp
        def fused(sp, tp, hp, x_, e_):
            # primal (non-differentiated) path: no stash — eval steps
            # must not pay the [M, mb, ...] hidden slab
            return run_forward(sp, tp, hp, x_, e_)

        def fused_fwd(sp, tp, hp, x_, e_):
            out, aux, stash = run_forward(sp, tp, hp, x_, e_,
                                          with_stash=True)
            return (out, aux), (sp, tp, hp, x_, e_, stash)

        def fused_bwd(res, cts):
            sp, tp, hp, x_, e_, stash = res
            ct_out, ct_aux = cts
            return run_backward_stash(sp, tp, hp, x_, e_, stash,
                                      ct_out, ct_aux)
    else:
        @jax.custom_vjp
        def fused(sp, tp, hp, x_, e_):
            return run_forward(sp, tp, hp, x_, e_)

        def fused_fwd(sp, tp, hp, x_, e_):
            out = run_forward(sp, tp, hp, x_, e_)
            return out, (sp, tp, hp, x_, e_)

        def fused_bwd(res, cts):
            sp, tp, hp, x_, e_ = res
            ct_out, ct_aux = cts
            return run_backward(sp, tp, hp, x_, e_, ct_out, ct_aux)

    fused.defvjp(fused_fwd, fused_bwd)
    out_part, aux_part = fused(stacked_params, tail_params, head_params,
                               x, extra)
    # collection outside the custom_vjp: the psum's transpose hands the
    # backward the FULL output cotangent on every rank
    out = lax.psum(out_part, axis_name)
    aux = lax.psum(aux_part, axis_name) / M
    return out, aux
