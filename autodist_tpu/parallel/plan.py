"""Execution plan: lower a compiled Strategy onto the device mesh.

This is the TPU-native replacement for the reference's graph-transformer
backend (``autodist/kernel/graph_transformer.py:55-92`` and the
synchronizer kernels). Where the reference rewrites a TF graph op-by-op —
replicating subgraphs, splicing collective ops, placing variables — the
rebuild expresses the same per-variable decisions *functionally*:

- **Replication** (replicator.py:73-156) is SPMD: the captured step is
  interpreted once inside ``shard_map`` over the ``data`` mesh axis.
- **AllReduceSynchronizer** (all_reduce_synchronizer.py:102-130) becomes a
  ``jax.lax.pmean`` over ``data``, optionally compressor-wrapped, with
  same-``group`` variables fused into one flat-bucket collective (the
  scoped-allocator equivalent, runner.py:33-46).
- **PSSynchronizer** (ps_synchronizer.py) in synchronous mode is
  numerically an average; its *placement* semantics (variables and
  optimizer slots living on reduction destinations) lower to ZeRO-style
  sharded state over the mesh with gather-on-read / scatter-on-update.
  Partitioned vars shard along the strategy's partition axis.
- Collective "spec" NCCL/RING collapses into XLA's ICI algorithm choice;
  ``RING`` forces an explicit ppermute ring (useful over DCN).
- Collective group/instance keys (reference collective_key.py:43-70, which
  disambiguate concurrent TF collectives) are subsumed: within one XLA
  program channel ids are compiler-assigned, and the cross-process data
  plane namespaces its keys by strategy id + variable name
  (runtime/session.py ``_key``).
"""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.const import (AXIS_DATA, BUCKET_BYTES_PER_CHUNK,
                                DEFAULT_CHUNK_SIZE, ENV)
from autodist_tpu.kernels.partitioner import PartitionerConfig
from autodist_tpu.telemetry import core as _telemetry
from autodist_tpu.parallel import compressor as comp
from autodist_tpu.parallel import schedule_ir as sir
from autodist_tpu.strategy.base import (AllReduceSynchronizer,
                                        PSSynchronizer)
from autodist_tpu.utils import logging


def ring_all_reduce(x, axis_name):
    """Explicit ring all-reduce (sum) via ppermute (reference RING spec).

    Bandwidth-optimal form: ring reduce-scatter (n-1 hops, each moving a
    1/n-size chunk) then a tiled all-gather of the reduced chunks — per
    device the wire is 2·(n-1)/n·|T| ≈ 2·|T|, vs (n-1)·|T| for a naive
    whole-tensor ring. That bound is why a strategy forces ``spec='RING'``
    on DCN-dominated meshes; on ICI, XLA's own algorithm choice usually
    does better, so this only runs when forced. Wire volume is pinned by
    ``tests/test_hlo_collectives.py`` against the compiled HLO.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape
    flat = jnp.ravel(x)
    m = -(-flat.size // n)
    flat = jnp.pad(flat, (0, m * n - flat.size))
    chunks = flat.reshape(n, m)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 hops device i owns the full sum of
    # chunk (i+1) % n
    cur = jax.lax.dynamic_index_in_dim(chunks, me, 0, keepdims=False)
    for step in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        idx = (me - step - 1) % n
        cur = cur + jax.lax.dynamic_index_in_dim(chunks, idx, 0,
                                                 keepdims=False)

    full = jax.lax.all_gather(cur, axis_name)   # [n, m]
    # device row j holds chunk (j+1)%n -> chunk c sits at row (c-1)%n
    full = full[jnp.asarray([(c - 1) % n for c in range(n)])]
    return full.reshape(-1)[:x.size].reshape(shape)


def hierarchical_all_reduce(x, axis_name, node_groups):
    """Two-level all-reduce (sum) over ``node_groups`` of axis
    positions: intra-node reduce-scatter, inter-node all-reduce over
    one chunk-owner per node, intra-node all-gather.

    This is the PCCL-style process-group synthesis for a two-tier
    (ICI within a node, DCN across nodes) topology: the only traffic
    that crosses the node boundary is each node's ``1/g`` chunk of the
    already-reduced bucket, so the DCN wire carries ``2(k-1)/k·B/g``
    bytes instead of the flat ring's ``2(n-1)/n·B`` — the gap
    :func:`~autodist_tpu.simulator.cost_model.hierarchical_time`
    prices. Addition is associative over the regrouping, so the result
    is the same sum the flat ring computes (bit-identical whenever the
    per-element sums are exactly representable). Degenerate group
    shapes (one node, or one device per node) collapse to a plain
    ``psum``.
    """
    k = len(node_groups)
    g = len(node_groups[0]) if node_groups else 0
    if k <= 1 or g <= 1:
        return jax.lax.psum(x, axis_name)
    shape = x.shape
    flat = jnp.ravel(x)
    m = -(-flat.size // g) * g
    flat = jnp.pad(flat, (0, m - flat.size))
    cur = jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                               tiled=True,
                               axis_index_groups=node_groups)
    inter = [[grp[r] for grp in node_groups] for r in range(g)]
    cur = jax.lax.psum(cur, axis_name, axis_index_groups=inter)
    out = jax.lax.all_gather(cur, axis_name, tiled=True,
                             axis_index_groups=node_groups)
    return out[:x.size].reshape(shape)


def hierarchical_psum_scatter(x, axis_name, node_groups, axis=0):
    """Two-level reduce-scatter (sum) along ``axis``: intra-node
    reduce-scatter, then inter-node reduce-scatter of the owned chunk
    over one representative per node — the scatter HALF of
    :func:`hierarchical_all_reduce`, so the only cross-node traffic is
    ``(k-1)/k`` of each node's ``1/g`` chunk. A chunk pre-permutation
    makes the final ownership IDENTICAL to the flat ``psum_scatter``
    (the device at data-axis position ``d`` owns chunk ``d``), so ZeRO
    shard layouts and update-sharding buckets can swap schedules
    without any relayout; the result is a pure re-association of the
    flat sum (bit-identical whenever the per-element sums are exactly
    representable). ``axis`` length must divide by the axis size.
    Degenerate group shapes collapse to the flat collective.
    """
    k = len(node_groups) if node_groups else 0
    g = len(node_groups[0]) if node_groups else 0
    if k <= 1 or g <= 1:
        return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                    tiled=True)
    n = k * g
    moved = jnp.moveaxis(x, axis, 0)
    m = moved.shape[0] // n
    rest = moved.shape[1:]
    # the two scatters deliver block (p, j) of a (g, k, m)-blocked
    # layout to the device at intra position p in node j (= data-axis
    # position j*g+p); pre-permuting (k, g) -> (g, j) block order makes
    # that block the flat layout's chunk j*g+p
    arranged = jnp.moveaxis(moved.reshape((k, g, m) + rest), 1, 0)
    arranged = arranged.reshape((n * m,) + rest)
    cur = jax.lax.psum_scatter(arranged, axis_name, scatter_dimension=0,
                               tiled=True, axis_index_groups=node_groups)
    inter = [[grp[r] for grp in node_groups] for r in range(g)]
    cur = jax.lax.psum_scatter(cur, axis_name, scatter_dimension=0,
                               tiled=True, axis_index_groups=inter)
    return jnp.moveaxis(cur, 0, axis)


def hierarchical_all_gather(x, axis_name, node_groups, axis=0):
    """Two-level all-gather along ``axis``: inter-node all-gather of
    this device's chunk (the DCN phase moves ``(k-1)/k`` of ``1/g`` of
    the payload per device), then intra-node all-gather, then the
    inverse of :func:`hierarchical_psum_scatter`'s chunk permutation —
    the result is IDENTICAL to the flat tiled ``all_gather`` (chunk
    ``d`` comes from data-axis position ``d``). The gather HALF of the
    two-level schedule: ZeRO param re-gathers and the weight-update-
    sharding bucket gather ride it when the shared cost-model decision
    picks the hierarchical schedule.
    """
    k = len(node_groups) if node_groups else 0
    g = len(node_groups[0]) if node_groups else 0
    if k <= 1 or g <= 1:
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)
    moved = jnp.moveaxis(x, axis, 0)
    m = moved.shape[0]
    rest = moved.shape[1:]
    inter = [[grp[r] for grp in node_groups] for r in range(g)]
    cur = jax.lax.all_gather(moved, axis_name, axis=0, tiled=True,
                             axis_index_groups=inter)
    out = jax.lax.all_gather(cur, axis_name, axis=0, tiled=True,
                             axis_index_groups=node_groups)
    # out block (p, j) holds the shard of data-axis position j*g+p;
    # permute back to flat chunk order
    out = jnp.moveaxis(out.reshape((g, k, m) + rest), 1, 0)
    out = out.reshape((k * g * m,) + rest)
    return jnp.moveaxis(out, 0, axis)


def _numel(shape):
    n = 1
    for d in (shape or (1,)):
        n *= int(d)
    return n


def bucket_bytes_cap(chunk_size=0):
    """Per-bucket byte cap for fused gradient collectives.

    ``AUTODIST_BUCKET_BYTES`` overrides directly; otherwise the cap
    derives from the strategy's ``chunk_size`` (tensors per merged
    group) at ``BUCKET_BYTES_PER_CHUNK`` each, so the reference knob
    keeps meaning something at modern model sizes: a group is never
    fused into one model-sized concat, it is packed into byte-capped
    buckets whose collectives can overlap the backward pass.
    """
    cap = ENV.AUTODIST_BUCKET_BYTES.val
    if cap:
        return max(1, cap)
    return (chunk_size or DEFAULT_CHUNK_SIZE) * BUCKET_BYTES_PER_CHUNK


def pack_buckets(items, cap_bytes, max_vars=0):
    """Greedy contiguous packing of ``[(key, nbytes)]`` into buckets.

    Pure and deterministic (the same inputs produce the same buckets on
    every process — divergent bucket layouts across SPMD hosts would
    deadlock the collective). A bucket closes when adding the next item
    would exceed ``cap_bytes`` (an item larger than the cap still gets
    a bucket of its own) or when it already holds ``max_vars`` items
    (0 = unbounded). Returns ``[[key, ...], ...]`` in input order.
    """
    buckets = []
    cur, cur_bytes = [], 0
    for key, nbytes in items:
        if cur and (cur_bytes + nbytes > cap_bytes or
                    (max_vars and len(cur) >= max_vars)):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(key)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def bucket_fusable(plan, dtype, size):
    """THE per-variable admission predicate for fused AR buckets,
    shared verbatim by the traced emitter (``sync_gradients``) and the
    static mirror (``static_collective_schedule``): same-group
    AllReduce vars whose compressor is stateless on the bucket wire
    (none / bf16 cast) or whose int8 error-feedback state admits
    bucket-level residuals (``compressor.int8_bucket_fusable``)."""
    return bool(plan.is_ar and plan.group is not None and
                (type(plan.compressor) in (comp.NoneCompressor,
                                           comp.HorovodCompressor) or
                 comp.int8_bucket_fusable(plan.compressor, dtype,
                                          size)))


def bucket_fusion_key(plan, dtype):
    """THE bucket-fusion identity: variables may share a bucket only
    when every field that changes the emitted collective agrees —
    group, compressor, dtype, spec, and the two per-bucket schedule
    knobs (hierarchical, weight-update sharding). Both emitters key
    their packing off this tuple, so the traced and static bucket
    layouts cannot drift."""
    return (plan.group, type(plan.compressor).__name__,
            str(jnp.dtype(dtype)), plan.spec, plan.hierarchical,
            plan.weight_update_sharding)


def _emit_bucket_tag(entry):
    """Telemetry tag for one emitted sync bucket (trace-time, so this
    fires once per compiled step, not per executed step): schedule
    shape (flat vs two-level), wire dtype, byte count and the
    schedule entry id — the per-bucket emission evidence the cohort
    timeline (and the roofline drift table) pairs with the measured
    step spans. No-op when telemetry is disabled."""
    tel = _telemetry.get()
    if not tel.enabled:
        return
    wire = {'Int8RingCompressor': 'i8',
            'HorovodCompressor': 'bf16',
            'HorovodCompressorEF': 'bf16'}.get(entry['compressor'],
                                               entry['dtype'])
    schedule = 'hier' if entry.get('hier') else 'flat'
    tel.event('bucket_emit', kind=entry['kind'], group=entry['group'],
              schedule=schedule, wire=wire, vars=entry['vars'],
              bytes=entry['bytes'],
              entry_id=entry.get('entry_id', ''))
    tel.count('plan/buckets_emitted')
    tel.count('plan/bucket_%s' % schedule)


def schedule_entry_key(entry):
    """Content key of one collective-schedule entry — THE join key
    between the static schedule (``static_collective_schedule``), the
    traced emission records (``ExecutionPlan.last_bucket_stats``) and
    the roofline observatory's per-entry drift table
    (:mod:`autodist_tpu.telemetry.roofline`). Built only from fields
    both sides carry identically (kind, dtype, compressor, byte count,
    leading member + member count); ``phase`` is deliberately excluded
    — the traced records do not know it, and kind already separates
    the grad/param halves of every pair the schedule emits."""
    members = entry.get('members') or []
    return '%s:%s:%s:%dB:%s+%d' % (
        entry['kind'], entry.get('dtype'),
        entry.get('compressor') or '-', int(entry.get('bytes', 0)),
        members[0] if members else '?', len(members))


def assign_entry_ids(entries, counts=None):
    """Stamp each entry with a stable ``entry_id``: its content key,
    suffixed ``#k`` for the k-th repeat of an identical key (equal-size
    ZeRO chunks of one variable). Deterministic given emission order,
    which both emission paths pin — so an id minted by the traced
    emission round-trips to exactly one static-schedule entry.
    ``counts`` threads the occurrence map across multiple calls within
    ONE trace (the param-gather records land after sync_gradients
    returns). Returns ``entries`` (mutated in place)."""
    counts = {} if counts is None else counts
    for e in entries:
        key = schedule_entry_key(e)
        k = counts.get(key, 0)
        counts[key] = k + 1
        e['entry_id'] = key if k == 0 else '%s#%d' % (key, k)
    return entries


def static_collective_schedule(strategy, graph_item, num_replicas,
                               sparse_lookups_per_replica=4096,
                               nodes=1, params=None,
                               hier_fallback=None):
    """Static mirror of :meth:`ExecutionPlan.sync_gradients`'s emission.

    Computes, WITHOUT tracing a step, the per-step collective schedule a
    strategy lowers to on an ``num_replicas``-way data mesh: the same
    bucket packing (``pack_buckets`` under the chunk_size-derived byte
    cap, reverse production order), the same ZeRO ``psum_scatter``
    chunking, the same per-bucket flat-vs-hierarchical decision
    (``cost_model.choose_hierarchical`` over ``nodes`` node groups and
    ``params``), and the param re-gather each sharded variable pays on
    the next step. This is what the simulator's cost model prices.

    Entries match the ``last_bucket_stats`` schema plus a ``phase``
    field: ``{'kind', 'group', 'compressor', 'dtype', 'spec', 'vars',
    'bytes', 'members', 'phase', 'hier', 'wus'}`` where ``phase`` is
    ``'grad'`` (gradient sync) or ``'param'`` (the post-update param
    re-gather — ZeRO all-gather or the weight-update-sharding bucket
    gather), ``hier`` is the node-group count of a two-level schedule
    (0 = flat; ZeRO scatter/gather halves and update-sharding buckets
    route through the same ``choose_hierarchical`` decision as AR
    buckets) and ``wus`` marks the reduce-scatter + all-gather pair a
    weight-update-sharded bucket lowers to
    (``choose_update_sharding``, the shared decision — padded bytes,
    sharded opt slots). Every entry additionally carries a stable
    ``entry_id`` (:func:`assign_entry_ids` over
    :func:`schedule_entry_key`) that the traced emission records and
    the roofline drift table join on.
    ``bytes``
    are RAW tensor bytes; anything REPORTING traffic must route them
    through ``simulator.cost_model.wire_bytes`` (as the cost model
    and ``profiling.bucket_report`` do) — under a
    compressed wire the raw figure overstates by 2-4x. Sparse
    (embedding) vars
    assume ``sparse_lookups_per_replica`` looked-up rows per step, the
    runtime's data-dependent quantity.

    Every entry is DERIVED from the schedule IR: the same
    ``schedule_ir.bucket_program`` lowering the traced emission
    executes produces the entry via ``schedule_ir.schedule_entry``, so
    predicted==traced is structural rather than test-pinned. When the
    caller's host layout forced the hierarchical fallback
    (``cost_model.num_node_groups_with_reason``), ``hier_fallback``
    carries the reason and rides every flat comm entry, so a priced
    flat win stays distinguishable from a layout degrade.
    """
    import numpy as np

    n = int(num_replicas)
    entries = []
    if n <= 1:
        return entries
    nodes = int(nodes or 1)
    from autodist_tpu.simulator.cost_model import (
        choose_hierarchical, choose_update_sharding,
        optimizer_slot_count)
    if params is None:
        from autodist_tpu.simulator.cost_model import CostModelParams
        params = CostModelParams()
    opt_slots = optimizer_slot_count(graph_item)

    def half_hier(nbytes, dtype, knob, spec):
        """Two-level decision for ONE scatter/gather half — the same
        shared choose_hierarchical call as the AR buckets (half time
        is exactly half of AR time, so the comparison is identical)."""
        if nodes <= 1:
            return 0
        return nodes if choose_hierarchical(
            nbytes, dtype, 'NoneCompressor', n, nodes, params,
            knob=knob, spec=spec) else 0

    node_cfg = {nd.var_name: nd for nd in strategy.node_config}
    sources = list(graph_item.trainable_var_op_to_var.values())
    plans = []
    for var in sources:
        node = node_cfg.get(var.name)
        if node is None:
            from autodist_tpu.strategy.base import StrategyNode
            node = StrategyNode(var_name=var.name,
                                synchronizer=AllReduceSynchronizer())
        plan = VarPlan(var, node)
        # mirror ExecutionPlan.__init__'s state-sharding rule
        if plan.is_ps and len(var.shape) > 0:
            ax = plan.shard_axis
            if var.shape[ax] >= n and plan.num_shards > 1:
                plan.state_sharded = True
                dim = int(var.shape[ax])
                plan.padded_dim = -(-dim // n) * n
                plan.pad = plan.padded_dim - dim
        plans.append(plan)

    def entry(kind, plan, nbytes, members, phase='grad', vars_=1,
              group=None, compressor=None, hier=0):
        prog = sir.bucket_program(kind, nbytes,
                                  str(np.dtype(plan.var.dtype)),
                                  compressor, plan.spec, n, hier=hier)
        e = sir.schedule_entry(prog, group=group, members=list(members),
                               vars_=vars_, phase=phase)
        # the legacy schema keeps the caller's literal compressor field
        # (None for the un-grouped kinds) — the IR meta normalizes to
        # registry names, which would change pinned entry ids
        e['compressor'] = compressor
        return e

    fusable = {}   # (group, compressor, dtype, spec, hier, wus) -> [idx]
    for i, (var, plan) in enumerate(zip(sources, plans)):
        itemsize = np.dtype(var.dtype).itemsize
        size = int(np.prod(var.shape or (1,)))
        nbytes = size * itemsize
        sparse = bool(graph_item.is_sparse(var)) and len(var.shape) == 2
        b = min(sparse_lookups_per_replica, int(var.shape[0])) \
            if sparse else 0
        sparse_bytes = n * b * (int(var.shape[1]) + 1) * itemsize \
            if sparse else None
        cname = type(plan.compressor).__name__
        if plan.state_sharded:
            padded_shape = list(var.shape)
            padded_shape[plan.shard_axis] = plan.padded_dim or \
                var.shape[plan.shard_axis]
            padded = int(np.prod(padded_shape)) * itemsize
            if sparse and plan.shard_axis == 0 and \
                    sparse_bytes < nbytes // n:
                entries.append(entry('sparse_scatter', plan, sparse_bytes,
                                     [var.name]))
            else:
                # mirror _capped_psum_scatter's chunking exactly
                # (incl. its per-chunk two-level decision)
                cap = bucket_bytes_cap(plan.chunk_size)
                ndim = len(var.shape)
                dstr = str(np.dtype(var.dtype))
                if padded <= cap or ndim < 2:
                    entries.append(entry(
                        'psum_scatter', plan, padded, [var.name],
                        hier=half_hier(padded, dstr,
                                       plan.hierarchical, plan.spec)))
                else:
                    split_axis = 0 if plan.shard_axis != 0 else 1
                    dim = int(padded_shape[split_axis])
                    row = padded // dim
                    k = min(dim, -(-padded // cap))
                    for j in range(k):
                        rows = dim * (j + 1) // k - dim * j // k
                        entries.append(entry(
                            'psum_scatter', plan, rows * row,
                            [var.name],
                            hier=half_hier(rows * row, dstr,
                                           plan.hierarchical,
                                           plan.spec)))
            # the updated shard is re-gathered for the next step. A
            # sparse (embedding) table only needs its looked-up rows
            # fresh — the loose-mode row-sparse plane refreshes them
            # point-to-point (BGETROWS), and the SPMD lowering gathers
            # rows, not the table — so the param phase is priced by
            # expected touched rows, not O(vocab x dim): full-size
            # pricing made AutoStrategy reject PS for exactly the
            # variables PS exists for.
            if sparse and plan.shard_axis == 0 and \
                    sparse_bytes < padded:
                entries.append(entry('sparse_all_gather', plan,
                                     sparse_bytes, [var.name],
                                     phase='param'))
            else:
                entries.append(entry(
                    'all_gather', plan, padded, [var.name],
                    phase='param',
                    hier=half_hier(padded, str(np.dtype(var.dtype)),
                                   plan.hierarchical, plan.spec)))
        elif sparse and type(plan.compressor) is comp.NoneCompressor \
                and sparse_bytes < nbytes:
            entries.append(entry('sparse_all_gather', plan, sparse_bytes,
                                 [var.name]))
        elif bucket_fusable(plan, var.dtype, size):
            fusable.setdefault(bucket_fusion_key(plan, var.dtype),
                               []).append(i)
        else:
            entries.append(entry('all_reduce', plan, nbytes, [var.name],
                                 group=plan.group, compressor=cname))
    # pack fusable groups exactly like sync_gradients: byte-capped
    # buckets in reverse production order, emitted tail-first
    pending = []
    for (group, cname, dtype, spec, hknob, wknob), idxs in \
            fusable.items():
        chunk = max(plans[i].chunk_size for i in idxs)
        cap = bucket_bytes_cap(chunk)
        items = [(i, int(np.prod(sources[i].shape or (1,))) *
                  np.dtype(sources[i].dtype).itemsize)
                 for i in reversed(idxs)]
        sizes = dict(items)
        for bucket in pack_buckets(items, cap,
                                   chunk or DEFAULT_CHUNK_SIZE):
            pending.append((bucket, sizes, group, cname, dtype, spec,
                            hknob, wknob))
    pending.sort(key=lambda b: -max(b[0]))
    for bucket, sizes, group, cname, dtype, spec, hknob, wknob in \
            pending:
        nbytes = sum(sizes[i] for i in bucket)
        if choose_update_sharding(nbytes, dtype, cname, n, params,
                                  knob=wknob, opt_slots=opt_slots,
                                  cross_node=nodes > 1, spec=spec):
            # weight-update-sharded bucket: reduce-scatter (grad
            # phase) + bucketed param all-gather (param phase), each
            # member zero-padded to a multiple of n — exactly what
            # _wus_scatter_bucket / gather_updated_params emit. The
            # psum_scatter kind is what makes memory_footprint drop
            # the members' opt-slot (and resident-grad) bytes to 1/n.
            itemsize = np.dtype(dtype).itemsize
            wbytes = sum((-(-(sizes[i] // itemsize) // n)) * n * itemsize
                         for i in bucket)
            hier = 0
            if nodes > 1 and choose_hierarchical(
                    wbytes, dtype, cname, n, nodes, params,
                    knob=hknob, spec=spec):
                hier = nodes
            members = [sources[i].name for i in bucket]
            for kind, phase in (('psum_scatter', 'grad'),
                                ('all_gather', 'param')):
                prog = sir.bucket_program(kind, wbytes, dtype, cname,
                                          spec, n, hier=hier, wus=True)
                entries.append(sir.schedule_entry(
                    prog, group=group, members=list(members),
                    vars_=len(bucket), phase=phase))
            continue
        hier = 0
        if nodes > 1 and choose_hierarchical(
                nbytes, dtype, cname, n, nodes, params,
                knob=hknob, spec=spec):
            hier = nodes
        prog = sir.bucket_program('all_reduce', nbytes, dtype, cname,
                                  spec, n, hier=hier)
        entries.append(sir.schedule_entry(
            prog, group=group,
            members=[sources[i].name for i in bucket],
            vars_=len(bucket), phase='grad'))
    if hier_fallback:
        # satellite of the unequal-host warning: the reason a flat
        # schedule was forced (vs merely priced cheaper) rides every
        # flat comm entry, joinable downstream by entry id
        for e in entries:
            if e['kind'] in ('all_reduce', 'psum_scatter',
                             'all_gather') and not e.get('hier'):
                e['hier_fallback'] = hier_fallback
    return assign_entry_ids(entries)


class ShardedGrad:
    """A reduce-scattered gradient shard (ZeRO-sharded PS variables).

    Produced by :meth:`ExecutionPlan.sync_gradients` for variables whose
    optimizer state is sharded; consumed by ``Optimizer._apply`` (updates
    the local shard only) or gathered to full on direct fetch.

    ``logical_dim`` records the unpadded size of the shard axis for
    uneven partitions (UnevenPartitionedPS): physical shards are padded
    to equal size, and :meth:`gather` slices the padding back off.

    ``hier_groups`` carries the node groups of a two-level param
    re-gather (the gather half of the hierarchical ZeRO schedule) when
    the shared cost-model decision picked it
    (:meth:`ExecutionPlan.gather_hier_groups`); None = flat.
    """

    def __init__(self, value, axis, logical_dim=None, hier_groups=None):
        self.value = value
        self.axis = axis
        self.logical_dim = logical_dim
        self.hier_groups = hier_groups

    def gather(self):
        if self.hier_groups:
            full = hierarchical_all_gather(self.value, AXIS_DATA,
                                           self.hier_groups,
                                           axis=self.axis)
        else:
            full = jax.lax.all_gather(self.value, AXIS_DATA,
                                      axis=self.axis, tiled=True)
        if self.logical_dim is not None and \
                full.shape[self.axis] != self.logical_dim:
            full = jax.lax.slice_in_dim(full, 0, self.logical_dim,
                                        axis=self.axis)
        return full


class UpdateShard:
    """One variable's 1/n flat shard inside a weight-update-sharded
    bucket (cross-replica weight-update sharding, arXiv:2004.13336).

    Produced by :meth:`ExecutionPlan.sync_gradients` carrying the
    MEAN-gradient shard of an update-sharded AR bucket member;
    consumed by ``Optimizer._apply``, which slices the matching param
    shard (:meth:`slice_param`), runs the fused shard-local update
    against shard-resident slots (``Optimizer.shard_update``) and
    hands back an UpdateShard of the UPDATED param via
    :meth:`with_value`; the frontend's ApplyGradients evaluation then
    re-gathers whole buckets at once through
    :meth:`ExecutionPlan.gather_updated_params`.

    The flat layout is row-major over the variable, zero-padded to a
    multiple of n; the device at data-axis position d owns elements
    ``[d*m, (d+1)*m)`` — the same ownership the flat and hierarchical
    reduce-scatters deliver. ``meta`` is the bucket record shared by
    every member (names, shard sizes, hier groups), which is how the
    gather side reassembles the exact scatter buckets.
    """

    is_update_shard = True
    axis_name = AXIS_DATA

    def __init__(self, value, plan, var, meta, index):
        self.value = value
        self.plan = plan
        self.var = var
        self.meta = meta
        self.index = index

    @property
    def shard_size(self):
        return self.meta['shard_sizes'][self.index]

    def slice_param(self, full_value):
        """This replica's flat param shard of the (replicated) full
        value — a local dynamic-slice, no communication."""
        m = self.shard_size
        flat = jnp.ravel(full_value)
        padded = m * self.plan.num_replicas
        if padded > flat.shape[0]:
            flat = jnp.pad(flat, (0, padded - flat.shape[0]))
        start = jax.lax.axis_index(AXIS_DATA) * m
        return jax.lax.dynamic_slice(flat, (start,), (m,))

    def with_value(self, new_value):
        return UpdateShard(new_value, self.plan, self.var, self.meta,
                           self.index)

    def gather(self):
        """Full var-shaped value from the shards (single-member gather
        — used by direct fetches / user arithmetic via ``_degrade``;
        the ApplyGradients fast path gathers whole buckets instead)."""
        if self.meta.get('hier_groups'):
            full = hierarchical_all_gather(self.value, AXIS_DATA,
                                           self.meta['hier_groups'])
        else:
            full = jax.lax.all_gather(self.value, AXIS_DATA, tiled=True)
        return full[:_numel(self.var.shape)].reshape(self.var.shape)


class VarPlan:
    """Resolved per-variable execution decisions."""

    def __init__(self, var, node):
        self.var = var
        self.node = node
        syncs = node.part_config if node.part_config else [node.synchronizer]
        self.sync = syncs[0]
        self.all_syncs = syncs
        self.is_ps = isinstance(self.sync, PSSynchronizer)
        self.is_ar = isinstance(self.sync, AllReduceSynchronizer)
        # shard geometry via the partitioner math module (reference
        # PartitionerConfig, kernel/partitioner.py:38-150)
        self.part_config = PartitionerConfig(node.partitioner)
        self.num_shards = self.part_config.num_shards
        self.partition_axis = self.part_config.axis
        self.sparse_synced = False   # set at trace time by sync_gradients
        self.staleness = getattr(self.sync, 'staleness', 0)
        self.sync_mode = getattr(self.sync, 'sync', True)
        # local-SGD window length H (PSSynchronizer.local_steps);
        # legacy strategies and AR synchronizers carry 1 (every-step)
        self.local_steps = max(
            1, int(getattr(self.sync, 'local_steps', 1) or 1))
        if self.is_ar:
            self.compressor = comp.create(self.sync.compressor, var.name)
            self.group = self.sync.group
            self.spec = self.sync.spec
            self.chunk_size = getattr(self.sync, 'chunk_size', 0)
            self.hierarchical = getattr(self.sync, 'hierarchical',
                                        'auto') or 'auto'
            self.weight_update_sharding = getattr(
                self.sync, 'weight_update_sharding', 'never') or 'never'
            if getattr(var, 'sparse_read', False):
                # row-lazy semantics (LazyAdam/LazyMomentum keep
                # zero-grad rows bit-identical) are defined over whole
                # rows; the flat 1/n shard layout cannot compute the
                # row mask shard-locally, so sparse-read variables keep
                # the replicated update — 'ineligible' is stronger than
                # 'never': the env override does not shard it either
                self.weight_update_sharding = 'ineligible'
        else:
            self.compressor = comp.create('NoneCompressor', var.name)
            self.group = None
            self.spec = 'AUTO'
            self.chunk_size = 0
            # the ZeRO scatter/gather halves route through the same
            # choose_hierarchical decision as the AR buckets; the
            # PSSynchronizer's knob governs it ('auto' default)
            self.hierarchical = getattr(self.sync, 'hierarchical',
                                        'auto') or 'auto'
            self.weight_update_sharding = 'never'
        # Cross-replica weight-update sharding (set by ExecutionPlan
        # from the per-bucket choose_update_sharding decision): the
        # gradient bucket is reduce-scattered, the optimizer updates
        # this replica's 1/n flat shard against shard-resident slots,
        # and the updated params ride a bucketed all-gather. The flat
        # layout is row-major, zero-padded to wus_padded = n * wus_shard.
        self.update_sharded = False
        self.wus_shard = 0       # per-replica flat shard elements
        self.wus_padded = 0      # padded flat size (n * wus_shard)
        self.wus_pad = 0         # zero-pad elements at the flat tail
        # ZeRO-style state sharding applies to partitioned vars; when the
        # partition axis does not divide the mesh data axis (the uneven
        # case, UnevenPartitionedPS) the physical state is zero-padded to
        # the next multiple and the padding sliced off on every read.
        self.state_sharded = False
        self.shard_axis = self.partition_axis if \
            self.partition_axis is not None else 0
        self.pad = 0             # physical padding rows on shard_axis
        self.padded_dim = None   # physical (padded) size of shard_axis


class ExecutionPlan:
    """Binds (strategy, graph_item, mesh) into callable sync/sharding hooks."""

    def __init__(self, strategy, graph_item, mesh, shard_ps_state=True,
                 loose=False, topology=None):
        self.strategy = strategy
        self.graph_item = graph_item
        self.mesh = mesh
        self.num_replicas = mesh.shape[AXIS_DATA]
        # two-level collective context: the data axis's node groups
        # (None = single-node mesh, flat emission — the degenerate
        # case) and the α-β constants the per-bucket flat-vs-
        # hierarchical decision prices with. ``topology`` is the
        # resource spec's validated Topology when the caller has one;
        # without it the analytic defaults apply.
        from autodist_tpu.parallel.mesh import data_axis_node_groups
        self.topology = topology
        self.hier_groups = data_axis_node_groups(
            mesh, forced_nodes=ENV.AUTODIST_HIERARCHY_NODES.val)
        from autodist_tpu.simulator.cost_model import CostModelParams
        self.cost_params = CostModelParams.from_topology(topology) \
            if topology is not None else CostModelParams()
        # loose mode: independent per-process programs + coord-service PS
        # (relaxed-consistency strategies); mesh is process-local.
        self.loose = loose
        # how many jax processes share this mesh (global SPMD mode); the
        # feed/fetch contract is process-local (between-graph semantics)
        self.num_processes = 1 if loose else \
            max(1, len({d.process_index for d in mesh.devices.flat}))
        self.local_replicas = max(1, self.num_replicas //
                                  self.num_processes)
        self.var_plans = {}
        nodes = {n.var_name: n for n in strategy.node_config}
        for name, var in graph_item.trainable_var_op_to_var.items():
            node = nodes.get(name)
            if node is None:
                from autodist_tpu.strategy.base import StrategyNode
                node = StrategyNode(
                    var_name=name, synchronizer=AllReduceSynchronizer())
                logging.debug('Variable %s missing from strategy; '
                              'defaulting to AllReduce', name)
            plan = VarPlan(var, node)
            if shard_ps_state and plan.is_ps and len(var.shape) > 0:
                ax = plan.shard_axis
                n = self.num_replicas
                if var.shape[ax] >= n and plan.num_shards > 1:
                    plan.state_sharded = True
                    dim = int(var.shape[ax])
                    plan.padded_dim = -(-dim // n) * n
                    plan.pad = plan.padded_dim - dim
            self.var_plans[name] = plan
        # Weight-update-sharding marking: the per-BUCKET decision
        # (cost_model.choose_update_sharding over the exact packed
        # buckets) is precomputed here because the optimizer-slot
        # PLACEMENT must be known before any trace — the session
        # places each marked variable's slots as flat 1/n shards.
        # static_collective_schedule runs the SAME packing and the
        # SAME shared decision the traced emission re-derives
        # (_wus_for), so marking, trace and pricing can never drift.
        env_wus = ENV.AUTODIST_WEIGHT_UPDATE_SHARDING.val
        may_shard = env_wus in ('auto', 'always') or (
            env_wus != 'never' and any(
                p.is_ar and p.weight_update_sharding != 'never'
                for p in self.var_plans.values()))
        if may_shard and self.num_replicas > 1:
            nodes_n = len(self.hier_groups) if self.hier_groups else 1
            for e in static_collective_schedule(
                    strategy, graph_item, self.num_replicas,
                    nodes=nodes_n, params=self.cost_params):
                if not (e.get('wus') and e['kind'] == 'psum_scatter'):
                    continue
                for name in e['members']:
                    p = self.var_plans.get(name)
                    if p is None:
                        continue
                    size = _numel(p.var.shape)
                    p.update_sharded = True
                    p.wus_shard = -(-size // self.num_replicas)
                    p.wus_padded = p.wus_shard * self.num_replicas
                    p.wus_pad = p.wus_padded - size
        self.max_staleness = max(
            [p.staleness for p in self.var_plans.values()] + [0])
        self._pure_sparse_cache = {}
        # per-bucket accounting from the most recent sync_gradients
        # trace: [{'kind', 'group', 'compressor', 'dtype', 'spec',
        # 'vars', 'bytes'}] — 'bytes' are RAW tensor bytes;
        # utils/profiling.bucket_report attaches the wire figure via
        # simulator.cost_model.wire_bytes so the bucket layout (and the
        # overlap + compression it enables) is auditable without
        # reading HLO. Each record carries the schedule 'entry_id'
        # (assign_entry_ids over the shared content key), which
        # round-trips to static_collective_schedule — the join the
        # roofline drift table runs on.
        self.last_bucket_stats = []
        self._entry_id_counts = {}
        # loose-mode gate: any sync=True var demands its staleness bound;
        # the program-wide gate enforces the tightest one (per-variable
        # windows collapse to one window since the step is one program).
        sync_stale = [p.staleness for p in self.var_plans.values()
                      if p.sync_mode]
        self.gate_enabled = bool(sync_stale)
        self.gate_staleness = min(sync_stale) if sync_stale else 0
        relaxed = [p for p in self.var_plans.values()
                   if p.staleness > 0 or not p.sync_mode]
        if relaxed and not loose:
            # Within one SPMD program all replicas are lock-step, which
            # trivially satisfies any staleness bound; the relaxed-
            # consistency fast path (multi-process async PS over the
            # coordination service) only engages in multi-process runs
            # with an all-relaxed-PS strategy.
            logging.warning(
                'Strategy requests relaxed consistency (async/stale) for '
                '%d vars; single-program execution is synchronous, which '
                'is a valid (staleness=0) schedule of the requested bound.',
                len(relaxed))
        # local-SGD window length H (docs/design/local-sgd.md): one
        # step is one program, so per-variable windows collapse to one
        # program-wide H — mixed requests take the tightest (min),
        # mirroring the gate's min-staleness collapse above.
        ps_h = [p.local_steps for p in self.var_plans.values()
                if p.is_ps]
        h = min(ps_h) if ps_h else 1
        if ps_h and len(set(ps_h)) > 1:
            logging.warning(
                'Strategy requests mixed local_steps %s across PS vars; '
                'the step is one program, so the tightest window (%d) '
                'applies to all of them.', sorted(set(ps_h)), h)
        env_h = ENV.AUTODIST_LOCAL_STEPS.val
        if env_h > 0:
            h = env_h
        if h > 1 and any(
                p.is_ps and getattr(p.sync, 'shared_optimizer', False)
                for p in self.var_plans.values()):
            logging.warning(
                'local_steps=%d is incompatible with shared_optimizer '
                '(the PS-resident update consumes per-step deltas, not '
                'window-averaged parameter deltas); clamping to 1.', h)
            h = 1
        if h > 1 and not loose:
            # within one SPMD program replicas are lock-step and sync
            # every step by construction — H>1 only means anything on
            # the multi-process loose PS data plane
            logging.warning(
                'local_steps=%d requested but execution is not loose-'
                'mode; single-program execution syncs every step '
                '(H=1 is the only schedule of this program).', h)
            h = 1
        self.local_steps = h

    def plan_for(self, var):
        name = var if isinstance(var, str) else var.name
        return self.var_plans[name]

    def _record_entry(self, entry):
        """Append one traced emission record, stamped with its
        schedule entry id (the occurrence map persists across the
        whole trace — sync_gradients resets it, the param-gather
        records reuse it), and emit its telemetry tag."""
        assign_entry_ids([entry], self._entry_id_counts)
        self.last_bucket_stats.append(entry)
        _emit_bucket_tag(entry)

    # -- gradient synchronization (runs inside shard_map) -----------------
    def _reduce_fn(self, spec, hier_groups=None):
        """Mean-reduce callable for ONE collective, routed through the
        schedule IR: the value's flat/two-level AR program lowers via
        ``schedule_ir.execute`` to the exact legacy emission (pmean,
        the forced ppermute ring, or the two-level composition) — one
        invocation per emitted collective, which the bucketing tests'
        reduce spy counts."""
        n = self.num_replicas
        k = len(hier_groups) if hier_groups else 0

        def fn(g):
            prog = sir.bucket_program(
                'all_reduce', g.size * jnp.dtype(g.dtype).itemsize,
                str(g.dtype), None, spec, n, hier=k,
                node_groups=hier_groups)
            return sir.execute(prog, g, AXIS_DATA)
        return fn

    def _hier_groups_for(self, nbytes, dtype, compressor_name, spec,
                         knob):
        """Node groups for ONE bucket's collective, or None for flat —
        the trace-time side of the SHARED cost-model decision
        (``cost_model.choose_hierarchical``), so the traced emission
        and ``static_collective_schedule`` can never drift."""
        groups = self.hier_groups
        if not groups:
            return None
        from autodist_tpu.simulator.cost_model import choose_hierarchical
        ok = choose_hierarchical(nbytes, dtype, compressor_name,
                                 self.num_replicas, len(groups),
                                 self.cost_params, knob=knob, spec=spec)
        return groups if ok else None

    def _wus_for(self, nbytes, dtype, compressor_name, spec, knob):
        """Replicated-vs-sharded weight-update decision for ONE bucket
        — the trace-time side of the SHARED cost-model decision
        (``cost_model.choose_update_sharding``), the same call the
        init-time slot-placement marking and
        ``static_collective_schedule`` make, so the traced emission,
        the slot layout and the priced schedule can never drift."""
        from autodist_tpu.simulator.cost_model import (
            choose_update_sharding, optimizer_slot_count)
        return choose_update_sharding(
            nbytes, dtype, compressor_name, self.num_replicas,
            self.cost_params, knob=knob,
            opt_slots=optimizer_slot_count(self.graph_item),
            cross_node=bool(self.hier_groups), spec=spec)

    def gather_hier_groups(self, plan):
        """Node groups for a ZeRO-sharded variable's param re-gather
        (``ShardedGrad.gather``), or None for flat — the gather half
        routes through the same shared ``choose_hierarchical``
        decision as its reduce-scatter half (half-vs-half compares
        exactly like AR-vs-AR; ``cost_model.hierarchical_half_time``)."""
        if not plan.state_sharded:
            return None
        import numpy as np
        shape = self.padded_shape(plan.var.name) or plan.var.shape
        nbytes = _numel(shape) * np.dtype(plan.var.dtype).itemsize
        return self._hier_groups_for(nbytes,
                                     str(np.dtype(plan.var.dtype)),
                                     'NoneCompressor', plan.spec,
                                     plan.hierarchical)

    # -- sparse (IndexedSlices-equivalent) gradient sync ------------------
    def _purely_sparse(self, var):
        """True iff every consumer of ``var`` is a recorded lookup: a
        dense use (tied embeddings, weight decay on the table, ...) puts
        gradient mass on rows outside the looked-up set, which the sparse
        wire would silently drop."""
        cached = self._pure_sparse_cache.get(var.name)
        if cached is not None:
            return cached
        from autodist_tpu.frontend import graph as fe
        lookup_ops = set(map(id, var.lookup_ops))
        read = var._read
        pure = True
        for node in self.graph_item.graph.nodes:
            if not isinstance(node, fe.Op) or id(node) in lookup_ops:
                continue
            operands = list(node.inputs) + list(node.kwargs.values())
            if any(x is var or (read is not None and x is read)
                   for x in operands):
                pure = False
                break
        self._pure_sparse_cache[var.name] = pure
        return pure

    def _sparse_ids(self, var, env):
        """Traced, flattened lookup-id vector for a sparse-read var, or
        None when the sparse path does not apply."""
        if not getattr(var, 'sparse_read', False) or \
                not getattr(var, 'lookup_ids', None) or \
                len(var.shape) != 2 or not self._purely_sparse(var):
            return None
        from autodist_tpu.frontend import graph as fe
        try:
            parts = [jnp.ravel(fe.evaluate(n, env)).astype(jnp.int32)
                     for n in var.lookup_ids]
        except KeyError:        # ids node depends on an un-fed placeholder
            return None
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _gather_slices(self, grad, ids):
        """All-gather each replica's (ids, rows) — the wire format of the
        reference's sparse sync (all_reduce_synchronizer.py:132-173
        all_gathers IndexedSlices indices+values)."""
        rows = jnp.take(grad, ids, axis=0)
        all_ids = jax.lax.all_gather(ids, AXIS_DATA)       # (n, B)
        all_rows = jax.lax.all_gather(rows, AXIS_DATA)     # (n, B, dim)
        return all_ids, all_rows

    def _sparse_allreduce(self, grad, ids):
        """Dense-equivalent mean of per-replica sparse grads: per replica,
        scatter-SET dedups repeated ids (rows already carry the summed
        contribution), then summing over replicas adds distinct workers."""
        all_ids, all_rows = self._gather_slices(grad, ids)

        def body(acc, xs):
            ids_r, rows_r = xs
            upd = jnp.zeros_like(grad).at[ids_r].set(rows_r, mode='drop')
            return acc + upd, None

        acc, _ = jax.lax.scan(body, jnp.zeros_like(grad),
                              (all_ids, all_rows))
        return acc / self.num_replicas

    def _pad_grad(self, plan, grad):
        """Zero-pad a gradient on the shard axis for uneven partitions."""
        if not plan.pad:
            return grad
        cfg = [(0, 0)] * grad.ndim
        cfg[plan.shard_axis] = (0, plan.pad)
        return jnp.pad(grad, cfg)

    def _sparse_scatter_to_shard(self, plan, grad, ids):
        """ZeRO variant: each shard owner keeps only its index range
        (reference splits IndexedSlices by index range,
        partitioner.py:660-684); out-of-range rows drop. Uneven
        partitions use the padded per-shard row count — real ids never
        land in the pad range, so padded rows stay zero."""
        n = self.num_replicas
        shard_rows = (grad.shape[0] + plan.pad) // n
        dim = grad.shape[1]
        all_ids, all_rows = self._gather_slices(grad, ids)
        offset = jax.lax.axis_index(AXIS_DATA) * shard_rows

        def body(acc, xs):
            ids_r, rows_r = xs
            local = ids_r - offset
            # negative indices would wrap (numpy semantics); send them
            # out of bounds high so mode='drop' discards them
            local = jnp.where(local >= 0, local, shard_rows)
            upd = jnp.zeros((shard_rows, dim), grad.dtype) \
                .at[local].set(rows_r, mode='drop')
            return acc + upd, None

        acc, _ = jax.lax.scan(
            body, jnp.zeros((shard_rows, dim), grad.dtype),
            (all_ids, all_rows))
        return ShardedGrad(acc / n, 0, logical_dim=grad.shape[0])

    def _capped_psum_scatter(self, plan, grad):
        """ZeRO reduce-scatter under the same byte cap as the AR buckets.

        A whole-tensor ``psum_scatter`` of a huge gradient serializes
        exactly like a mega all-reduce bucket would, so gradients above
        the cap are split along a NON-scatter axis and reduce-scattered
        chunk by chunk: ownership along the scatter axis is unchanged
        (each chunk scatters the same row ranges to the same owners),
        so concatenating the chunk results is elementwise-identical to
        the single collective. 1-D gradients have no other axis to
        split and go out whole (they are small in practice).
        Returns the local shard value (pre-divided mean).
        """
        n = self.num_replicas
        axis = plan.shard_axis
        g = self._pad_grad(plan, grad)
        cap = bucket_bytes_cap(plan.chunk_size)
        nbytes = g.size * jnp.dtype(g.dtype).itemsize

        def scatter(x, nb):
            # each chunk's scatter independently takes the two-level
            # schedule when the shared cost-model decision prices it
            # cheaper (the hierarchical treatment of the ZeRO scatter
            # half; the gather half decides in gather_hier_groups)
            groups = self._hier_groups_for(int(nb), str(x.dtype),
                                           'NoneCompressor', plan.spec,
                                           plan.hierarchical)
            prog = sir.bucket_program(
                'psum_scatter', int(nb), str(x.dtype), None, plan.spec,
                n, hier=len(groups) if groups else 0,
                node_groups=groups)
            self._record_entry(sir.schedule_entry(
                prog, members=[plan.var.name]))
            return sir.execute(prog, x, AXIS_DATA, axis=axis)

        if nbytes <= cap or g.ndim < 2:
            return scatter(g, nbytes)
        split_axis = 0 if axis != 0 else 1
        dim = g.shape[split_axis]
        k = min(dim, -(-int(nbytes) // cap))
        bounds = [dim * i // k for i in range(1, k)]
        parts = jnp.split(g, bounds, axis=split_axis)
        return jnp.concatenate(
            [scatter(p, p.size * jnp.dtype(p.dtype).itemsize)
             for p in parts], axis=split_axis)

    def sync_gradients(self, sources, grads, env):
        """Average gradients across the data axis per each var's strategy.

        Same-group AllReduce vars with a stateless compressor are packed
        into byte-capped buckets (``pack_buckets``; cap from the
        strategy's ``chunk_size`` / ``AUTODIST_BUCKET_BYTES``) and one
        collective is issued per bucket, in REVERSE gradient-production
        order: the backward pass produces the LAST layer's gradients
        first, so the tail bucket's collective launches while earlier
        layers' backward compute is still in flight (with the XLA
        latency-hiding scheduler, runtime/session.py) instead of one
        model-sized concat serializing behind the whole backward and
        doubling peak gradient memory. Stateful compressors (EF /
        PowerSGD) and PS vars are reduced individually; sparse-read
        (embedding) vars ship (indices, rows) instead of the dense
        vocab-sized gradient whenever that moves fewer bytes; ZeRO
        reduce-scatters are chunked under the same cap.
        """
        self.last_bucket_stats = []
        self._entry_id_counts = {}
        if self.num_replicas == 1:
            return grads
        n = self.num_replicas
        out = list(grads)
        fusable = {}   # (group, compressor cls, dtype, spec) -> [idx]
        for i, (var, grad) in enumerate(zip(sources, grads)):
            plan = self.plan_for(var)
            ids = self._sparse_ids(plan.var, env)
            sparse_bytes = None if ids is None else \
                n * ids.size * (grad.shape[1] + 1)
            if plan.state_sharded:
                if ids is not None and plan.shard_axis == 0 and \
                        sparse_bytes < grad.size // n:
                    out[i] = self._sparse_scatter_to_shard(plan, grad, ids)
                    plan.sparse_synced = True
                    continue
                # ZeRO path: reduce-scatter straight to the shard owner;
                # uneven partitions pad to the next multiple of the mesh.
                out[i] = ShardedGrad(
                    self._capped_psum_scatter(plan, grad),
                    plan.shard_axis,
                    logical_dim=grad.shape[plan.shard_axis],
                    hier_groups=self.gather_hier_groups(plan))
            elif (ids is not None and
                    type(plan.compressor) is comp.NoneCompressor and
                    sparse_bytes < grad.size):
                out[i] = self._sparse_allreduce(grad, ids)
                plan.sparse_synced = True
            elif bucket_fusable(plan, grad.dtype, grad.size):
                fusable.setdefault(bucket_fusion_key(plan, grad.dtype),
                                   []).append(i)
            else:
                out[i] = plan.compressor.reduce(
                    grad, env, self._reduce_fn(plan.spec))
        # Pack every fusable group into byte-capped buckets, then emit
        # ALL buckets (across groups) ordered by reverse production:
        # the bucket holding the highest variable indices first. Each
        # bucket independently picks flat vs two-level: on a multi-node
        # mesh the shared cost-model decision can send a large
        # DCN-bound bucket down the hierarchical schedule while small
        # buckets keep the flat ring.
        pending = []   # (bucket idxs, group, cname, dtype, spec,
        #                 hknob, wknob)
        for (group, cname, dtype, spec, hknob, wknob), idxs in \
                fusable.items():
            chunk = max(self.plan_for(sources[i]).chunk_size
                        for i in idxs)
            cap = bucket_bytes_cap(chunk)
            items = [(i, int(grads[i].size *
                             jnp.dtype(grads[i].dtype).itemsize))
                     for i in reversed(idxs)]
            for bucket in pack_buckets(items, cap,
                                       chunk or DEFAULT_CHUNK_SIZE):
                pending.append((bucket, group, cname, dtype, spec,
                                hknob, wknob))
        pending.sort(key=lambda b: -max(b[0]))
        for bucket, group, cname, dtype, spec, hknob, wknob in pending:
            nbytes = sum(int(grads[i].size *
                             jnp.dtype(grads[i].dtype).itemsize)
                         for i in bucket)
            if self._wus_for(nbytes, dtype, cname, spec, wknob):
                # cross-replica weight-update sharding: the bucket is
                # reduce-SCATTERED instead of all-reduced — each
                # replica receives its contiguous 1/n of every member,
                # updates it shard-locally (Optimizer.shard_update
                # against shard-resident slots) and the updated params
                # ride one bucketed all-gather (gather_updated_params)
                for i, sh in self._wus_scatter_bucket(
                        bucket, sources, grads, group, cname, dtype,
                        spec, hknob):
                    out[i] = sh
                continue
            groups = self._hier_groups_for(nbytes, dtype, cname, spec,
                                           hknob)
            prog = sir.bucket_program(
                'all_reduce', nbytes, dtype, cname, spec,
                self.num_replicas, hier=len(groups) if groups else 0,
                node_groups=groups)
            self._record_entry(sir.schedule_entry(
                prog, group=group,
                members=[sources[i].name for i in bucket],
                vars_=len(bucket)))
            if len(bucket) == 1 and groups is None:
                i = bucket[0]
                plan = self.plan_for(sources[i])
                out[i] = plan.compressor.reduce(
                    grads[i], env, self._reduce_fn(spec))
                continue
            flats = [grads[i].reshape(-1) for i in bucket]
            sizes = [f.shape[0] for f in flats]
            if cname == 'Int8RingCompressor':
                buf = self._int8_bucket_reduce(bucket, sources, flats,
                                               env, hier_groups=groups,
                                               program=prog)
            else:
                reduce_fn = self._reduce_fn(spec, hier_groups=groups) \
                    if groups else self._reduce_fn(spec)
                buf = jnp.concatenate(flats)
                if cname == 'HorovodCompressor' and \
                        buf.dtype == jnp.float32:
                    buf = reduce_fn(
                        buf.astype(jnp.bfloat16)).astype(jnp.float32)
                else:
                    buf = reduce_fn(buf)
            offset = 0
            for i, size in zip(bucket, sizes):
                out[i] = buf[offset:offset + size].reshape(
                    grads[i].shape)
                offset += size
        return out

    def _int8_bucket_reduce(self, bucket, sources, flats, env,
                            hier_groups=None, program=None):
        """Quantized-collective reduction of ONE packed bucket.

        The whole bucket is quantized as a single vector with per-block
        scales (``AUTODIST_QUANT_BLOCK`` elements per f32 scale — an
        outlier gradient poisons only its own block, not every member of
        the bucket) and rides one block-quantized int8 ring all-reduce
        with per-hop requantization. Error feedback stays PER MEMBER:
        each variable's residual from aux-state is added to its slice
        before quantization, and the slice of what the wire dropped is
        written back as that member's next-step residual. The fusion
        predicate (``compressor.int8_bucket_fusable``) only admits
        members with a residual (f32, >= ``MIN_SIZE``) — the
        missing-residual branch below is a safety net for callers with
        uninitialized aux-state (bench harnesses), not a sanctioned
        uncompensated mode. Returns the reduced (mean) flat bucket
        buffer, ready to slice back into member shapes.
        """
        aux = getattr(env, 'aux_state', None) or {}
        comp_flats, res_keys = [], []
        for i, flat in zip(bucket, flats):
            key = 'compressor/%s' % sources[i].name
            res = (aux.get(key) or {}).get('residual')
            if res is not None:
                flat = flat + res.reshape(-1)
                res_keys.append(key)
            else:
                res_keys.append(None)
            comp_flats.append(flat)
        buf = jnp.concatenate(comp_flats)
        transmitted = comp.block_roundtrip(buf)
        offset = 0
        for i, key, flat in zip(bucket, res_keys, comp_flats):
            size = flat.shape[0]
            if key is not None:
                env.aux_updates[key] = {'residual': (
                    flat - transmitted[offset:offset + size]
                ).reshape(self.plan_for(sources[i]).var.shape)}
            offset += size
        n = self.num_replicas
        if program is None:
            program = sir.bucket_program(
                'all_reduce',
                int(buf.size * jnp.dtype(buf.dtype).itemsize),
                str(buf.dtype), 'Int8RingCompressor', 'AUTO', n,
                hier=len(hier_groups) if hier_groups else 0,
                node_groups=hier_groups)
        # quantize once (the roundtrip above), requantize at the tier
        # boundary: the IR lowering dispatches the int8 ring (flat) or
        # the f32-ICI / int8-DCN two-level composition
        return sir.execute(program, transmitted, AXIS_DATA)

    def _wus_scatter_bucket(self, bucket, sources, grads, group, cname,
                            dtype, spec, hknob):
        """Scatter half of ONE weight-update-sharded bucket.

        Pads each member's flat gradient to a multiple of n, interleaves
        the members' per-replica rows so a SINGLE reduce-scatter hands
        every replica the contiguous concat of its member shards (no
        second relayout collective), and wraps each member's
        mean-gradient shard in an :class:`UpdateShard`. The scatter
        independently takes the two-level schedule under the same
        shared ``choose_hierarchical`` decision as an equal-bytes AR
        bucket (half-vs-half prices exactly like AR-vs-AR). Returns
        ``[(source index, UpdateShard)]``.
        """
        n = self.num_replicas
        rows, shard_sizes = [], []
        for i in bucket:
            f = grads[i].reshape(-1)
            padded = -(-f.shape[0] // n) * n
            if padded > f.shape[0]:
                f = jnp.pad(f, (0, padded - f.shape[0]))
            rows.append(f.reshape(n, -1))
            shard_sizes.append(padded // n)
        buf = jnp.concatenate(rows, axis=1).reshape(-1)
        padded_bytes = int(buf.size * jnp.dtype(buf.dtype).itemsize)
        groups = self._hier_groups_for(padded_bytes, dtype, cname, spec,
                                       hknob)
        prog = sir.bucket_program(
            'psum_scatter', padded_bytes, dtype, cname, spec, n,
            hier=len(groups) if groups else 0, wus=True,
            node_groups=groups)
        shard = sir.execute(prog, buf, AXIS_DATA)
        meta = {'members': [sources[i].name for i in bucket],
                'shard_sizes': shard_sizes,
                'hier_groups': groups,
                'group': group, 'compressor': cname, 'dtype': dtype,
                'spec': spec, 'bytes': padded_bytes}
        self._record_entry(sir.schedule_entry(
            prog, group=group, members=list(meta['members']),
            vars_=len(bucket)))
        out, off = [], 0
        for pos, (i, m) in enumerate(zip(bucket, shard_sizes)):
            out.append((i, UpdateShard(shard[off:off + m], self,
                                       sources[i], meta, pos)))
            off += m
        return out

    def gather_updated_params(self, shards):
        """Gather half of the weight-update-sharding schedule: one
        bucketed all-gather per scatter bucket, reassembling every
        member's full updated value from the shard-local optimizer
        results.

        ``shards`` maps var name -> :class:`UpdateShard` carrying the
        UPDATED param shard (``Optimizer._apply``'s output); called by
        the frontend's ApplyGradients evaluation. Buckets mirror the
        scatter buckets exactly (the shared ``meta`` record), which is
        what ``static_collective_schedule``'s param-phase
        ``all_gather`` entries price; a PARTIALLY applied bucket (the
        user updated only some members — rare) degrades to per-member
        gathers. Returns ``{var name: full var-shaped value}``.
        """
        out = {}
        buckets = {}
        for name, sh in shards.items():
            buckets.setdefault(id(sh.meta), (sh.meta, {}))[1][name] = sh
        for meta, members in buckets.values():
            names = meta['members']
            hier = len(meta['hier_groups']) if meta['hier_groups'] \
                else 0
            if set(names) != set(members):
                for name, sh in members.items():
                    out[name] = sh.gather()
                    mprog = sir.bucket_program(
                        'all_gather',
                        sh.shard_size * self.num_replicas *
                        jnp.dtype(sh.value.dtype).itemsize,
                        meta['dtype'], meta['compressor'],
                        meta['spec'], self.num_replicas, hier=hier,
                        wus=True, node_groups=meta['hier_groups'])
                    self._record_entry(sir.schedule_entry(
                        mprog, group=meta['group'], members=[name]))
                continue
            cat = jnp.concatenate([members[nm].value for nm in names])
            groups = meta['hier_groups']
            prog = sir.bucket_program(
                'all_gather', meta['bytes'], meta['dtype'],
                meta['compressor'], meta['spec'], self.num_replicas,
                hier=hier, wus=True, node_groups=groups)
            full = sir.execute(prog, cat, AXIS_DATA)
            self._record_entry(sir.schedule_entry(
                prog, group=meta['group'], members=list(names),
                vars_=len(names)))
            mat = full.reshape(self.num_replicas, -1)
            off = 0
            for nm, m in zip(names, meta['shard_sizes']):
                var = members[nm].var
                flat = mat[:, off:off + m].reshape(-1)
                out[nm] = flat[:_numel(var.shape)].reshape(var.shape)
                off += m
        return out

    # -- padded physical layout (uneven partitions) ------------------------
    def padded_shape(self, var_name):
        """Physical (device) shape of a variable's state array."""
        plan = self.var_plans.get(var_name)
        if plan is None:
            return None
        shape = list(plan.var.shape)
        if plan.state_sharded and plan.pad:
            shape[plan.shard_axis] = plan.padded_dim
        return tuple(shape)

    def pad_host(self, var_name, value):
        """Logical host value -> physical (padded) layout."""
        plan = self.var_plans.get(var_name)
        if plan is None or not (plan.state_sharded and plan.pad):
            return value
        return self._pad_grad(plan, jnp.asarray(value))

    def unpad_host(self, var_name, value):
        """Physical layout -> logical host value."""
        plan = self.var_plans.get(var_name)
        if plan is None or not (plan.state_sharded and plan.pad):
            return value
        dim = plan.var.shape[plan.shard_axis]
        slicer = [slice(None)] * value.ndim
        slicer[plan.shard_axis] = slice(0, dim)
        return value[tuple(slicer)]

    # -- state shardings (used by the Session when placing arrays) --------
    def var_sharding(self, var_name):
        plan = self.var_plans.get(var_name)
        if plan is not None and plan.state_sharded:
            spec = [None] * len(plan.var.shape)
            spec[plan.shard_axis] = AXIS_DATA
            return NamedSharding(self.mesh, P(*spec))
        return NamedSharding(self.mesh, P())

    def var_spec(self, var_name):
        """PartitionSpec form (for shard_map in_specs)."""
        plan = self.var_plans.get(var_name)
        if plan is not None and plan.state_sharded:
            spec = [None] * len(plan.var.shape)
            spec[plan.shard_axis] = AXIS_DATA
            return P(*spec)
        return P()

    def replicated_sharding(self):
        return NamedSharding(self.mesh, P())

    def feed_splittable(self, value, placeholder=None):
        """Reference remapper rule (remapper.py:109-123): split feeds with a
        *polymorphic* (declared-None) batch dim across replicas, duplicate
        the rest. Fixed-shape placeholders are never split, matching the
        reference's shape-compatibility check.

        Unlike the reference's ``np.array_split`` (ragged per-replica
        batches under TF's dynamic shapes), XLA needs static equal
        shards, so a batch that does not divide the replica count is
        REPLICATED — numerically exact for mean losses but n× the
        FLOPs; warned once per placeholder so the cost is never silent.
        """
        if placeholder is not None:
            shape = getattr(placeholder, 'shape', None)
            if shape is not None and (len(shape) == 0 or
                                      shape[0] is not None):
                return False
        # Feeds are process-local (between-graph semantics): the value only
        # has to split across this process's local replicas.
        ok = (getattr(value, 'ndim', 0) >= 1 and
              value.shape[0] % self.local_replicas == 0 and
              value.shape[0] > 0)
        if (not ok and self.local_replicas > 1 and
                getattr(value, 'ndim', 0) >= 1 and value.shape[0] > 0):
            key = id(placeholder) if placeholder is not None else None
            if not hasattr(self, '_split_warned'):
                self._split_warned = set()
            if key not in self._split_warned:
                self._split_warned.add(key)
                logging.warning(
                    'Feed %s batch dim %d does not divide the %d local '
                    'replicas; the feed is REPLICATED on every replica '
                    '(exact numerics, %dx the FLOPs). Pad the batch to '
                    'a multiple of %d to split it.',
                    getattr(placeholder, 'name', '<tensor>'),
                    value.shape[0], self.local_replicas,
                    self.local_replicas, self.local_replicas)
        return ok

    def describe(self):
        """Human-readable lowering summary (logged like the reference logs
        its compiled strategy, autodist.py:117)."""
        lines = ['ExecutionPlan over mesh %s:' % dict(self.mesh.shape)]
        if any(p.is_ps and getattr(p.sync, 'reduction_destination', '')
               for p in self.var_plans.values()):
            lines.append(
                '  (PS reduction destinations are advisory under SPMD: '
                'state shards over the mesh, collectives replace '
                'push/pull. In loose mode they are load-bearing: each '
                'variable lives on the PS endpoint its destination maps '
                'to — session._init_ps_endpoints)')
        for name, p in self.var_plans.items():
            kind = 'AllReduce' if p.is_ar else 'PS'
            extra = ''
            if p.is_ps and getattr(p.sync, 'reduction_destination', ''):
                extra += ' dest=%s' % p.sync.reduction_destination
            if p.num_shards > 1:
                extra += ' shards=%d axis=%s' % (p.num_shards,
                                                 p.partition_axis)
            if p.state_sharded:
                extra += ' [ZeRO-sharded%s]' % (
                    ' pad=%d' % p.pad if p.pad else '')
            if p.is_ar:
                extra += ' group=%s compressor=%s' % (
                    p.group, type(p.compressor).__name__)
            if p.update_sharded:
                extra += ' [update-sharded%s]' % (
                    ' pad=%d' % p.wus_pad if p.wus_pad else '')
            if p.staleness:
                extra += ' staleness=%d' % p.staleness
            lines.append('  %s: %s%s' % (name, kind, extra))
        return '\n'.join(lines)
