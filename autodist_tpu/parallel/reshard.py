"""Device-side resharding: strategy-A layout -> strategy-B layout.

The portable-redistribution idea (PAPERS.md) applied to this runtime's
per-variable state layouts: an :class:`~autodist_tpu.parallel.plan.
ExecutionPlan` places every variable either REPLICATED or ZeRO-sharded
along one axis of the ``data`` mesh axis (padded for uneven partitions).
Migrating live state between two plans — an elastic re-plan picking a
new strategy, checkpoint-free strategy switching generally — is then a
per-variable layout map, executed ON DEVICE with collectives chosen by
the redistribution cost model, never a host round trip:

==================  ==================  ===========================
source layout       target layout       collective
==================  ==================  ===========================
replicated          replicated          none (``noop``)
replicated          sharded(b)          local slice (``shard``, 0 wire)
sharded(a)          replicated          ``all_gather``
sharded(a)          sharded(b), a != b  ``all_to_all`` OR
                                        ``gather_scatter`` — cheaper
                                        one per the cost model
sharded(a)          sharded(a), pad'    ``gather_scatter`` (repad)
==================  ==================  ===========================

``all_to_all`` moves the same ``(n-1)/n`` wire fraction as a gather
but never materializes the full tensor per device; ``gather_scatter``
(all-gather + local re-slice in ONE program) handles the padded /
non-dividing shapes ``all_to_all``'s tiled split cannot, at an extra
full-size HBM pass the model prices. The chosen op per variable rides
the :class:`ReshardOp` record so migrations are auditable
(``session.health_stats`` replan entries embed the summary).

Numerics: every path is a pure data movement — no arithmetic touches
the values — so a round trip A -> B -> A is bit-identical (the
property ``tests/test_reshard.py`` pins).
"""
from dataclasses import dataclass, field, asdict

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from autodist_tpu.const import AXIS_DATA
from autodist_tpu.parallel.axes import shard_map as _shard_map
from autodist_tpu.utils import logging


def var_layout(plan, name):
    """One variable's physical layout under ``plan``:
    ``{'sharded', 'axis', 'padded_dim', 'pad'}`` (axis fields are None
    for replicated state)."""
    p = plan.var_plans[name]
    if not p.state_sharded:
        return {'sharded': False, 'axis': None, 'padded_dim': None,
                'pad': 0}
    return {'sharded': True, 'axis': int(p.shard_axis),
            'padded_dim': int(p.padded_dim or
                              p.var.shape[p.shard_axis]),
            'pad': int(p.pad)}


@dataclass
class ReshardOp:
    """One variable's planned layout move."""
    var_name: str
    kind: str                      # noop|shard|all_gather|all_to_all|
    #                                gather_scatter
    src: dict = field(default_factory=dict)
    dst: dict = field(default_factory=dict)
    wire_bytes: int = 0            # per-device bytes on the wire
    est_time_s: float = 0.0        # redistribution cost-model estimate

    def to_dict(self):
        return asdict(self)

    def ir_program(self, n, elems, dtype='float32'):
        """This move as a :mod:`~autodist_tpu.parallel.schedule_ir`
        program — the same IR gradient syncs lower through, so the
        shape algebra verifies reshards too (``tools/analyze.py
        --schedule`` runs it). Element space is the flattened padded
        physical array in the DESTINATION coordinate frame; every path
        is pure data movement, so holdings carry full-value (ALL-
        contrib) fragments and the algebra checks coverage, never
        reduction completeness. ``ReshardOp`` stores layouts only, so
        the caller supplies the mesh size ``n`` and the physical
        element count ``elems``. Chaining ``run_algebra`` holdings
        through consecutive programs proves A -> B -> A identity
        (``tests/test_schedule_ir.py`` pins it)."""
        from autodist_tpu.parallel import schedule_ir as sir
        n = int(n)
        wire = sir.wire_of_dtype(dtype)
        meta = {'reshard': self.kind, 'var': self.var_name}
        name = 'reshard_%s_%s' % (self.kind, self.var_name)
        full = (tuple(range(n)),)
        if self.kind == 'noop':
            state = 'value_sharded' if self.src.get('sharded') \
                else 'value_replicated'
            E = sir._pad_to(elems, n) if state == 'value_sharded' \
                else int(elems)
            return sir.Program(name, n, E, str(dtype), (), state,
                               state, meta)
        E = sir._pad_to(elems, n)
        m = E // n
        chunks = (tuple((d * m, (d + 1) * m) for d in range(n)),)
        if self.kind == 'shard':
            # replicated -> sharded: zero-wire local projection; the
            # algebra checks each device already covers its chunk.
            steps = (sir.Step('scatter', tier='local', wire=wire,
                              groups=full, chunks=chunks),)
            return sir.Program(name, n, E, str(dtype), steps,
                               'value_replicated', 'value_sharded',
                               meta)
        if self.kind == 'all_gather':
            steps = (sir.Step('all_gather', tier='dcn', wire=wire,
                              groups=full, span=((0, E),),
                              nbytes=sir.wire_nbytes(E, wire)),)
            return sir.Program(name, n, E, str(dtype), steps,
                               'value_sharded', 'value_replicated',
                               meta)
        if self.kind == 'all_to_all':
            # sharded(a) -> sharded(b): in the destination frame each
            # source shard is the block transpose — device d holds one
            # mm-slice of every destination chunk — and one wired
            # scatter redistributes them into contiguous chunks.
            E = sir._pad_to(elems, n * n)
            m = E // n
            mm = m // n
            ALL = frozenset(range(n))
            init = [[(j * m + d * mm, j * m + (d + 1) * mm, ALL)
                     for j in range(n)] for d in range(n)]
            chunks = (tuple((d * m, (d + 1) * m) for d in range(n)),)
            nb = (n - 1) / float(max(1, n)) * \
                sir.wire_nbytes(E, wire) or 1.0
            steps = (sir.Step('scatter', tier='dcn', wire=wire,
                              groups=full, chunks=chunks, nbytes=nb),)
            return sir.Program(name, n, E, str(dtype), steps, init,
                               'value_sharded', meta)
        if self.kind == 'gather_scatter':
            steps = (sir.Step('all_gather', tier='dcn', wire=wire,
                              groups=full, span=((0, E),),
                              nbytes=sir.wire_nbytes(E, wire)),
                     sir.Step('scatter', tier='local', wire=wire,
                              groups=full, chunks=chunks))
            return sir.Program(name, n, E, str(dtype), steps,
                               'value_sharded', 'value_sharded', meta)
        raise ValueError('Unknown reshard kind %r' % (self.kind,))


def _move_cost(kind, nbytes, n, params):
    """Redistribution cost-model estimate for one move of ``nbytes``
    physical bytes over the ``n``-way data axis. Collectives price at
    the DCN tier when the plan spans nodes is unknowable here, so the
    conservative cross-node constants apply; ``gather_scatter``
    additionally pays a full-tensor HBM pass (the per-device
    materialize + re-slice ``all_to_all`` avoids)."""
    if n <= 1 or kind in ('noop', 'shard'):
        return 0.0
    alpha, beta = params.link(cross_node=True)
    t = (n - 1) * alpha + (n - 1) / n * float(nbytes) * beta
    if kind == 'gather_scatter':
        t += float(nbytes) * params.compress_s_per_byte
    return t


def plan_reshard(old_plan, new_plan, params=None):
    """Plan the per-variable moves from ``old_plan``'s layouts to
    ``new_plan``'s. Pure (no device work); returns ``[ReshardOp]``
    covering every variable both plans know, cheapest collective per
    the redistribution cost model."""
    if params is None:
        params = getattr(new_plan, 'cost_params', None) or \
            getattr(old_plan, 'cost_params', None)
    n = old_plan.num_replicas
    ops = []
    for name in old_plan.var_plans:
        if name not in new_plan.var_plans:
            continue
        src = var_layout(old_plan, name)
        dst = var_layout(new_plan, name)
        var = old_plan.var_plans[name].var
        itemsize = np.dtype(var.dtype).itemsize
        phys = list(var.shape)
        if src['sharded']:
            phys[src['axis']] = src['padded_dim']
        nbytes = int(np.prod(phys or [1])) * itemsize
        if src == dst:
            kind = 'noop'
        elif not src['sharded'] and dst['sharded']:
            kind = 'shard'
        elif src['sharded'] and not dst['sharded']:
            kind = 'all_gather'
        else:
            # sharded -> sharded: all_to_all only lowers when neither
            # side is padded (its tiled split needs exact division);
            # otherwise the single-program gather+re-slice handles any
            # geometry. Where both apply, the cost model picks.
            clean = (src['pad'] == 0 and dst['pad'] == 0 and
                     src['axis'] != dst['axis'])
            if clean and _move_cost('all_to_all', nbytes, n, params) <= \
                    _move_cost('gather_scatter', nbytes, n, params):
                kind = 'all_to_all'
            else:
                kind = 'gather_scatter'
        wire = 0 if kind in ('noop', 'shard') else \
            int((n - 1) / max(1, n) * nbytes)
        ops.append(ReshardOp(
            var_name=name, kind=kind, src=src, dst=dst,
            wire_bytes=wire,
            est_time_s=_move_cost(kind, nbytes, n, params)))
    return ops


def _spec_for(layout, ndim):
    if not layout['sharded']:
        return P()
    spec = [None] * ndim
    spec[layout['axis']] = AXIS_DATA
    return P(*spec)


def reshard_fn(op, old_plan, new_plan):
    """Compile-ready callable moving ONE variable's physical array from
    ``op.src`` to ``op.dst`` layout — a single device-side program
    (shard_map over the data axis; XLA lowers the collective), reusable
    for any array of the variable's physical shape (optimizer slots
    shaped like their variable ride the same fn)."""
    mesh = new_plan.mesh
    n = new_plan.num_replicas
    var = new_plan.var_plans[op.var_name].var
    logical = tuple(int(d) for d in var.shape)
    ndim = len(logical)
    src, dst = op.src, op.dst

    def unpad_src(x):
        if src['sharded'] and src['pad']:
            x = jax.lax.slice_in_dim(x, 0, logical[src['axis']],
                                     axis=src['axis'])
        return x

    def pad_dst(x):
        if dst['sharded'] and dst['pad']:
            cfg = [(0, 0)] * x.ndim
            cfg[dst['axis']] = (0, dst['pad'])
            x = jnp.pad(x, cfg)
        return x

    if op.kind == 'noop':
        return lambda x: x

    if op.kind == 'shard':
        def shard(x):
            x = pad_dst(x)
            size = x.shape[dst['axis']] // n
            me = jax.lax.axis_index(AXIS_DATA)
            return jax.lax.dynamic_slice_in_dim(
                x, me * size, size, axis=dst['axis'])
        return jax.jit(_shard_map(shard, mesh, P(),
                                  _spec_for(dst, ndim)))

    if op.kind == 'all_gather':
        def gather(x):
            full = jax.lax.all_gather(x, AXIS_DATA, axis=src['axis'],
                                      tiled=True)
            return unpad_src(full)
        return jax.jit(_shard_map(gather, mesh,
                                  _spec_for(src, ndim), P()))

    if op.kind == 'all_to_all':
        def a2a(x):
            return jax.lax.all_to_all(x, AXIS_DATA,
                                      split_axis=dst['axis'],
                                      concat_axis=src['axis'],
                                      tiled=True)
        return jax.jit(_shard_map(a2a, mesh, _spec_for(src, ndim),
                                  _spec_for(dst, ndim)))

    if op.kind == 'gather_scatter':
        def gs(x):
            full = unpad_src(
                jax.lax.all_gather(x, AXIS_DATA, axis=src['axis'],
                                   tiled=True))
            full = pad_dst(full)
            size = full.shape[dst['axis']] // n
            me = jax.lax.axis_index(AXIS_DATA)
            return jax.lax.dynamic_slice_in_dim(
                full, me * size, size, axis=dst['axis'])
        return jax.jit(_shard_map(gs, mesh, _spec_for(src, ndim),
                                  _spec_for(dst, ndim)))

    raise ValueError('Unknown reshard kind %r' % (op.kind,))


def apply_reshard(old_plan, new_plan, arrays, ops=None, extra=None):
    """Execute a reshard plan on device.

    Args:
        old_plan / new_plan: the two :class:`ExecutionPlan`\\ s. They
            must share one mesh (a reshard moves layouts, not devices —
            growing the mesh itself is a different operation).
        arrays: ``{var name: physical jax.Array}`` under ``old_plan``'s
            layouts (the session's ``_var_state``).
        ops: a ``plan_reshard`` result to execute (default: planned
            fresh).
        extra: optional ``{var name: [more arrays]}`` that share their
            variable's physical layout (optimizer slot tensors); moved
            through the SAME compiled fn.

    Returns ``(new_arrays, new_extra, ops)`` with every array placed
    per ``new_plan``. Values are moved, never recomputed — bit-exact.
    """
    if list(old_plan.mesh.devices.flat) != \
            list(new_plan.mesh.devices.flat):
        raise ValueError('reshard requires both plans on one mesh; '
                         'got %s vs %s' % (old_plan.mesh, new_plan.mesh))
    if ops is None:
        ops = plan_reshard(old_plan, new_plan)
    extra = extra or {}
    out, out_extra = {}, {}
    moved = 0
    for op in ops:
        arr = arrays.get(op.var_name)
        if arr is None:
            continue
        fn = reshard_fn(op, old_plan, new_plan)
        out[op.var_name] = fn(arr)
        if op.var_name in extra:
            out_extra[op.var_name] = [fn(a)
                                      for a in extra[op.var_name]]
        if op.kind != 'noop':
            moved += 1
    logging.info('reshard: %d vars moved (%d layout changes), '
                 'est %.3g s, %.1f KiB wire per device', len(out),
                 moved, sum(o.est_time_s for o in ops),
                 sum(o.wire_bytes for o in ops) / 1024.0)
    return out, out_extra, ops


def summarize(ops):
    """Compact audit record of a reshard plan (rides health_stats)."""
    kinds = {}
    for op in ops:
        kinds[op.kind] = kinds.get(op.kind, 0) + 1
    return {'vars': len(ops), 'kinds': kinds,
            'wire_bytes': sum(o.wire_bytes for o in ops),
            'est_time_s': sum(o.est_time_s for o in ops)}
