"""Session: the per-step execution driver.

Replaces the reference's ``WrappedSession`` + ``Remapper``
(``autodist/runner.py:78-132``, ``autodist/remapper.py:29-313``). Where
the reference patches TF's feed/fetch expansion registry and talks to a
grpc server, the TPU session owns the training state (variables, optimizer
slots, compressor aux state) as sharded ``jax.Array``s and compiles one
fused XLA program per distinct (fetches, feed-signature) pair:

- **feed remapping** (remapper.py:109-123): feeds whose leading dim splits
  evenly across the ``data`` axis are sharded onto it; others replicated.
- **fetch remapping** (remapper.py:125-185): train ops run on all replicas
  and fetch as None; tensors with a batch ("polymorphic") dim concatenate
  across replicas; everything else returns the master replica's value.
- the whole captured program is interpreted inside ``shard_map`` over the
  mesh, so replication+synchronization compile into a single program (the
  reference's in-graph replication + collective splicing equivalent).
"""
import os
from collections import deque as _deque

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import telemetry as _telemetry
from autodist_tpu.const import (AXIS_DATA, DEFAULT_CHECKPOINT_DIR,
                                DEFAULT_TRACE_DIR, ENV)
from autodist_tpu.frontend import graph as fe
from autodist_tpu.parallel.axes import shard_map as _shard_map
from autodist_tpu.parallel.plan import ShardedGrad
from autodist_tpu.utils import logging


class RunOptions:
    """Shim for tf.RunOptions: trace_level triggers a profiler trace
    (reference runner.py:64-75 writes chrome traces)."""

    NO_TRACE = 0
    FULL_TRACE = 3

    def __init__(self, trace_level=0, trace_dir=None):
        self.trace_level = trace_level
        self.trace_dir = trace_dir or DEFAULT_TRACE_DIR


def assign_ps_endpoints(var_plans, endpoints):
    """Map each variable to PS endpoint indices, one PER SHARD.

    Placement honors the strategy's per-shard ``reduction_destination``s
    (reference ps_lb_strategy.py:64-83 bin-packing;
    partitioned_ps_strategy.py:89-96 places each shard of a partitioned
    variable on its own PS — ``part_config`` is consumed here, not just
    ``syncs[0]``): endpoints co-located on the destination's host are
    preferred (several on one host spread by destination ordinal);
    destinations on unknown hosts map by their ordinal among the sorted
    distinct destinations; vars without a destination hash stably.
    Returns ``{var name: [endpoint idx per shard]}`` (a 1-element list
    for unpartitioned variables). Pure function so placement is
    unit-testable and deterministic across processes.
    """
    import zlib
    n = len(endpoints)
    hosts = [h for h, _ in endpoints]
    all_dests = set()
    for p in var_plans.values():
        if not p.is_ps:
            continue
        for s in getattr(p, 'all_syncs', [p.sync]):
            d = getattr(s, 'reduction_destination', '')
            if d:
                all_dests.add(d)
    dest_ord = {d: i for i, d in enumerate(sorted(all_dests))}

    def resolve(label, sync, is_ps):
        dest = getattr(sync, 'reduction_destination', '') if is_ps else ''
        if dest:
            dhost = dest.split(':', 1)[0]
            cands = [i for i, h in enumerate(hosts) if h == dhost]
            if cands:
                return cands[dest_ord[dest] % len(cands)]
            return dest_ord[dest] % n
        return zlib.crc32(label.encode()) % n

    out = {}
    for name, p in var_plans.items():
        syncs = list(getattr(p, 'all_syncs', [p.sync]))
        nshards = getattr(p, 'num_shards', 1)
        if nshards > 1 and len(syncs) == nshards:
            out[name] = [
                resolve('%s/shard%d' % (name, i), s, p.is_ps)
                for i, s in enumerate(syncs)]
        else:
            out[name] = [resolve(name, p.sync, p.is_ps)]
    return out


def live_members_on_plane(coord, ns):
    """THE live-membership definition for namespace ``ns`` — claimed
    ordinals minus excluded slots — as ``(live, world, excluded)``.
    :func:`admit_worker`'s cap check and the coordinator's scale-up
    clamp (``Coordinator._live_world_estimate``) both ride this one
    implementation: if the definition ever changes (e.g. counting
    done/ markers), they must move together or the clamp and the
    authoritative admit-time refusal silently disagree."""
    world = coord.incr('%s/join/world' % ns, 0)
    excluded = sum(
        1 for i in range(world)
        if coord.incr('excluded/%s/p%d' % (ns, i), 0) > 0)
    return world - excluded, world, excluded


def admit_worker(coord, ns, max_workers=None, wait_init_s=120.0,
                 launch_workers=None):
    """The live scale-UP admit handshake: join worker ``coord`` into the
    RUNNING loose-mode namespace ``ns`` (the second half of elasticity —
    PR 4 made workers *leaving* survivable; this makes joining possible).

    One protocol, one place: :class:`Session` joins through it when
    ``AUTODIST_ELASTIC_JOIN`` is set, and the chaos tests drive it
    with a raw client — the handshake must not be
    re-implemented per caller or the fault-injection coverage
    (``faultline``'s ``join_*`` kinds) stops meaning anything.

    Ordering is the contract (each step's placement matters):

    1. wait for ``<ns>/session/init-done`` — a join is only legal
       against a cohort whose init rendezvous completed (the world
       counter is only guaranteed seeded after it, and the chief clears
       stale markers before it).
    2. claim a worker slot: an atomic ``INCR`` of ``<ns>/join/world``
       (the same counter the launch cohort seeded to its quorum — no
       new service atomic needed). Refused when the claim would exceed
       ``AUTODIST_MAX_WORKERS``.
    3. bind the slot's fence generation BEFORE any namespace write, so
       every admit-path write is already fenceable: a joiner declared
       dead mid-admit is rejected exactly like any other zombie.
    4. compute the adopted step FLOOR: the min of live members'
       published steps (``CLEAN_CLOSE_STEP`` releases and never-
       published zeros skipped) — the one value that neither blocks the
       cohort's staleness gates (a join at step 0 would stall everyone
       at ``floor + staleness``) nor claims progress ahead of any peer.
    5. bump ``<ns>/epoch`` — MEMBERSHIP BECOMES VISIBLE FIRST, then
       the floor is published and the heartbeat baseline laid down.
       This order is the one whose failure window SELF-HEALS: a joiner
       dying after the bump is a visible member with no step/beat,
       which the never-beat rule declares dead and the exclude path
       releases within one heartbeat window. The reverse order
       (step counter before membership) leaves an INVISIBLE frozen
       counter inside the gate's prefix-min that no survivor can ever
       exclude — a permanent cohort stall with no recovery path.

    Returns ``{'worker_id', 'worker', 'world', 'generation',
    'adopted_step', 'epoch', 'admit_wall_s'}``.
    """
    import time as _time
    from autodist_tpu.runtime.coord_client import CLEAN_CLOSE_STEP
    if max_workers is None:
        max_workers = ENV.AUTODIST_MAX_WORKERS.val
    t0 = _time.monotonic()
    coord.wait_key('%s/session/init-done' % ns, timeout_s=wait_init_s)
    world_key = '%s/join/world' % ns
    # the cap bounds LIVE membership, not cumulative ordinals: the
    # monotone counter never decrements, so dead (excluded) workers
    # must hand their headroom back or a long-running job with churn
    # would ratchet itself below the ceiling it is allowed to refill.
    # (One serial INCR per ordinal: at the default 64-worker cap this
    # is a handful of round-trips paid once per admit, not per step.)
    live, before, excluded_n = live_members_on_plane(coord, ns)
    if launch_workers and before < launch_workers:
        raise RuntimeError(
            'cannot join namespace %s: its world counter (%d) is below '
            'the launch quorum (%d) — the cohort never seeded it (a '
            'stale init-done marker on a reused service, or not an '
            'elastic-capable run)' % (ns, before, launch_workers))
    if live >= max_workers:
        raise RuntimeError(
            'cannot join namespace %s: live membership (%d of %d '
            'claimed slots) is already at AUTODIST_MAX_WORKERS=%d'
            % (ns, live, before, max_workers))
    world = coord.incr(world_key, 1)
    worker_id = world - 1
    worker = 'p%d' % worker_id
    flight = _telemetry.recorder()
    flight.record('admit_claim', worker=worker, world=world, ns=ns)
    if world - excluded_n > max_workers:
        # the cap read above and the claim are separate RPCs, so two
        # concurrent joiners can both pass the pre-check; the LAST
        # claim lands over the cap. The claim cannot be rolled back
        # (the monotone counter never re-issues ordinals — a decrement
        # would hand the next joiner a colliding slot), so retire the
        # slot as already-excluded + released: any survivor that ever
        # sees it skips it without paying a heartbeat window, and the
        # live membership never exceeds the cap.
        coord.incr('excluded/%s/%s' % (ns, worker), 1)
        coord.publish_step(worker, CLEAN_CLOSE_STEP,
                           prefix='%s/step/' % ns)
        flight.record('admit_cap_retire', worker=worker, world=world)
        raise RuntimeError(
            'cannot join namespace %s: a concurrent join raced this '
            'claim past AUTODIST_MAX_WORKERS=%d (slot %s retired as '
            'excluded)' % (ns, max_workers, worker))
    # fence binding precedes every namespace write below; generation>0
    # means this SLOT was admitted before and its holder declared dead
    # (slots are never re-issued by the monotone world counter, so that
    # only happens to a supervised re-admit of this same joiner).
    fence_key = 'fence/%s/%s' % (ns, worker)
    generation = coord.incr(fence_key, 0)
    coord.fence(fence_key, generation)
    flight.record('admit_fence_bind', worker=worker,
                  generation=generation)
    floor = None
    for i in range(worker_id):
        step = coord.incr('%s/step/p%d' % (ns, i), 0)
        if step == 0 or step >= CLEAN_CLOSE_STEP:
            # never-published (a half-admitted ghost, or a cohort still
            # at step 0 — then every member reads 0 and the floor
            # degrades to 0 anyway) or a departed worker's release
            continue
        floor = step if floor is None else min(floor, step)
    # a crashed-but-not-yet-excluded peer can still be in this min, but
    # the staleness gate bounds how stale: every live counter (and so
    # any recent corpse's) is within gate_staleness of the cohort's
    # front, so adopting it costs the joiner at most `staleness` extra
    # catch-up steps — never a cohort stall
    floor = floor or 0
    # epoch bump BEFORE the step publish (see step 5 above): every
    # post-claim death must leave a VISIBLE member the exclusion
    # machinery can clean up, never an invisible counter it cannot
    epoch = coord.incr('%s/epoch' % ns, 1)
    flight.record('admit_epoch_bump', worker=worker, epoch=epoch)
    coord.publish_step(worker, floor, prefix='%s/step/' % ns)
    flight.record('admit_floor_publish', worker=worker, floor=floor)
    coord.heartbeat('%s/%s' % (ns, worker))
    wall = _time.monotonic() - t0
    logging.info(
        'admitted %s into %s at epoch %d: world %d -> %d, adopted step '
        'floor %d, generation %d (%.3fs)', worker, ns, epoch, before,
        world, floor, generation, wall)
    return {'worker_id': worker_id, 'worker': worker, 'world': world,
            'generation': generation, 'adopted_step': floor,
            'epoch': epoch, 'admit_wall_s': wall}


def admit_reader(coord, ns, wait_init_s=120.0):
    """Admit a NON-VOTING serving replica into namespace ``ns`` — the
    reader half of :func:`admit_worker`, deliberately missing every
    step that makes a worker count:

    - no fence bind: readers never take writer generations (a
      read-only data connection cannot even issue FENCE —
      :class:`~autodist_tpu.runtime.coord_client.ReadOnlyViolation`);
    - no ``join/world`` claim, no epoch bump, no step publish: the
      reader must be invisible to :func:`live_members_on_plane`, the
      staleness gates and every exclusion/quorum path — a reader dying
      mid-pull must cost the training cohort NOTHING, not even one
      heartbeat window of exclusion work.

    Readers claim ordinals on their own ``<ns>/serve/world`` counter
    (same monotone-claim idiom, disjoint key) and heartbeat under
    ``hb/serve/<ns>/r<i>`` — a SERVE-prefixed liveness plane the
    training cohort never scans. ``coord`` must be a WRITABLE control
    connection (the claim and beats are INCRs); the replica's bulk
    data pulls ride a separate read-only connection.

    Returns ``{'reader_id', 'reader', 'serve_world', 'admit_wall_s'}``.
    """
    import time as _time
    t0 = _time.monotonic()
    # same legality condition as a worker join: the world/step keys a
    # reader is about to poll are only guaranteed seeded (and stale
    # markers cleared) after the cohort's init rendezvous
    coord.wait_key('%s/session/init-done' % ns, timeout_s=wait_init_s)
    serve_world = coord.incr('%s/serve/world' % ns, 1)
    reader_id = serve_world - 1
    reader = 'r%d' % reader_id
    coord.heartbeat('serve/%s/%s' % (ns, reader))
    _telemetry.recorder().record('serve_admit', reader=reader, ns=ns,
                                 serve_world=serve_world)
    wall = _time.monotonic() - t0
    logging.info('admitted serving replica %s into %s (serve world %d, '
                 'non-voting, %.3fs)', reader, ns, serve_world, wall)
    return {'reader_id': reader_id, 'reader': reader,
            'serve_world': serve_world, 'admit_wall_s': wall}


class _LazyDefault:
    """Non-data descriptor: a class-level fallback a stub session
    built via ``__new__`` (liveness/chaos tests exercise single
    methods that way) resolves to the same process-wide value
    ``__init__`` would have bound — and which any instance assignment
    shadows. Deliberately NOT ``__getattr__``: that hook would convert
    an ``AttributeError`` escaping any Session property getter into a
    misleading ``AttributeError: <property name>``."""

    def __init__(self, factory, name):
        self._factory = factory
        self._name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        val = self._factory()
        obj.__dict__[self._name] = val
        return val


class Session:
    """Stateful driver over the functional compiled step.

    Multi-process modes:

    - **global SPMD** (sync strategies): every process joins one program
      over a multi-host mesh; gradient sync rides XLA collectives. The
      feed/fetch contract stays process-local (between-graph semantics:
      each worker feeds its own batch, fetches its own replicas' values).
    - **loose** (all-relaxed PS strategies): each process runs an
      independent local program; variables are authoritative on the native
      coord service, workers pull values / push update deltas every step
      (apply-per-push = reference staleness-mode accumulators,
      ps_synchronizer.py:387-458) gated by the bounded-staleness window.
    """

    _tel = _LazyDefault(lambda: _telemetry.get(), '_tel')
    _flight = _LazyDefault(lambda: _telemetry.recorder(), '_flight')
    _step_walls = _LazyDefault(
        lambda: _deque(maxlen=ENV.AUTODIST_TELEMETRY_MAX_SPANS.val),
        '_step_walls')
    # stub sessions (__new__) have no sentry, no telemetry push lane
    # and no roofline tracker; real ones bind in __init__
    _monitor = None
    _tel_pipe = None
    _tel_push_handle = None
    _roofline_tracker = None
    _last_step_cost = None
    _last_exec_wall = 0.0

    def __init__(self, graph_item, plan, cluster=None, coord=None):
        self._graph_item = graph_item
        self._plan = plan
        self._mesh = plan.mesh
        self._cluster = cluster
        self._coord = coord
        self._cache = {}
        self._step_count = 0
        self._round_count = 0   # completed local-SGD sync rounds
        self._closed = False
        self._loose = plan.loose
        # namespace coord-service keys by strategy id: a reused/leaked
        # service must not serve a previous run's vars or step counters.
        # (Assigned before identity: the elastic admit below claims a
        # worker slot under this namespace.)
        self._ns = getattr(plan.strategy, 'id', 'default')
        if self._loose and coord is None:
            raise RuntimeError('loose multi-process mode needs a coord '
                               'service client')
        # telemetry handles + the run boundary BEFORE the elastic
        # admit below: the admit handshake records flight events, and
        # a run_start recorded after them would wipe the only live
        # admit trail from the conformance replay (the checker resets
        # per-run tracking at every boundary). Worker identity is
        # attached once the admit has settled it.
        self._tel = _telemetry.get()
        self._flight = _telemetry.recorder()
        self._flight.set_context(ns=self._ns)
        self._flight.record('run_start', ns=self._ns)
        # -- elastic scale-UP: live JOIN into a running namespace ----------
        # AUTODIST_ELASTIC_JOIN marks this process as a joiner: it was
        # not part of the launch cohort, so its definitive identity is
        # the slot the admit handshake claims — the spawner's env
        # process id is advisory only. The env is rewritten to the
        # claimed slot so everything downstream (worker name, heartbeat
        # peers, pipeline floor loops) agrees with the control plane.
        self._joining = False
        self._admit = None
        if self._loose and ENV.AUTODIST_ELASTIC_JOIN.val:
            # launch_workers guards the never-seeded case: a stale
            # init-done marker on a reused service must refuse the
            # join, not hand out a launch-cohort ordinal (read BEFORE
            # the identity env rewrite below)
            self._admit = admit_worker(
                coord, self._ns,
                launch_workers=ENV.AUTODIST_NUM_PROCESSES.val)
            os.environ[ENV.AUTODIST_PROCESS_ID.name] = \
                str(self._admit['worker_id'])
            os.environ[ENV.AUTODIST_NUM_PROCESSES.name] = \
                str(self._admit['world'])
            self._joining = True
        self._num_workers = ENV.AUTODIST_NUM_PROCESSES.val
        self._worker_name = 'p%d' % ENV.AUTODIST_PROCESS_ID.val
        self._flight.set_context(worker=self._worker_name)
        # uniform per-step wall series: EVERY executed train step's
        # run() wall time lands here, loose or SPMD, pipelined or
        # serial (the t_step phase timing only covers loose-mode
        # paths). Bounded ring; count/total survive in the telemetry
        # series when enabled.
        self._step_walls = _deque(
            maxlen=ENV.AUTODIST_TELEMETRY_MAX_SPANS.val)
        # a joiner is never the chief: the chief seeded the PS and owns
        # the cohort rendezvous — a joiner consumes both
        self._is_chief = not ENV.AUTODIST_WORKER.val and \
            not self._joining
        # Bucketed AllReduce sync (plan.sync_gradients) only overlaps
        # the backward pass if XLA is allowed to schedule the bucket
        # collectives asynchronously — arm the latency-hiding flags
        # (opt-out: AUTODIST_XLA_OVERLAP=0). libtpu reads them at
        # backend init, so on an already-up backend they reach only
        # processes launched after this point (the coordinator forwards
        # LIBTPU_INIT_ARGS to workers).
        if not self._loose and plan.num_replicas > 1 and \
                any(p.is_ar for p in plan.var_plans.values()):
            from autodist_tpu.utils.jax_env import setup_overlap_flags
            applied = setup_overlap_flags()
            if applied:
                logging.info('Gradient bucketing active: armed XLA '
                             'overlap flags %s', applied)
        # -- elastic recovery (epoch-fenced membership) --------------------
        # Peer-failure policy: what a survivor does when a peer misses
        # heartbeats (fail = raise, exclude = fence + shrink membership,
        # restart = wait for the coordinator-supervised replacement).
        self._policy = ENV.AUTODIST_PEER_FAILURE_POLICY.val
        self._min_workers = ENV.AUTODIST_MIN_WORKERS.val
        self._excluded = set()      # peer keys dropped from membership
        self._dead_since = {}       # restart policy: key -> detect time
        self._epoch_seen = 0        # membership epoch (coord counter)
        self._generation = 0        # this worker's fencing generation
        self._fence_key = ''
        self._rejoining = False
        # live world size: the launch quorum GROWN by admitted joiners
        # (the <ns>/join/world counter). Every membership-derived
        # quantity — gate party counts, the AUTODIST_MIN_WORKERS floor,
        # pipeline peer floors, the close() purge quorum — re-evaluates
        # against this, never the launch-time count.
        self._world = self._num_workers
        self._health = {'policy': self._policy, 'missed_beats': 0,
                        'epoch_bumps': 0, 'exclusions': [],
                        'rejoins': [], 'recovery_wall_s': [],
                        'joins': [], 'replans': [],
                        'auto_checkpoints': 0}
        if self._joining:
            self._health['admitted'] = dict(self._admit)
        if self._loose:
            # every write this process makes rides connections bound to
            # its fencing generation: once a survivor (or the restart
            # supervisor) bumps our fence counter, the service rejects
            # our writes — a zombie can never corrupt post-death state.
            # fence/excluded counters live OUTSIDE the run namespace:
            # the run-end purge (close) must not unfence a zombie or
            # erase the exclusion record it may still need to observe
            self._fence_key = 'fence/%s' % self._key(self._worker_name)
            self._generation = coord.incr(self._fence_key, 0)
            coord.fence(self._fence_key, self._generation)
            self._flight.set_context(generation=self._generation)
            self._flight.record('fence_bind', worker=self._worker_name,
                                generation=self._generation)
            # generation > 0 means a previous incarnation was declared
            # dead: this process is its supervised replacement and must
            # REJOIN (skip the init barrier nobody else attends, pull
            # current params from the PS, resume at the published step).
            # A live JOINer claims a fresh slot (generation 0) instead.
            self._rejoining = self._generation > 0 and not self._joining
            if self._is_chief and not self._rejoining:
                # a reused service may hold a PREVIOUS run's init-done
                # marker (deterministic strategy id, crashed run whose
                # close-purge never ran): left in place, a joiner
                # launched before this chief could admit against the
                # stale world counter and collide with the reset below
                # — delete it FIRST (it is re-published only after this
                # run's rendezvous completes). The residual window (a
                # joiner passing wait_key before this delete) requires
                # joiners launched before the run they join, which the
                # scale-up paths never do.
                self._coord.delete(self._key('session/init-done'))
                # likewise a previous run's telemetry namespace (batch
                # keys + the atomic batch counters): the close-side
                # purge below covers the normal path, but a crashed
                # prior run whose close never ran would replay its
                # stale batches into THIS run's cohort trace — the
                # per-worker batch counter would hand the collector
                # sequence numbers that decode to the dead run's spans
                self._coord.delete_namespace(self._key('telemetry/'))
                # likewise any staged epoch-swap plan (generation
                # counter included): a crashed prior run's staged
                # generation must never be validated/acked — let alone
                # applied — by THIS run's cohort (the armed boundary
                # would compare against the dead run's step floors)
                from autodist_tpu.runtime import swap_keys
                swap_keys.purge_all(self._coord, self._ns)
                # seed the elastic world counter to the launch quorum
                # BEFORE the init rendezvous (admits wait for the
                # init-done marker, so no join can race this). A stale
                # counter on a reused service is forced back to the
                # quorum — joins are only legal against live state.
                cur = coord.incr(self._key('join/world'), 0)
                if cur != self._num_workers:
                    coord.incr(self._key('join/world'),
                               self._num_workers - cur)
            self._epoch_seen = coord.incr(self._key('epoch'), 0)
            self._refresh_membership(
                adopt_growth=self._rejoining or self._joining)
            if self._rejoining:
                self._step_count = coord.incr(
                    self._key('step/') + self._worker_name, 0)
                logging.info(
                    'rejoining as %s under generation %d at published '
                    'step %d (membership epoch %d)', self._worker_name,
                    self._generation, self._step_count, self._epoch_seen)
            elif self._joining:
                # the admit handshake already published this floor; the
                # session resumes counting from it
                self._step_count = self._admit['adopted_step']
            # under a local-SGD window the published counters hold sync
            # ROUNDS, not train steps: a (re)joiner adopts the round
            # floor and resumes at that round's first train step
            h = max(1, getattr(plan, 'local_steps', 1))
            if h > 1 and (self._rejoining or self._joining):
                self._round_count = self._step_count
                self._step_count *= h
        # -- online performance sentry (chief-side) --------------------
        # The CohortMonitor streams the cohort's span batches off the
        # telemetry namespace (poll rides the push cadence), issues
        # straggler verdicts with phase attribution, records
        # slowdown/recovered flight events, and — on the
        # AUTODIST_RECALIBRATE_EVERY cadence — refits the cost model's
        # link constants from live traffic for _replan_for_world's
        # re-rank. Chief-only (verdicts need the whole cohort's spans,
        # which only the chief collects) and telemetry-gated: with
        # AUTODIST_TELEMETRY off nobody pushes batches to consume.
        self._monitor = None
        self._recalibrate_every = ENV.AUTODIST_RECALIBRATE_EVERY.val
        self._last_recalibrate_step = 0
        if self._loose and self._is_chief and self._tel.enabled and \
                ENV.AUTODIST_STRAGGLER_POLICY.val != 'off':
            from autodist_tpu.telemetry.monitor import CohortMonitor
            self._monitor = CohortMonitor(
                client=self._coord, ns=self._ns,
                workers=lambda: ['p%d' % i
                                 for i in self._live_members()],
                flight=self._flight,
                # our own batches are tapped at drain time, never
                # fetched back off the wire (ingest_local)
                local_worker=self._worker_name)
        # -- device-plane roofline observatory (per-worker) ------------
        # AUTODIST_ROOFLINE: per-step MFU/regime accounting — FLOPs +
        # bytes-accessed from the compiled step (cost_analysis() on
        # the lowered program, computed once per compilation below)
        # over the measured wall and the topology's peak table.
        # Samples land on the telemetry series, feed the monitor's
        # compute/memory-bound verdict refinement, and a drop below
        # the rolling baseline records an mfu_regression flight event.
        self._roofline_tracker = None
        self._roofline_costs = {}
        self._last_step_cost = None
        self._last_exec_wall = 0.0
        if ENV.AUTODIST_ROOFLINE.val:
            from autodist_tpu.telemetry.roofline import RooflineTracker
            rs = getattr(cluster, '_resource_spec', None)
            topo = rs.topology if rs is not None else \
                getattr(plan, 'topology', None)
            if topo is not None:
                peak_flops, peak_hbm = topo.peaks()
            else:
                forced = ENV.AUTODIST_ROOFLINE_PEAKS.val
                peak_flops = forced.get('flops')
                peak_hbm = forced.get('hbm_gbps')
                peak_hbm = peak_hbm * 1e9 if peak_hbm else None
            self._roofline_tracker = RooflineTracker(
                peak_flops=peak_flops, peak_hbm_bps=peak_hbm,
                tel=self._tel, flight=self._flight,
                worker=self._worker_name)
        # chief-side auto-checkpoint backstop: with restarts in play the
        # PS state is authoritative, but a periodic chief snapshot
        # bounds the blast radius of losing the PS itself
        self._auto_ckpt = None
        self._auto_ckpt_every = ENV.AUTODIST_AUTO_CHECKPOINT_EVERY.val
        if self._loose and self._is_chief and self._auto_ckpt_every:
            from autodist_tpu.checkpoint.saver import CheckpointManager
            self._auto_ckpt = CheckpointManager(
                os.path.join(DEFAULT_CHECKPOINT_DIR, 'auto', self._ns),
                max_to_keep=2, async_save=True)
        # proxy variables (reference proxy_variable.py:46-190): a worker-
        # local cached copy serves reads. In SPMD programs reads are
        # already device-local, so the proxy is inherently satisfied; in
        # loose mode it is real: the pre-step PS pull is replaced by the
        # cache, refreshed from the PS after each push (the reference's
        # post-update assign, proxy_variable.py:163-190).
        self._proxy_vars = {
            name for name, p in plan.var_plans.items()
            if p.is_ps and any(getattr(s, 'local_replication', False)
                               for s in p.all_syncs)}
        self._proxy_cache = {}
        self._proxy_hits = 0
        # PS-resident optimizer (reference partitioner.py:570-573): vars
        # whose strategy asks for service-side updates with shared slots
        self._shared_opt_vars = {
            name for name, p in plan.var_plans.items()
            if p.is_ps and any(getattr(s, 'shared_optimizer', False)
                               for s in p.all_syncs)}
        self._shared_warned = set()
        self._shared_pushes = 0
        # row-sparse PS data plane (BSADD/BGETROWS): sparse-flagged 2-D
        # PS variables whose per-step delta touches few rows ship only
        # those rows. Partitioned sparse vars qualify when partitioned
        # on axis 0 (the axis the builders force for sparse vars).
        self._sparse_vars = {
            name for name, p in plan.var_plans.items()
            if p.is_ps and getattr(p.var, 'sparse_read', False)
            and len(p.var.shape) == 2
            and (p.num_shards <= 1 or p.partition_axis == 0)}
        self._sparse_stats = {
            'sparse_pushes': 0, 'rows_pushed': 0,
            'dense_bytes_avoided': 0, 'zero_push_skips': 0,
            'row_refreshes': 0, 'rows_refreshed': 0,
            'full_refreshes': 0}
        self._sparse_refresh_count = {}
        # loose-mode PS data plane: a persistent TransferPool worker
        # (own connection) per endpoint, variables placed by
        # reduction_destination (multi-server PS)
        self._pool = None
        self._ps_addrs = []
        self._ps_index = {}
        self._ps_bytes = 0
        self._ps_push_bytes = 0
        self._ps_pull_bytes = 0
        self._ps_ep_bytes = []
        self._ps_seconds = 0.0
        # quantized-push error feedback (AUTODIST_PS_WIRE_DTYPE=i8):
        # per-variable host-side residual of the mass the last push's
        # block quantization dropped, added back into the next delta
        # before classification so loose mode stays convergent. Only
        # touched by the push path (pipeline thread at depth 2 —
        # pushes are serialized through the pipeline join). Transient:
        # not checkpointed (worst case one push's quantization error
        # is lost on restart, bounded by a block's scale).
        self._push_residual = {}
        # async pipeline (AUTODIST_PS_PIPELINE_DEPTH >= 2): step N's
        # delta push + publish and step N+1's variable pull run on a
        # dedicated background thread; run() only joins the result.
        # _stats_lock guards the wire accounting those threads share
        # with the main thread.
        import threading
        self._stats_lock = threading.Lock()
        # executed re-plans (AUTODIST_EXECUTE_REPLAN): the background
        # re-rank thread STAGES a migration here; run() applies it at
        # the next step boundary (the only safe point — mid-step state
        # is half old-layout, half new).
        self._replan_lock = threading.Lock()
        self._pending_replan = None
        # epoch-swap handshake (runtime/swap_keys.py, docs/design/
        # epoch-swap.md): _pending_swap holds the staged generation
        # this member validated (and, once armed, the commit boundary
        # every member applies it at); _swap_gen_seen is the last
        # generation this member acked/nacked, _swap_applied_gen the
        # last one it applied. All guarded by _replan_lock.
        self._pending_swap = None
        self._swap_gen_seen = 0
        self._swap_applied_gen = 0
        self._pipe = None
        self._inflight = None
        self._stashed_prefetch = None
        self._pipeline_depth = 1
        # telemetry batch pushes ride their OWN background lane (one
        # TransferPool worker, own fenced connection, created lazily
        # on the first push): a telemetry batch never belongs on the
        # step's critical path — at depth 1 the serial data plane
        # would otherwise pay a full wire round trip per push cadence
        self._tel_pipe = None
        self._tel_push_handle = None
        self._ps_phase = {'pull_s': 0.0, 'push_s': 0.0, 'step_s': 0.0,
                          'exposed_wait_s': 0.0, 'train_steps': 0,
                          'sync_rounds': 0, 'discarded_prefetches': 0}
        # local-SGD window (docs/design/local-sgd.md): H local optimizer
        # steps per PS sync round. H=1 (the plan default) is today's
        # every-step loose push — NONE of the window machinery engages.
        # Under H>1 the staleness gate, the published counters and the
        # pipeline floors all count sync ROUNDS, not train steps;
        # _window_base holds the pulled values the current window's
        # delta is computed against, and _round_count the completed
        # rounds. The merge rule (average vs raw sum) is the
        # AUTODIST_LOCAL_SGD_AVERAGE knob — average scales each
        # worker's window delta by 1/W so the sum-based delta wire
        # lands on the mean of the workers' windows. (_round_count is
        # initialized with _step_count up top: the elastic admit above
        # may already have adopted a published round floor.)
        self._local_steps = max(1, getattr(plan, 'local_steps', 1))
        self._window_base = None
        if self._loose:
            self._init_ps_endpoints()
            depth = ENV.AUTODIST_PS_PIPELINE_DEPTH.val
            if depth > 2:
                logging.warning(
                    'AUTODIST_PS_PIPELINE_DEPTH=%d clamps to 2: a pull '
                    'must follow the previous push of the same variable '
                    '(read-your-writes), so at most one step can be in '
                    'flight', depth)
                depth = 2
            self._pipeline_depth = depth
            if depth > 1:
                from autodist_tpu.runtime import coord_client as cc
                coord_addr = getattr(self._coord, 'address', None)
                # the pipeline thread publishes steps through its OWN
                # control-plane connection (CoordClient sockets are not
                # thread-safe; the main thread keeps using self._coord)
                self._pipe = cc.TransferPool(
                    [lambda: self._fenced_connect(coord_addr)])
        if self._proxy_vars and not self._loose:
            logging.info(
                'local_proxy_variable on %d vars: subsumed by SPMD '
                '(variable reads are device-local in a single program)',
                len(self._proxy_vars))
        # graph-mutation guard (reference autodist.py:152-165): the
        # captured program must not grow after the session is built.
        # VariableRead nodes are excluded: they are framework-internal and
        # created lazily (fetch normalization, jit trace of Variable.read).
        self._built_node_count = self._user_node_count()
        self._init_state()
        # liveness: peers judge us by our beat counter. A background
        # beater decouples it from step cadence — a long XLA compile or
        # an inter-run data-loading phase must not read as death.
        self._hb_seen = {}
        self._rebuild_hb_peers()   # over the LIVE world, not the quorum
        self._hb_stop = None
        hb_timeout = ENV.AUTODIST_HEARTBEAT_TIMEOUT.val
        # armed whenever heartbeats are on, even alone at launch: a
        # 1-process namespace can GROW (live join), and the joiner
        # would judge this process by a beat counter nobody advances
        # between steps — a long XLA recompile would then read as death
        if self._loose and hb_timeout:
            import threading
            self._hb_stop = threading.Event()
            me = self._key(self._worker_name)
            interval = min(hb_timeout / 4.0, 10.0)
            stop = self._hb_stop

            # dial the address the MAIN client resolved (env may carry a
            # NIC address that all-local runs rewrote to loopback)
            coord_addr = getattr(self._coord, 'address', None)

            def beat_loop():
                # own client: CoordClient sockets are not thread-safe.
                # Connection failures are retried forever: a long XLA
                # compile or data stall on OUR side must not permanently
                # silence the beats and get us declared dead by peers.
                # A FENCED rejection is different: we WERE declared dead
                # and superseded — stop beating for good (a zombie must
                # not look alive to anyone).
                from autodist_tpu.runtime.coord_client import \
                    FencedWriteError, connect_with_retry
                client = None
                warned = False
                try:
                    while not stop.is_set():
                        if client is None:
                            try:
                                # SHORT op timeout: a half-open socket
                                # must surface within the heartbeat
                                # window (not the generous data-plane
                                # timeout) so the loop reconnects and
                                # keeps beating
                                client = connect_with_retry(
                                    coord_addr, deadline_s=interval,
                                    op_timeout=min(10.0, interval))
                                if self._fence_key:
                                    client.fence(self._fence_key,
                                                 self._generation)
                            except FencedWriteError:
                                logging.warning(
                                    'heartbeat thread: this worker was '
                                    'declared dead and fenced; beats '
                                    'stop here')
                                break
                            except Exception:  # noqa: BLE001 - advisory
                                if not warned:
                                    warned = True
                                    logging.warning(
                                        'heartbeat thread cannot reach '
                                        'the coord service at %s yet; '
                                        'retrying every %.0fs',
                                        coord_addr, interval)
                                if stop.wait(interval):
                                    break
                                continue
                        try:
                            client.heartbeat(me)
                        except FencedWriteError:
                            logging.warning(
                                'heartbeat thread: this worker was '
                                'declared dead and fenced; beats stop '
                                'here')
                            break
                        except OSError:
                            try:
                                client.close()
                            except OSError:
                                pass
                            client = None
                            continue
                        if stop.wait(interval):
                            break
                finally:
                    if client is not None:
                        try:
                            client.close()
                        except OSError:
                            pass

            self._hb_thread = threading.Thread(
                target=beat_loop, daemon=True, name='autodist-heartbeat')
            self._hb_thread.start()

    def _user_node_count(self):
        return sum(1 for n in self._graph_item.graph.nodes
                   if not isinstance(n, fe.VariableRead))

    def refresh_mutation_guard(self):
        """Re-baseline the mutation guard after a SANCTIONED graph
        extension — a later ``autodist.function`` trace adds nodes
        through the framework itself, which is not the user-mutation
        hazard the guard exists to catch. Optimizer slot state is
        refreshed too: the extension may have traced a train op whose
        optimizer the session had not seen at build time."""
        self._built_node_count = self._user_node_count()
        if self._refresh_opt_state():
            # compiled steps close over the opt-state pytree STRUCTURE;
            # a grown structure invalidates them (they would unzip stale
            # in_specs against the new state)
            self._cache.clear()

    def _refresh_opt_state(self):
        """Init + place optimizer slot state {uid: {var name: leaf
        state}} for any (optimizer, var) pair in the graph not already
        covered. One optimizer may appear in several ApplyGradients
        nodes — the variable sets merge rather than keeping only the
        first node's. Newly seen optimizers start with fresh slots.
        Returns True when anything was added."""
        added = False
        opt_vars = {}   # uid -> (optimizer, {var name: Variable})
        for node in self._graph_item.graph.nodes:
            if isinstance(node, fe.ApplyGradients):
                opt = node.optimizer
                _, seen = opt_vars.setdefault(opt.uid, (opt, {}))
                for _, v in node.grads_and_vars:
                    seen[v.name] = v
        for uid, (opt, seen) in opt_vars.items():
            have = self._opt_state.get(uid, {})
            missing = [v for name, v in seen.items() if name not in have]
            if not missing:
                continue
            host_vals = {v.name: np.asarray(v.init_value)
                         for v in missing}
            slots = opt.init_slot_state(missing, host_vals)
            state = self._opt_state.setdefault(uid, {})
            for vname, leafstate in slots.items():
                state[vname] = self._place_slots(vname, leafstate)
                added = True
        return added

    def _key(self, suffix):
        return '%s/%s' % (self._ns, suffix)

    def peer_step(self, process_id):
        """Another worker's published completed-step counter (0 if none)."""
        return self._coord.incr(self._key('step/') + 'p%d' % process_id, 0)

    def _active_workers(self):
        """Current gate membership size (self-inclusive): the LIVE
        world (launch quorum + admitted joiners) minus peers excluded
        under the ``exclude`` policy — re-evaluated per gate slice, so
        both shrinks and grows reach a blocked waiter mid-wait."""
        return self._world - len(self._excluded)

    def _live_members(self):
        """Worker ordinals currently in the membership (excluded peers
        dropped) — the set gate bounds and pipeline peer floors range
        over."""
        return [i for i in range(self._world)
                if self._key('p%d' % i) not in self._excluded]

    def _snap_round_open(self, client, worker):
        """Flip this worker's snapshot-parity counter
        (``<ns>/snap/<worker>``) to ODD before the sync round's first
        push frame: the serving tier's epoch-consistent snapshot pull
        (serving/replica.py) pins all live writers' parities even,
        pulls, and re-reads — any round open or completed in between
        invalidates the pull. A stale ODD counter left by a crashed
        predecessor of this slot (supervised restart) is normalized
        with a second bump: an open must always END odd or the reader
        contract inverts for the rest of the run."""
        if client.incr(self._key('snap/%s' % worker), 1) & 1 == 0:
            client.incr(self._key('snap/%s' % worker), 1)

    def _snap_round_close(self, client, worker):
        """EVEN after push + publish: the round's deltas are landed and
        counted, so a reader pinning now gets a mutually consistent
        set. Symmetric normalization with :meth:`_snap_round_open`."""
        if client.incr(self._key('snap/%s' % worker), 1) & 1:
            client.incr(self._key('snap/%s' % worker), 1)

    def _rebuild_hb_peers(self):
        me = ENV.AUTODIST_PROCESS_ID.val
        self._hb_peers = [self._key('p%d' % i)
                          for i in range(self._world) if i != me]

    def _refresh_membership(self, adopt_growth=True):
        """Adopt membership changes recorded on the control plane, in
        BOTH directions. Grows: the ``join/world`` counter advanced by
        admitted joiners (each already publishing a step counter and a
        beat before its epoch bump made it observable — see
        :func:`admit_worker`); the heartbeat peer list and, on the
        chief, the strategy re-rank (:meth:`_replan_for_world`) follow.
        Shrinks: per-worker excluded markers (atomic counters), never a
        read-modify-write list, so two survivors excluding two
        different peers concurrently cannot lose each other's update.

        ``adopt_growth=False`` is the FRESH-cohort init call: a reused
        service can hold a crashed previous run's larger counter, and
        no join can legitimately precede this run's rendezvous (admits
        wait for the init-done marker every cohort member's epoch
        baseline is read before), so a fresh member adopting a bigger
        world at init would be adopting phantom members — it starts at
        the launch quorum and learns real growth from epoch bumps.
        Rejoining replacements and live joiners DO adopt at init: the
        world they re-enter may legitimately have grown."""
        world = self._coord.incr(self._key('join/world'), 0)
        if adopt_growth and world > self._world:
            fresh = 0
            for i in range(self._world, world):
                wkey = self._key('p%d' % i)
                if self._coord.incr('excluded/%s' % wkey, 0) > 0:
                    # a slot retired at admit time (a claim raced past
                    # AUTODIST_MAX_WORKERS) or already excluded: it was
                    # never a live join and must not inflate the audit
                    # trail or trigger a re-rank
                    self._excluded.add(wkey)
                    continue
                fresh += 1
                self._health['joins'].append(
                    {'worker': 'p%d' % i, 'epoch': self._epoch_seen})
            if fresh:
                logging.info(
                    'membership grew: %d worker(s) joined at epoch %d '
                    '(world %d -> %d)', fresh, self._epoch_seen,
                    self._world, world)
            self._world = world
            self._rebuild_hb_peers()
            if self._is_chief and fresh:
                # OFF the gate's critical path: this runs inside the
                # staleness gate's failure_check, where a synchronous
                # candidate enumeration would stall the chief's step
                # publishing — and with it every peer blocked on the
                # chief's counter. The re-rank is pure bookkeeping into
                # _health, so it rides a daemon thread; health_stats
                # joins it before reporting.
                import threading
                t = threading.Thread(
                    target=self._replan_for_world, args=(world,),
                    daemon=True, name='autodist-replan')
                # a LIST, not a slot: a second grow while the first
                # re-rank still runs must not orphan it — health_stats
                # joins them all before reporting
                if not hasattr(self, '_replan_threads'):
                    self._replan_threads = []
                self._replan_threads.append(t)
                t.start()
        for i in range(self._world):
            w = 'p%d' % i
            wkey = self._key(w)
            if wkey in self._excluded:
                continue
            if self._coord.incr('excluded/%s' % wkey, 0) > 0:
                self._excluded.add(wkey)
        if self._key(self._worker_name) in self._excluded:
            self._flight.record('self_excluded',
                                worker=self._worker_name,
                                epoch=self._epoch_seen)
            self._flight.dump('self_excluded')
            raise RuntimeError(
                'this worker (%s) was declared dead and excluded from '
                'the run at epoch %d; its writes are fenced — exiting '
                'instead of training into rejected pushes'
                % (self._worker_name, self._epoch_seen))

    def _replan_for_world(self, world):
        """On admit, re-rank strategies for the NEW world size with the
        simulator (``AutoStrategy`` over the grown replica count) and
        record the predicted-vs-kept decision. By default execution
        KEEPS the current plan and this is pure audit trail; with
        ``AUTODIST_EXECUTE_REPLAN`` set, a migratable re-plan (the PS
        family, preserving the current relaxed-consistency flags so
        loose mode stays loose) is additionally STAGED here and applied
        by ``run()`` at the next step boundary through the device-side
        resharding path (:mod:`autodist_tpu.parallel.reshard`). Never
        fatal either way — a re-rank failure must not take down the
        training it advises."""
        entry = {'world': world,
                 'kept': dict(getattr(self._plan.strategy, 'cost', None)
                              or {}).get('builder', ''),
                 'migrated': False}
        try:
            rs = getattr(self._cluster, '_resource_spec', None)
            if rs is None:
                entry['skipped'] = 'no resource spec on the cluster'
            else:
                from autodist_tpu.strategy.builders import AutoStrategy
                # continuous calibration closes the loop here: when the
                # monitor has refit the link constants from live
                # traffic, the re-rank prices with MEASURED, not
                # analytic, alpha-beta — and the audit entry records
                # which constants priced it
                params = None
                if self._monitor is not None:
                    params = self._monitor.calibrated_params()
                entry['cost_constants'] = \
                    'measured' if params is not None else 'analytic'
                if params is not None:
                    a, b = params.link(
                        cross_node=rs.topology.multi_node)
                    entry['cost_alpha_beta'] = {
                        'alpha_s': a, 'beta_s_per_byte': b}
                auto = AutoStrategy(
                    num_replicas=world * max(1, self._plan.local_replicas),
                    cost_params=params)
                best = auto.build(self._graph_item, rs)
                cost = dict(getattr(best, 'cost', None) or {})
                entry['predicted'] = cost.get('builder', '')
                entry['predicted_step_time_s'] = \
                    cost.get('predicted_step_time_s')
                kept_rank = next(
                    (c.report.predicted_step_time_s
                     for c in auto.last_ranked
                     if c.name == entry['kept'] and c.report is not None),
                    None)
                entry['kept_predicted_step_time_s'] = kept_rank
                execute = ENV.AUTODIST_EXECUTE_REPLAN.val and self._loose
                logging.info(
                    're-ranked strategies for world=%d: predicted best '
                    '%s (%.4gs/step), kept %s%s', world,
                    entry['predicted'],
                    entry['predicted_step_time_s'] or float('nan'),
                    entry['kept'] or '(hand-picked)',
                    ' — staging migration through the reshard path'
                    if execute else
                    ' (AUTODIST_EXECUTE_REPLAN off: audit only)')
                if execute:
                    mig = self._build_migratable_strategy(world, rs,
                                                          params=params)
                    if mig is None:
                        entry['migration_skipped'] = \
                            'no PS-family candidate for this strategy'
                    else:
                        entry['migration_staged'] = dict(
                            getattr(mig, 'cost', None) or {}) \
                            .get('builder', '')
                        self._flight.record(
                            'replan_staged', world=world,
                            builder=entry['migration_staged'])
                        # cohort-wide epoch-swap handshake: stage the
                        # plan on the control plane, collect the peer
                        # ack quorum, arm the commit boundary — every
                        # member (chief included) applies at step B
                        # through _apply_pending_swap. Runs on this
                        # re-rank daemon thread; bounded by the
                        # AUTODIST_SWAP_* knobs.
                        self._stage_swap(mig, world, entry)
        except Exception as e:  # noqa: BLE001 - advisory, never fatal
            entry['error'] = '%s: %s' % (type(e).__name__, e)
            logging.warning('strategy re-rank for world=%d failed: %s',
                            world, entry['error'])
        self._health['replans'].append(entry)

    def _build_migratable_strategy(self, world, rs, params=None):
        """Best strategy this LIVE session can actually migrate to: the
        PS family with the current strategy's relaxed-consistency flags
        preserved (sync / staleness / shared_optimizer / proxy), so the
        re-plan stays a loose-mode strategy — switching execution MODE
        (loose <-> SPMD) live would need a new runtime, not a reshard.
        The top-ranked candidate is returned REGARDLESS of data-plane
        geometry: re-keyed shards and moved PS endpoints are legal
        because the epoch-swap handshake (:meth:`_stage_swap`) makes
        every member apply the new plan at the same step boundary and
        the chief re-keys the authoritative PS copies before anyone
        pulls under it. Returns None when the current strategy carries
        no PS sync to clone flags from, or no candidate ranks."""
        from autodist_tpu.simulator import search
        from autodist_tpu.strategy import builders as b
        from autodist_tpu.strategy.base import PSSynchronizer
        flags = None
        for node in self._plan.strategy.node_config:
            for sync in [node.synchronizer] + list(node.part_config):
                if isinstance(sync, PSSynchronizer):
                    flags = {'sync': sync.sync,
                             'staleness': sync.staleness,
                             'shared_optimizer': sync.shared_optimizer,
                             'local_proxy_variable':
                                 sync.local_replication}
                    break
            if flags is not None:
                break
        if flags is None:
            return None
        cands = [
            ('PS', lambda: b.PS(**flags)),
            ('PSLoadBalancing', lambda: b.PSLoadBalancing(**flags)),
            ('PartitionedPS', lambda: b.PartitionedPS(**flags)),
            ('UnevenPartitionedPS',
             lambda: b.UnevenPartitionedPS(**flags)),
        ]
        feasible, _ = search.rank(
            self._graph_item, rs, candidates=cands, params=params,
            num_replicas=world * max(1, self._plan.local_replicas))
        if feasible:
            return feasible[0].strategy
        logging.info(
            'executed re-plan: no PS-family candidate ranked for '
            'world=%d; keeping the current plan', world)
        return None

    def _apply_pending_replan(self):
        with self._replan_lock:
            pending, self._pending_replan = self._pending_replan, None
        if pending is not None:
            self._execute_replan(**pending)

    @staticmethod
    def _ps_geometry(plan, name):
        """Data-plane key layout for one variable under ``plan`` (the
        pure-plan form of :meth:`_shard_info`'s key list)."""
        p = plan.var_plans.get(name)
        nshards = getattr(p, 'num_shards', 1) if p is not None else 1
        if nshards > 1:
            return ['var/%s/shard%d' % (name, i) for i in range(nshards)]
        return ['var/%s' % name]

    # -- epoch-swap handshake (docs/design/epoch-swap.md) ------------------
    # The verified ordering (analysis/epoch_swap_model.py): the chief
    # STAGES plan N+1 under a generation-keyed plan key, every peer
    # validates and ACKs (any NACK cancels the stage), the chief ARMS
    # the commit marker with boundary B = prefix_min(published) +
    # gate_staleness + 2, and every member — chief included — applies
    # the staged plan at the start of step B. The boundary-safety
    # argument: a member executing step s implies every member
    # published >= s - staleness - 1, so at arm time no member has
    # started step B and every member's step-B start check observes
    # the armed marker.

    def _validate_swap_strategy(self, strategy, world):
        """Can THIS member execute ``strategy`` live? Compiles it and
        builds its :class:`ExecutionPlan` over this member's mesh (the
        same construction :meth:`_execute_replan` performs at apply
        time, so an apply-time failure is caught here, at ack time,
        where a NACK still cancels the swap cleanly). Raises on any
        plan this member would have to refuse."""
        from autodist_tpu.parallel.plan import ExecutionPlan
        from autodist_tpu.strategy.base import StrategyCompiler
        compiled = StrategyCompiler(self._graph_item).prune(strategy)
        new_plan = ExecutionPlan(
            compiled, self._graph_item, self._mesh,
            loose=self._loose, topology=self._plan.topology)
        # weight-update-sharded optimizer slots live as FLAT 1/n
        # shards; a plan flipping any variable's update-sharding needs
        # a slot-layout conversion the reshard pass (which moves
        # var-SHAPED leaves) does not perform — NACK at validation so
        # no member ever reaches a refusal after the boundary is armed
        # (PS-family candidates never set update-sharding, so this
        # only rejects hand-staged exotic plans)
        wus_moved = [
            name for name in self._graph_item.graph.variables
            if getattr(self._plan.var_plans.get(name),
                       'update_sharded', False) !=
            getattr(new_plan.var_plans.get(name),
                    'update_sharded', False)]
        if wus_moved:
            raise RuntimeError(
                'weight-update-sharding layout changes for %s — flat '
                'slot shards need their own conversion pass'
                % sorted(wus_moved)[:4])
        return compiled, new_plan

    def _live_ack_peers(self, client):
        """The peers whose ACK the staged plan needs RIGHT NOW: live
        membership (re-evaluated on every poll, so an exclusion mid-
        handshake shrinks the quorum) minus this worker, minus peers
        that closed cleanly (done marker / released step sentinel —
        a finished peer never pulls again and needs no say)."""
        from autodist_tpu.runtime.coord_client import CLEAN_CLOSE_STEP
        me = ENV.AUTODIST_PROCESS_ID.val
        out = []
        for i in self._live_members():
            if i == me:
                continue
            w = 'p%d' % i
            if client.get('done/%s' % self._key(w)) is not None:
                continue
            if client.incr(self._key('step/') + w, 0) >= \
                    CLEAN_CLOSE_STEP:
                continue
            out.append(i)
        return out

    def request_strategy_swap(self, strategy, world=None):
        """Public trigger for a cohort-wide strategy migration: runs
        the epoch-swap handshake for ``strategy`` on a background
        thread and returns the audit entry (mutated as the handshake
        progresses; ``entry['swap']`` appears once the boundary is
        armed). The swap itself lands when every member's step counter
        reaches the armed boundary. Loose mode only."""
        if not self._loose:
            raise RuntimeError('strategy swap requires loose mode')
        import threading
        world = world if world is not None else self._world
        entry = {'world': world,
                 'kept': dict(getattr(self._plan.strategy, 'cost',
                                      None) or {}).get('builder', ''),
                 'migrated': False, 'requested': True}
        self._health['replans'].append(entry)
        t = threading.Thread(
            target=self._stage_swap, args=(strategy, world, entry),
            daemon=True, name='autodist-swap-stage')
        if not hasattr(self, '_replan_threads'):
            self._replan_threads = []
        self._replan_threads.append(t)
        t.start()
        return entry

    def _stage_swap(self, strategy, world, entry):
        """Chief half of the epoch-swap handshake: stage -> collect the
        ack quorum over LIVE membership -> arm the commit boundary.
        Any NACK or an ack timeout cancels the stage (generation keys
        deleted) and retries with backoff, bounded by
        ``AUTODIST_SWAP_MAX_RETRIES``; exhausting the retries degrades
        to an audit-only entry. Runs on a background thread with its
        own fenced control-plane connection. Never fatal."""
        import time as _time

        from autodist_tpu.runtime import swap_keys
        from autodist_tpu.runtime.coord_client import CLEAN_CLOSE_STEP
        ack_timeout = ENV.AUTODIST_SWAP_ACK_TIMEOUT_S.val
        backoff = ENV.AUTODIST_SWAP_RETRY_BACKOFF_S.val
        max_retries = ENV.AUTODIST_SWAP_MAX_RETRIES.val
        builder = dict(getattr(strategy, 'cost', None)
                       or {}).get('builder', '')
        client = None
        try:
            # the staged plan must be executable HERE too: a chief
            # that arms a plan it later refuses would fork the cohort
            self._validate_swap_strategy(strategy, world)
            # own connection: this thread runs beside the main step
            # loop and CoordClient sockets are not thread-safe
            client = self._fenced_connect(
                getattr(self._coord, 'address', None))
            for attempt in range(max_retries + 1):
                gen = swap_keys.current_gen(client, self._ns) + 1
                swap_keys.stage_plan(client, self._ns, gen, world,
                                     strategy)
                self._flight.record('swap_stage', gen=gen, world=world,
                                    builder=builder)
                logging.info(
                    'epoch swap gen %d staged for world=%d (%s); '
                    'waiting for the peer ack quorum', gen, world,
                    builder or 'hand-staged')
                deadline = _time.time() + ack_timeout
                quorum, nacks = False, {}
                while _time.time() < deadline:
                    peers = self._live_ack_peers(client)
                    acked, nacks = swap_keys.read_acks(
                        client, self._ns, gen, peers)
                    if nacks:
                        break
                    if len(acked) == len(peers):
                        quorum = True
                        break
                    _time.sleep(0.05)
                if not quorum:
                    reason = 'nack' if nacks else 'ack_timeout'
                    swap_keys.cancel(client, self._ns, gen)
                    self._flight.record(
                        'swap_cancel', gen=gen, reason=reason,
                        detail=str(sorted(nacks.items()))[:256])
                    entry.setdefault('swap_cancels', []).append(
                        {'gen': gen, 'reason': reason,
                         'nacks': {('p%d' % w): r
                                   for w, r in nacks.items()}})
                    logging.warning(
                        'epoch swap gen %d cancelled (%s%s)%s', gen,
                        reason, ': %s' % nacks if nacks else '',
                        '; retrying after %.1fs' % backoff
                        if attempt < max_retries else '')
                    if attempt < max_retries:
                        _time.sleep(backoff)
                        continue
                    entry['migration_skipped'] = (
                        'epoch-swap handshake failed after %d '
                        'attempt(s): %s' % (attempt + 1, reason))
                    return
                # quorum complete: arm. Boundary floors are the LIVE
                # members' published counters (sync ROUNDS under a
                # local-SGD window — the same unit the gate and the
                # apply check use); released sentinels are skipped.
                floors = []
                for i in self._live_members():
                    f = client.incr(self._key('step/') + 'p%d' % i, 0)
                    if f < CLEAN_CLOSE_STEP:
                        floors.append(f)
                if not floors:
                    floors = [self._step_count
                              if self._local_steps == 1
                              else self._round_count]
                boundary = swap_keys.compute_boundary(
                    floors, self._plan.gate_staleness)
                swap_keys.arm(client, self._ns, gen, boundary)
                self._flight.record('swap_arm', gen=gen,
                                    boundary=boundary,
                                    floor=min(floors))
                with self._replan_lock:
                    self._pending_swap = {
                        'gen': gen, 'strategy': strategy,
                        'world': world, 'boundary': boundary,
                        'entry': entry}
                entry['swap'] = {'gen': gen, 'boundary': boundary,
                                 'attempts': attempt + 1}
                logging.info(
                    'epoch swap gen %d armed: boundary step %d '
                    '(floor %d + staleness %d + 2)', gen, boundary,
                    min(floors), self._plan.gate_staleness)
                return
        except Exception as e:  # noqa: BLE001 - advisory, never fatal
            entry['migration_skipped'] = \
                'epoch-swap staging failed: %s: %s' \
                % (type(e).__name__, e)
            logging.warning('epoch-swap staging for world=%d failed: '
                            '%s', world, entry['migration_skipped'])
        finally:
            if client is not None:
                client.close()

    def _poll_swap_stage(self):
        """Member half of the handshake, piggybacked on the staleness
        gate's failure check and on every step start: discover a newly
        staged generation (validate + ACK, or NACK), and pick up the
        armed boundary. One counter read on the fast path; never
        raises (a control-plane hiccup here must not fail the gate
        slice it rides on)."""
        if not getattr(self, '_loose', False) \
                or getattr(self, '_coord', None) is None \
                or not ENV.AUTODIST_EXECUTE_REPLAN.val:
            return
        from autodist_tpu.runtime import swap_keys
        try:
            gen = swap_keys.current_gen(self._coord, self._ns)
            if gen <= 0:
                return
            with self._replan_lock:
                pending = self._pending_swap
                if pending is not None and pending['gen'] < gen:
                    # superseded: the chief cancelled this generation
                    # and re-staged — the new one is validated below
                    self._pending_swap = pending = None
            if not self._is_chief and gen > self._swap_gen_seen and \
                    gen > self._swap_applied_gen:
                self._swap_gen_seen = gen
                staged = swap_keys.read_plan(self._coord, self._ns,
                                             gen)
                if staged is None:
                    return   # cancelled between counter and plan read
                _, world, strategy = staged
                me = ENV.AUTODIST_PROCESS_ID.val
                try:
                    self._validate_swap_strategy(strategy, world)
                except Exception as e:  # noqa: BLE001 - NACK carries it
                    reason = '%s: %s' % (type(e).__name__, e)
                    swap_keys.write_nack(self._coord, self._ns, gen,
                                         me, reason)
                    self._flight.record('swap_nack', gen=gen,
                                        worker=self._worker_name,
                                        reason=reason[:256])
                    logging.warning(
                        'epoch swap gen %d NACKed: %s', gen, reason)
                    return
                swap_keys.write_ack(self._coord, self._ns, gen, me)
                self._flight.record('swap_ack', gen=gen,
                                    worker=self._worker_name)
                with self._replan_lock:
                    self._pending_swap = pending = {
                        'gen': gen, 'strategy': strategy,
                        'world': world, 'boundary': 0, 'entry': None}
            if pending is not None and not pending['boundary']:
                b = swap_keys.read_boundary(self._coord, self._ns,
                                            pending['gen'])
                if b:
                    with self._replan_lock:
                        pending['boundary'] = b
        except Exception as e:  # noqa: BLE001 - poll must not fail
            logging.debug('epoch-swap poll failed: %s: %s',
                          type(e).__name__, e)

    def _apply_pending_swap(self):
        """Apply an armed epoch swap at the start of step B (sync
        round B under a local-SGD window). Called before anything
        touches the plan on every run; a member whose counter resumed
        PAST the boundary (supervised restart) applies on its first
        run — the chief's re-keyed PS copies are the authoritative
        state either way."""
        with self._replan_lock:
            pending = self._pending_swap
            if pending is None or not pending.get('boundary'):
                return
            h = self._local_steps
            nxt = self._step_count + 1 if h == 1 \
                else self._round_count + 1
            if nxt < pending['boundary'] or \
                    (h > 1 and self._step_count % h != 0):
                return
            self._pending_swap = None
        entry = pending.get('entry')
        if entry is None:
            # non-chief members audit the swap too (the chief's entry
            # came from its re-rank / request)
            entry = {'world': pending['world'],
                     'kept': dict(getattr(self._plan.strategy, 'cost',
                                          None) or {})
                     .get('builder', ''),
                     'migrated': False,
                     'swap': {'gen': pending['gen'],
                              'boundary': pending['boundary']}}
            self._health['replans'].append(entry)
        self._execute_replan(pending['strategy'], pending['world'],
                             entry, swap=pending)

    def _execute_replan(self, strategy, world, entry, swap=None):
        """Migrate this session's live state to a re-ranked strategy —
        the execution half of the elastic re-plan (ROADMAP item 3's
        resharding unlock). At a step boundary, atomically:

        1. build the new :class:`ExecutionPlan` over the SAME mesh;
        2. move ``_var_state`` (and every optimizer slot shaped like
           its variable) old-layout -> new-layout ON DEVICE through
           :mod:`autodist_tpu.parallel.reshard` — values are moved,
           never recomputed, so the migration is bit-exact;
        3. re-init compressor aux state whose contract changed
           (carrying entries whose compressor kept shape+keys);
        4. swap the plan and drop compiled steps.

        Without ``swap`` (legacy chief-local call) the shared data
        plane is UNTOUCHED: a migration that would change any
        variable's shard-key geometry or move it between PS endpoints
        is REFUSED (recorded as ``migration_skipped``) — live peers
        would keep using the old keys.

        With ``swap`` (an ARMED epoch-swap record: every member
        applies this plan at the same step boundary) re-keying is
        LEGAL: the chief additionally copies the authoritative PS
        values of every re-keyed variable old-keys -> new-keys (BSET
        resets the per-key accumulator state; old keys become inert —
        a mid-swap zombie's old-plan pushes land where nobody reads,
        on top of its generation fence) and publishes a ready marker
        non-chief members wait on before their first new-plan pull.
        Every member wraps the apply in a snapshot-parity open/close
        (:meth:`_snap_round_open`), so a serving replica's snapshot
        pull straddling the migration can never revalidate.

        Never fatal without ``swap``: everything fallible runs BEFORE
        the swap and the new state is built entirely on the side, so
        any failure keeps the old plan + state untouched and records
        the error on the replan audit entry. With ``swap`` a failure
        AFTER the boundary was armed re-raises instead: other members
        are applying the plan this member just failed, and training on
        silently against the old keys would fork the model.
        """
        import time as _time
        t0 = _time.perf_counter()
        old_plan = self._plan
        try:
            from autodist_tpu.parallel import reshard as reshard_mod
            from autodist_tpu.parallel.plan import ExecutionPlan
            from autodist_tpu.strategy.base import StrategyCompiler
            compiled = StrategyCompiler(self._graph_item).prune(strategy)
            new_plan = ExecutionPlan(
                compiled, self._graph_item, self._mesh,
                loose=self._loose, topology=old_plan.topology)
            # a mid-flight background push/pull rides the OLD plan's
            # placement: join it first, discard its prefetch
            if self._pipe is not None:
                pre = self._join_pipeline()
                if pre is not None:
                    self._account_prefetch_discard(pre)
            variables = list(self._graph_item.graph.variables)
            # without an armed epoch swap a re-keying migration must
            # NEVER execute — live peers would keep using the old keys
            moved_geom = [
                name for name in variables
                if self._ps_geometry(old_plan, name) !=
                self._ps_geometry(new_plan, name)] if self._loose else []
            if moved_geom and swap is None:
                entry['migration_skipped'] = (
                    'shard geometry changes for %s — re-keying a live '
                    'data plane needs cohort-wide propagation'
                    % sorted(moved_geom)[:4])
                logging.warning(
                    'executed re-plan for world=%d refused: %s', world,
                    entry['migration_skipped'])
                self._flight.record('replan_refused', world=world,
                                    reason='shard_geometry')
                self._flight.dump('replan_refusal')
                return
            # weight-update-sharded slots live as FLAT 1/n shards; a
            # plan change that flips any variable's update-sharding
            # would need a slot-layout conversion the reshard pass
            # (which moves var-SHAPED leaves) does not perform — refuse
            # rather than silently carry a mislaid slot layout
            wus_moved = [
                name for name in variables
                if getattr(old_plan.var_plans.get(name),
                           'update_sharded', False) !=
                getattr(new_plan.var_plans.get(name),
                        'update_sharded', False)]
            if wus_moved:
                entry['migration_skipped'] = (
                    'weight-update-sharding layout changes for %s — '
                    'flat slot shards need their own conversion pass'
                    % sorted(wus_moved)[:4])
                logging.warning(
                    'executed re-plan for world=%d refused: %s', world,
                    entry['migration_skipped'])
                self._flight.record('replan_refused', world=world,
                                    reason='weight_update_sharding')
                self._flight.dump('replan_refusal')
                return
            # device-side layout moves: vars + matching optimizer slots
            ops = reshard_mod.plan_reshard(old_plan, new_plan)
            fns = {op.var_name:
                   reshard_mod.reshard_fn(op, old_plan, new_plan)
                   for op in ops}
            new_vars = {
                name: fns[name](arr) if name in fns else arr
                for name, arr in self._var_state.items()}
            new_opt = {}
            for uid, by_var in self._opt_state.items():
                new_by_var = {}
                for vname, leafstate in by_var.items():
                    fn = fns.get(vname)
                    phys = old_plan.padded_shape(vname)

                    def move(leaf, fn=fn, phys=phys):
                        if fn is not None and phys is not None and \
                                hasattr(leaf, 'shape') and \
                                tuple(leaf.shape) == tuple(phys):
                            return fn(leaf)
                        return leaf
                    new_by_var[vname] = jax.tree.map(move, leafstate)
                new_opt[uid] = new_by_var
            # compressor aux state: carry entries whose contract
            # (keys + per-replica shapes) is unchanged, re-init the
            # rest — at worst one step of error feedback resets, the
            # same bound as a worker restart
            n = new_plan.num_replicas
            rep_sharding = NamedSharding(self._mesh, P(AXIS_DATA))
            new_aux = {}
            for name, vplan in new_plan.var_plans.items():
                aux = vplan.compressor.init_state(
                    np.asarray(vplan.var.init_value))
                if not aux:
                    continue
                key = 'compressor/%s' % name
                old = self._aux_state.get(key)
                if old is not None and set(old) == set(aux) and all(
                        tuple(old[k].shape[1:]) == tuple(v.shape)
                        for k, v in aux.items()):
                    new_aux[key] = old
                else:
                    new_aux[key] = {
                        k: self._put(
                            jnp.broadcast_to(jnp.asarray(v),
                                             (n,) + tuple(v.shape)),
                            rep_sharding)
                        for k, v in aux.items()}
            # new endpoint placement is computed on the side too; an
            # index that MOVES any live variable between endpoints
            # aborts like a geometry change would (peers keep dialing
            # the old endpoints) — unless an armed epoch swap makes
            # every member adopt the new placement at the boundary
            new_ps_index = self._ps_index
            moved_eps = []
            if self._loose:
                from autodist_tpu.runtime import coord_client as cc
                eps = cc.ps_endpoints()
                if eps:
                    new_ps_index = assign_ps_endpoints(
                        new_plan.var_plans, eps)
                    moved_eps = [
                        name for name in variables
                        if self._ps_index.get(name) is not None
                        and new_ps_index.get(name) !=
                        self._ps_index.get(name)]
                    if moved_eps and swap is None:
                        entry['migration_skipped'] = (
                            'endpoint placement moves for %s — '
                            'needs cohort-wide propagation'
                            % sorted(moved_eps)[:4])
                        logging.warning(
                            'executed re-plan for world=%d refused: '
                            '%s', world, entry['migration_skipped'])
                        self._flight.record(
                            'replan_refused', world=world,
                            reason='endpoint_placement')
                        self._flight.dump('replan_refusal')
                        return
            # ---- swap (everything above built on the side) ----
            # epoch swap: the data-plane re-key brackets the plan swap
            # in a snapshot-parity open/close — a serving replica's
            # epoch-consistent pull straddling the migration pins an
            # odd (or advanced) parity and can never revalidate a
            # snapshot that mixes old- and new-key reads
            rekeyed = sorted(set(moved_geom) | set(moved_eps)) \
                if swap is not None else []
            auth = {}
            if swap is not None and self._loose:
                self._snap_round_open(self._coord, self._worker_name)
            if rekeyed and self._is_chief and self._loose:
                # authoritative PS values under the OLD keys (the PS
                # copy, not this worker's possibly-stale local state,
                # is the model) — fetched before the plan swap flips
                # _shard_info to the new layout
                parts, _ = self._fetch_var_parts(rekeyed)
                for name in rekeyed:
                    pc, _keys = self._shard_info(name)
                    got = parts.get(name, [None])
                    if any(p is None for p in got):
                        # never stored (init-barrier window): the local
                        # device copy is the best value in existence
                        auth[name] = np.asarray(self._plan.unpad_host(
                            name, np.asarray(self._var_state[name])))
                    else:
                        auth[name] = got[0] if pc is None \
                            else pc.merge(got)
            self._plan = new_plan
            self._var_state = new_vars
            self._opt_state = new_opt
            self._aux_state = new_aux
            self._cache.clear()
            self._proxy_cache = {}
            self._proxy_vars = {
                name for name, p in new_plan.var_plans.items()
                if p.is_ps and any(getattr(s, 'local_replication', False)
                                   for s in p.all_syncs)}
            self._shared_opt_vars = {
                name for name, p in new_plan.var_plans.items()
                if p.is_ps and any(getattr(s, 'shared_optimizer', False)
                                   for s in p.all_syncs)}
            self._sparse_vars = {
                name for name, p in new_plan.var_plans.items()
                if p.is_ps and getattr(p.var, 'sparse_read', False)
                and len(p.var.shape) == 2
                and (p.num_shards <= 1 or p.partition_axis == 0)}
            self._ps_index = new_ps_index
            if swap is not None and self._loose:
                from autodist_tpu.runtime import swap_keys
                try:
                    if self._is_chief:
                        if auth:
                            # re-key: authoritative values land under
                            # the NEW plan's keys (BSET resets each
                            # key's accumulator/slot state wholesale);
                            # the old keys become inert — nobody reads
                            # them, zombie old-plan pushes land there
                            # harmlessly, and the run-end purge sweeps
                            # them
                            self._store_var_parts(auth)
                        swap_keys.mark_ready(self._coord, self._ns,
                                             swap['gen'])
                    elif rekeyed:
                        # the chief may reach its own boundary later
                        # than us: our first new-plan pull must not
                        # race the re-key
                        swap_keys.wait_ready(
                            self._coord, self._ns, swap['gen'],
                            ENV.AUTODIST_SWAP_ACK_TIMEOUT_S.val)
                finally:
                    self._snap_round_close(self._coord,
                                           self._worker_name)
                self._swap_applied_gen = swap['gen']
                self._flight.record(
                    'swap_apply', gen=swap['gen'],
                    worker=self._worker_name,
                    boundary=swap['boundary'],
                    step=self._step_count + 1
                    if self._local_steps == 1
                    else self._round_count + 1)
            entry['migrated'] = True
            entry['migration'] = {
                'world': world,
                'builder': dict(getattr(strategy, 'cost', None)
                                or {}).get('builder', ''),
                'strategy_id': compiled.id,
                'reshard': reshard_mod.summarize(ops),
                'rekeyed_vars': len(rekeyed),
                # bytes the re-key pushed to the NEW PS keys (the
                # authoritative-copy BSETs) — the reshard summary only
                # counts device-collective wire bytes, which are 0 for
                # a single-host re-partition
                'rekey_ps_bytes': int(sum(
                    np.asarray(v).nbytes for v in auth.values())),
                'wall_s': round(_time.perf_counter() - t0, 4)}
            self._flight.record(
                'replan_swap', world=world,
                builder=entry['migration']['builder'],
                wall_s=entry['migration']['wall_s'])
            self._tel.record_span(
                'replan_swap', t0, _time.perf_counter() - t0,
                world=world, worker=self._worker_name)
            logging.info(
                'executed re-plan for world=%d: migrated to %s in '
                '%.3fs (%s); compiled steps dropped, state moved '
                'device-side', world,
                entry['migration']['builder'] or compiled.id,
                entry['migration']['wall_s'],
                entry['migration']['reshard'])
        except Exception as e:  # noqa: BLE001 - keep the old plan
            entry['migration_error'] = '%s: %s' % (type(e).__name__, e)
            self._plan = old_plan
            logging.warning(
                'executed re-plan for world=%d failed (%s); keeping '
                'the current plan', world, entry['migration_error'])
            self._flight.record('replan_failed', world=world,
                                error=entry['migration_error'])
            self._flight.dump('replan_failure')
            if swap is not None:
                # past an armed boundary the cohort is committed: the
                # other members are applying the plan this member just
                # failed — training on against the old keys would fork
                # the model silently. Fail fast instead.
                raise

    def _exclude_peer(self, wkey, timeout):
        """Epoch-fenced exclusion of a dead peer. Every detector fences
        the zombie's writer generation FIRST — on every service it can
        write to (each PS endpoint keeps its own fence counter) —
        BEFORE the exclusion becomes observable anywhere: the moment
        any process can see the marker, the zombie's writes must
        already be rejected. Fencing is idempotent (any bump past the
        bound generation fences; concurrent detectors just bump
        further). Then exactly one survivor wins the atomic claim and
        re-bounds the membership: it releases the dead worker's step
        counter with the same ``1 << 30`` sentinel a clean close
        publishes (deleting the key instead would let any later
        delta-0 read resurrect it at zero and wedge every survivor's
        gate forever) and bumps the membership epoch so every other
        survivor adopts the shrunk quorum on its next liveness check.
        The fence/excluded counters live OUTSIDE the run namespace
        (``fence/<ns>/<w>``, ``excluded/<ns>/<w>``): they survive the
        run-end purge, so a zombie stays fenced — and its exclusion
        stays observable — after the survivors are gone."""
        w = wkey.rsplit('/', 1)[-1]
        if self._active_workers() - 1 < self._min_workers:
            raise RuntimeError(
                'worker %s missed heartbeats for > %.0fs but excluding '
                'it would leave %d live workers, below '
                'AUTODIST_MIN_WORKERS=%d — failing instead of shrinking'
                % (w, timeout, self._active_workers() - 1,
                   self._min_workers))
        fkey = 'fence/%s' % wkey
        self._pool.run([(ep, lambda c, k=fkey: c.incr(k, 1))
                        for ep in range(len(self._pool))])
        coord_addr = tuple(getattr(self._coord, 'address', ()) or ())
        if coord_addr not in [tuple(a) for a in self._ps_addrs]:
            self._coord.incr(fkey, 1)
        self._flight.record('fence_bump', worker=w,
                            by=self._worker_name)
        claim = self._coord.incr('excluded/%s' % wkey, 1)
        self._flight.record('exclude_claim', worker=w, claim=claim,
                            by=self._worker_name)
        if claim == 1:
            from autodist_tpu.runtime.coord_client import CLEAN_CLOSE_STEP
            self._coord.publish_step(w, CLEAN_CLOSE_STEP,
                                     prefix=self._key('step/'))
            self._flight.record('release', worker=w,
                                by=self._worker_name)
            self._epoch_seen = self._coord.incr(self._key('epoch'), 1)
            self._flight.record('epoch_bump', epoch=self._epoch_seen,
                                by=self._worker_name)
            self._health['epoch_bumps'] += 1
            logging.warning(
                'declared peer %s dead (no heartbeat for > %.0fs): '
                'generation fenced, excluded from membership — epoch '
                '%d, %d active workers remain', w, timeout,
                self._epoch_seen, self._active_workers() - 1)
        else:
            # another survivor won the claim; adopt its epoch
            self._epoch_seen = self._coord.incr(self._key('epoch'), 0)
        self._excluded.add(wkey)
        self._health['exclusions'].append(
            {'worker': w, 'epoch': self._epoch_seen})
        # an exclusion means somebody died — exactly when the last
        # N control-plane events are worth keeping
        self._flight.dump('exclusion:%s' % w)

    def _check_peers_alive(self):
        """Liveness + recovery policy while blocked on the staleness
        gate (reference coordinator.py:98-110 monitors hard-exit the
        chief when a worker dies; here the signal is a stalled
        coord-service beat counter, judged on this process's own clock
        — immune to cross-host clock skew). Under the default ``fail``
        policy a dead peer raises; ``exclude`` shrinks the membership
        (epoch bump + generation fencing); ``restart`` keeps waiting
        for the coordinator-supervised replacement."""
        import time as _time
        # adopt membership changes FIRST — exclusions other survivors
        # fenced in AND joins (the epoch bump is how an admitted worker
        # becomes visible). This runs even with heartbeats disabled:
        # the gate's party count must grow for a join regardless of
        # whether failure DETECTION is armed.
        epoch = self._coord.incr(self._key('epoch'), 0)
        if epoch != self._epoch_seen:
            self._health['epoch_bumps'] += epoch - self._epoch_seen
            self._epoch_seen = epoch
            self._refresh_membership()
            self._flight.record('epoch_adopt', epoch=epoch,
                                worker=self._worker_name)
            logging.warning('membership epoch advanced to %d: %d '
                            'active workers', epoch,
                            self._active_workers())
        # the epoch-swap handshake piggybacks on the gate poll: a
        # member blocked here for a whole staleness window still
        # discovers (and acks) a staged plan and picks up the armed
        # boundary without waiting for its next step start
        self._poll_swap_stage()
        timeout = ENV.AUTODIST_HEARTBEAT_TIMEOUT.val
        if not timeout:
            return
        # belt and braces alongside the background beater: a waiter is
        # trivially alive, refresh our beat on every gate slice too
        self._coord.heartbeat(self._key(self._worker_name))
        peers = [w for w in self._hb_peers if w not in self._excluded]
        dead = self._coord.dead_workers(peers, timeout, self._hb_seen)
        if dead:
            # a peer that closed its session cleanly stops beating but
            # is NOT a crash: it published a done key (Session.close)
            dead = [w for w in dead
                    if self._coord.get('done/%s' % w) is None]
        # restart policy: a peer beating again after a declared death
        # is its reborn incarnation — record the recovery wall time
        for w in list(self._dead_since):
            if w not in dead:
                wall = _time.time() - self._dead_since.pop(w)
                self._health['rejoins'].append(w.rsplit('/', 1)[-1])
                self._health['recovery_wall_s'].append(round(wall, 3))
                logging.info('peer %s is heartbeating again %.1fs '
                             'after its death was detected', w, wall)
        if not dead:
            return
        self._health['missed_beats'] += \
            sum(1 for w in dead if w not in self._dead_since)
        if self._policy == 'exclude':
            for w in dead:
                self._exclude_peer(w, timeout)
            return
        if self._policy == 'restart':
            now = _time.time()
            wait_cap = ENV.AUTODIST_RESTART_WAIT_S.val
            for w in dead:
                short = w.rsplit('/', 1)[-1]
                if self._coord.get(
                        self._key('failed/%s' % short)) is not None:
                    raise RuntimeError(
                        'worker %s exhausted its supervised restarts '
                        '(AUTODIST_MAX_WORKER_RESTARTS) and was marked '
                        'permanently failed — aborting' % short)
                if w not in self._dead_since:
                    self._dead_since[w] = now
                    logging.warning(
                        'peer %s missed heartbeats for > %.0fs; '
                        'policy=restart: waiting for its supervised '
                        'replacement', w, timeout)
                elif now - self._dead_since[w] > wait_cap:
                    # backstop for a silently dead supervisor: the
                    # normal abort is the failed marker above
                    raise RuntimeError(
                        'worker %s has been dead for %.0fs with no '
                        'supervised replacement and no failed marker '
                        '(AUTODIST_RESTART_WAIT_S=%.0f) — aborting'
                        % (short, now - self._dead_since[w], wait_cap))
            # truthy = recovery in flight: the staleness gate re-arms
            # its window instead of timing out under the supervisor
            return True
        raise RuntimeError(
            'worker(s) %s missed heartbeats for > %.0fs while this '
            'process waited on the staleness gate — failing fast '
            'instead of hanging' % (sorted(dead), timeout))

    # -- loose-mode PS endpoint placement ----------------------------------
    def _init_ps_endpoints(self):
        """Bring up the PS data plane: a persistent
        :class:`~autodist_tpu.runtime.coord_client.TransferPool` worker
        (own connection, lazily dialed) per endpoint. With
        ``AUTODIST_PS_ENDPOINTS`` set, each variable is served by the
        endpoint its strategy ``reduction_destination`` maps to — host
        match first (endpoints co-located with PS nodes), else the
        destination's ordinal among the distinct destinations — so
        PSLoadBalancing's byte-size bin-packing (reference
        ps_lb_strategy.py:64-83) decides real runtime placement, like
        the reference's one tf.Server per PS node
        (utils/server_starter.py:48-75). Without endpoints, all
        variables live on the coord service (single-PS layout; the pool
        worker dials its own connection so background transfers never
        contend with the main thread's control-plane client)."""
        from autodist_tpu.runtime import coord_client as cc
        from autodist_tpu.runtime.cluster import is_local_address
        eps = cc.ps_endpoints()
        if eps:
            # a locally-hosted endpoint may be bound to loopback
            # (all-local runs); dialing 127.0.0.1 works under either
            # bind, while the raw NIC address fails against a loopback
            # bind — same rewrite the coord-service connection applies
            # (autodist.py)
            self._ps_addrs = [
                ('127.0.0.1' if is_local_address(host) else host, port)
                for host, port in eps]
            self._ps_index = assign_ps_endpoints(self._plan.var_plans,
                                                 eps)
            counts = [0] * len(eps)
            for idxs in self._ps_index.values():
                for i in idxs:
                    counts[i] += 1
            logging.info('PS data plane: %d endpoints, variable shards '
                         'per endpoint %s', len(eps), counts)
        else:
            self._ps_addrs = [tuple(getattr(self._coord, 'address',
                                            (None, 0)))]
        self._pool = cc.TransferPool(
            [lambda addr=addr: self._fenced_connect(addr)
             for addr in self._ps_addrs])

    def _fenced_connect(self, addr):
        """Dial a data/control-plane connection bound to this worker's
        fencing generation: every write it carries is rejected by the
        service once we are declared dead and superseded."""
        from autodist_tpu.runtime import coord_client as cc
        client = cc.connect_with_retry(addr)
        if self._fence_key:
            client.fence(self._fence_key, self._generation)
        return client

    @staticmethod
    def _stable_idx(name, n):
        import zlib
        return zlib.crc32(name.encode()) % n

    def _shard_info(self, name):
        """Loose-mode transfer geometry for a variable: its
        :class:`PartitionerConfig` (None when unpartitioned) and the
        per-shard key suffixes. Partitioned variables live as one
        tensor PER SHARD on the data plane (``var/<name>/shard<i>``) so
        each shard lands on the endpoint its ``part_config`` destination
        names (reference partitioned_ps_strategy.py:89-96 + per-shard
        variables, kernel/partitioner.py:153-173)."""
        p = self._plan.var_plans.get(name)
        nshards = getattr(p, 'num_shards', 1) if p is not None else 1
        if nshards > 1:
            return (p.part_config,
                    ['var/%s/shard%d' % (name, i) for i in range(nshards)])
        return None, ['var/%s' % name]

    def _shard_endpoints(self, name, nshards):
        """Endpoint index per shard (extended if the strategy named
        fewer destinations than shards)."""
        idxs = self._ps_index.get(name)
        if idxs is None:
            idxs = [self._stable_idx(name, len(self._ps_addrs))]
            self._ps_index[name] = idxs
        if len(idxs) < nshards:
            idxs = [idxs[i % len(idxs)] for i in range(nshards)]
        return idxs

    def _transfer_groups(self, names):
        """Group every (variable, shard) transfer unit by the endpoint
        it lives on: ``{endpoint: [(key_suffix, name, shard_i,
        part_config)]}`` plus the per-name shard counts."""
        groups = {}
        shard_counts = {}
        for name in names:
            pc, keys = self._shard_info(name)
            idxs = self._shard_endpoints(name, len(keys))
            shard_counts[name] = len(keys)
            for i, (key, ep) in enumerate(zip(keys, idxs)):
                groups.setdefault(ep, []).append((key, name, i, pc))
        return groups, shard_counts

    def _account_ep_bytes(self, name):
        """Attribute one whole-tensor transfer's wire bytes to the
        endpoints its shards live on (per-endpoint load accounting).
        Caller must hold ``_stats_lock`` (pipeline threads and the main
        thread both account)."""
        if not self._ps_ep_bytes:
            self._ps_ep_bytes = [0] * len(self._ps_addrs)
        var = self._graph_item.var_by_name(name)
        pc, keys = self._shard_info(name)
        idxs = self._shard_endpoints(name, len(keys))
        if pc is None:
            sizes = [int(np.prod(var.shape)) if var.shape else 1]
        else:
            sizes = [int(np.prod(s)) for s in
                     pc.shard_shapes(var.shape)]
        for ep, n in zip(idxs, sizes):
            self._ps_ep_bytes[ep] += self._wire_nbytes(n)

    def _auto_checkpoint(self):
        """Chief-side recovery backstop: snapshot the post-step variable
        state every ``AUTODIST_AUTO_CHECKPOINT_EVERY`` train steps
        (async save — the device->host copy is the only on-path cost).
        Never fatal: the backstop degrading must not kill the training
        it exists to protect."""
        try:
            tree = {name: self._local_value(name)
                    for name in self._graph_item.graph.variables}
            self._auto_ckpt.save(self._step_count, tree)
            self._health['auto_checkpoints'] += 1
        except Exception as e:  # noqa: BLE001 - backstop, not the run
            logging.warning('auto-checkpoint at step %d failed: %s: %s',
                            self._step_count, type(e).__name__, e)

    @property
    def health_stats(self):
        """Elastic-recovery observability (feeds
        :func:`autodist_tpu.utils.profiling.health_report`): the peer
        failure policy, this worker's fencing generation, the current
        membership epoch, declared-dead counts, exclusions, observed
        rejoins with their recovery wall times, and the auto-checkpoint
        count. Empty for SPMD (non-loose) sessions: none of the
        recovery machinery runs there, and reporting its zero-state as
        if it did would be misleading."""
        if not self._loose:
            return {}
        # strategy re-ranks may still be running on their background
        # threads (spawned from the gate's failure_check): join them
        # all so the report never misses a decision it exists to audit
        for t in getattr(self, '_replan_threads', ()):
            if t.is_alive():
                t.join(timeout=60.0)
        out = dict(self._health)
        out.update(
            epoch=self._epoch_seen,
            generation=self._generation,
            rejoining=self._rejoining,
            joining=self._joining,
            num_workers=self._num_workers,
            world=self._world,
            active_workers=self._active_workers(),
            excluded=sorted(w.rsplit('/', 1)[-1]
                            for w in self._excluded))
        if self._monitor is not None:
            # the perf section: rolling cohort stats, active verdicts
            # (exclude candidates under policy=advise), the
            # slowdown/recovered audit and the recalibration
            # trajectory — health_report/format_health render it
            out['perf'] = self._monitor.snapshot()
        return out

    # -- telemetry plane ---------------------------------------------------
    @property
    def step_wall_series(self):
        """The uniform per-step wall series: ``run()``'s wall seconds
        for every executed train step, EVERY mode (loose or SPMD,
        pipelined or serial) — the series the telemetry snapshot
        reads. Bounded ring
        (``AUTODIST_TELEMETRY_MAX_SPANS``), oldest first."""
        return list(self._step_walls)

    def _join_tel_push(self):
        """Join the previous background telemetry push (keeps pushes
        FIFO-ordered on the lane and surfaces — logged, never raised —
        any error it hit)."""
        handle, self._tel_push_handle = self._tel_push_handle, None
        if handle is None:
            return
        try:
            handle.result()
        except Exception as e:  # noqa: BLE001 - advisory plane
            logging.warning('background telemetry batch push failed: '
                            '%s: %s', type(e).__name__, e)

    def _maybe_push_telemetry(self, client, step, final=False):
        """Batch-push this worker's drained span records to the
        ``<ns>/telemetry/`` namespace every
        ``AUTODIST_TELEMETRY_PUSH_EVERY`` train steps. Steady-state
        pushes ride a dedicated background lane (one lazily-created
        ``TransferPool`` worker with its own fenced connection): a
        telemetry batch never belongs on the step's critical path —
        at depth 1 the serial data plane would otherwise pay a full
        wire round trip per cadence. ``final=True`` (the close-time
        flush) joins the lane and pushes synchronously on the
        caller's client so nothing is in flight when the chief
        collects and purges. Never fatal: a telemetry push failing
        must not take down the training it observes."""
        if not self._tel.enabled or not self._loose:
            return
        every = ENV.AUTODIST_TELEMETRY_PUSH_EVERY.val
        if not final and (not every or step % every):
            return
        try:
            records = self._tel.drain_spans()
            # the monitor's zero-wire tap: our own drained batch is
            # ingested directly (it still goes to the wire below for
            # the cohort trace; poll skips fetching it back)
            if self._monitor is not None and records:
                self._monitor.ingest_local(records)
            if final:
                self._join_tel_push()
                _telemetry.push_records(client, self._ns,
                                        self._worker_name, records)
                return
            if not records:
                return
            if self._tel_pipe is None:
                from autodist_tpu.runtime import coord_client as cc
                coord_addr = getattr(self._coord, 'address', None)
                self._tel_pipe = cc.TransferPool(
                    [lambda: self._fenced_connect(coord_addr)])
            self._join_tel_push()
            ns, worker = self._ns, self._worker_name
            self._tel_push_handle = self._tel_pipe.submit(
                0, lambda c: _telemetry.push_records(c, ns, worker,
                                                     records))
        except Exception as e:  # noqa: BLE001 - advisory plane
            logging.warning('telemetry batch push failed at step %d: '
                            '%s: %s', step, type(e).__name__, e)

    def cohort_telemetry(self):
        """Chief-side cohort collection: every live member's pushed
        span batches off the PS telemetry namespace, tagged per
        worker and sorted on the shared wall axis. Loose mode only
        (SPMD programs have no PS plane to aggregate over); returns
        ``[]`` when telemetry is disabled or nothing was pushed."""
        if not self._loose or self._coord is None:
            return []
        members = ['p%d' % i for i in range(self._world)]
        return _telemetry.collect_records(self._coord, self._ns,
                                          members)

    def export_chrome_trace(self, path=None):
        """Assemble the cohort timeline and write Chrome
        ``trace_event`` JSON (chief-side; ``tools/trace_view.py`` is
        the offline twin). Returns the path, or None when there was
        nothing to export."""
        import json as _json
        records = self.cohort_telemetry()
        # this worker's still-undrained spans join the export (the
        # chief rarely pushes to itself)
        for rec in self._tel.drain_spans():
            rec.setdefault('worker', self._worker_name)
            records.append(rec)
        if not records:
            return None
        records.sort(key=lambda r: r.get('t0', 0.0))
        # attribute control-plane instants to THIS process's row: ring
        # events carry the SUBJECT worker (e.g. the excluded peer),
        # not the actor
        trace = _telemetry.chrome_trace(
            records,
            flight_events=[dict(e, worker_self=self._worker_name)
                           for e in self._flight.events()])
        if path is None:
            path = os.path.join(_telemetry.telemetry_dir(),
                                'trace-%s.json' % self._ns)
        os.makedirs(os.path.dirname(os.path.abspath(path)),
                    exist_ok=True)
        with open(path, 'w') as f:
            _json.dump(trace, f)
        logging.info('telemetry: wrote cohort Chrome trace (%d events) '
                     'to %s', len(trace['traceEvents']), path)
        return path

    @property
    def ps_stats(self):
        """Loose-mode wire accounting: payload bytes moved and seconds
        spent on PS pulls+pushes (the measured per-step PS overhead),
        plus the per-endpoint byte split (balanced placement evidence),
        the row-sparse plane's counters (``sparse``: sparse_pushes,
        rows_pushed, dense_bytes_avoided, zero_push_skips, row/full
        refreshes — docs/design/sparse-ps.md)
        and the async-pipeline phase breakdown — per-train-step pull /
        step / push seconds, the wire seconds actually EXPOSED on the
        critical path, and ``overlap_frac`` = the fraction of wire time
        the pipeline hid behind compute and host tail (0 at depth 1 by
        construction)."""
        with self._stats_lock:
            ph = dict(self._ps_phase)
            out = {'bytes': self._ps_bytes, 'seconds': self._ps_seconds,
                   # direction split: the quantized (i8) wire only
                   # shrinks pushes, so A/Bs must compare push bytes
                   'push_bytes': self._ps_push_bytes,
                   'pull_bytes': self._ps_pull_bytes,
                   'bytes_per_endpoint': list(self._ps_ep_bytes),
                   'mb_per_s': (self._ps_bytes / 1e6 / self._ps_seconds
                                if self._ps_seconds else 0.0),
                   'sparse': dict(self._sparse_stats)}
        steps = max(1, ph['train_steps'])
        # wire phases happen once per SYNC ROUND: at H=1 rounds ==
        # train steps (every push is a round) and the divide is the
        # legacy per-step one bit-for-bit; under a local-SGD window
        # (H>1) dividing by train steps would understate the per-round
        # pull/push/exposed averages by H x. step_s stays per train
        # step — compute happens every step regardless of the window.
        rounds = max(1, ph['sync_rounds']) if ph['sync_rounds'] \
            else steps
        wire = ph['pull_s'] + ph['push_s']
        out['pipeline'] = {
            'depth': self._pipeline_depth,
            'train_steps': ph['train_steps'],
            'sync_rounds': ph['sync_rounds'],
            'local_steps': self._local_steps,
            'discarded_prefetches': ph['discarded_prefetches'],
            'pull_s': ph['pull_s'] / rounds,
            'step_s': ph['step_s'] / steps,
            'push_s': ph['push_s'] / rounds,
            'exposed_wait_s': ph['exposed_wait_s'] / rounds,
            'overlap_frac': max(0.0, min(1.0, 1.0 -
                                ph['exposed_wait_s'] / wire))
            if wire > 0 else 0.0,
        }
        return out

    # -- multi-process placement helpers ----------------------------------
    def _put(self, value, sharding):
        """Place a host value that is logically global (same on every
        process): works for replicated and sharded NamedShardings."""
        if self._plan.num_processes == 1:
            return jax.device_put(jnp.asarray(value), sharding)
        val = np.asarray(value)
        return jax.make_array_from_callback(
            val.shape, sharding, lambda idx: val[idx])

    def _put_feed(self, value, spec):
        """Place a process-local feed: under multi-process SPMD the value
        is this worker's chunk of the global batch (reference between-graph
        feeds, remapper.py:109-123)."""
        sharding = NamedSharding(self._mesh, spec)
        if self._plan.num_processes == 1:
            return jax.device_put(jnp.asarray(value), sharding)
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(value))

    def _local_stack(self, arr):
        """This process's replicas of a P(data)-stacked output.

        Dedup by data-axis offset: on a multi-axis mesh a device holds one
        addressable shard per (data × other-axes) tile, but replicas across
        non-data axes carry the same data rows."""
        if self._plan.num_processes == 1:
            return np.asarray(arr)
        by_offset = {}
        for s in arr.addressable_shards:
            by_offset.setdefault(s.index[0].start or 0, s)
        shards = [by_offset[k] for k in sorted(by_offset)]
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    # -- state ------------------------------------------------------------
    def _init_state(self):
        plan = self._plan
        if plan.num_processes > 1:
            # replicas must start from the chief's initial values
            # (reference shares initializers: all_reduce_synchronizer.py:
            # 175-196); broadcast before placing.
            from jax.experimental import multihost_utils
            names = sorted(self._graph_item.graph.variables)
            vals = [np.asarray(
                self._graph_item.graph.variables[n].init_value)
                for n in names]
            vals = multihost_utils.broadcast_one_to_all(vals)
            for n, v in zip(names, vals):
                self._graph_item.graph.variables[n].init_value = \
                    np.asarray(v)
        if self._loose:
            variables = self._graph_item.graph.variables

            # chief seeds the authoritative PS copies across endpoints,
            # one tensor per shard for partitioned variables — one
            # pipelined vmset batch per endpoint (one round trip each
            # instead of one per variable/shard/chunk). A REJOINING
            # incarnation must never re-seed: the PS holds the trained
            # state its replacement exists to pick up.
            if self._is_chief and not self._rejoining:
                self._store_var_parts(
                    {name: v.init_value
                     for name, v in variables.items()})
            # heartbeat baseline BEFORE the barrier: once any gate runs,
            # every peer has a timestamp (a missing one reads as dead)
            self._coord.heartbeat(self._key(self._worker_name))
            if not (self._rejoining or self._joining):
                # a live JOINer is never a barrier party: its admit
                # handshake already waited for the init-done marker, so
                # the rendezvous below completed before it could exist
                self._coord.barrier(self._key('session/init'),
                                    self._num_workers, timeout_s=120.0)
                if self._is_chief:
                    # replacements key off this marker: only skip the
                    # init rendezvous once it actually completed
                    self._coord.set(self._key('session/init-done'), '1')
            elif self._coord.get(
                    self._key('session/init-done')) is None:
                # the prior incarnation died BEFORE its cohort's init
                # rendezvous completed: the replacement must fill the
                # dead worker's barrier slot, or the original cohort
                # blocks forever on a party that no longer exists
                self._coord.barrier(self._key('session/init'),
                                    self._num_workers, timeout_s=120.0)
            if not self._is_chief or self._rejoining:
                served_map, _ = self._fetch_var_parts(list(variables))
                for name, parts in served_map.items():
                    var = variables[name]
                    pc, _ = self._shard_info(name)
                    served = parts[0] if pc is None else pc.merge(parts)
                    var.init_value = served.astype(var.init_value.dtype)
        self._var_state = {}
        for name, var in self._graph_item.graph.variables.items():
            self._var_state[name] = self._put(
                plan.pad_host(name, jnp.asarray(var.init_value)),
                plan.var_sharding(name))
        self._opt_state = {}
        self._refresh_opt_state()
        # Loose-mode optimizer slots: worker-local by default (the
        # device-local TPU-native choice), or PS-resident and shared via
        # the strategy's shared_optimizer flag — the reference's
        # semantics, where the optimizer is re-created over PS-resident
        # variables (kernel/partitioner.py:570-573) and the update op
        # runs on the PS (ps_synchronizer.py:175-176). Shared mode ships
        # raw gradients (BSTEP); the divergence is real and measured:
        # 2 workers x 5 momentum(0.9) steps on c0-style data moved b to
        # 1.477 (shared) vs 0.993 (worker-local) — 1.49x, near the
        # theoretical 1.58x for interleaved equal gradients — because
        # the PS velocity integrates all 10 pushes while each local one
        # sees only 5 (tests/integration/test_multiprocess.py::
        # test_shared_optimizer_state_on_ps).
        # compressor/aux state. These leaves are *per-replica* (e.g. each
        # device's error-feedback residual differs), so they carry an
        # explicit leading replica dimension sharded over the data axis.
        n = plan.num_replicas
        rep_sharding = NamedSharding(self._mesh, P(AXIS_DATA))
        self._aux_state = {}
        for name, vplan in plan.var_plans.items():
            aux = vplan.compressor.init_state(
                np.asarray(vplan.var.init_value))
            if aux:
                self._aux_state['compressor/%s' % name] = {
                    k: self._put(
                        jnp.broadcast_to(jnp.asarray(v),
                                         (n,) + tuple(v.shape)),
                        rep_sharding)
                    for k, v in aux.items()}

    def _place_slots(self, var_name, leafstate):
        """Shard optimizer slots like their variable (ZeRO, padded like
        the variable for uneven partitions); scalars (e.g. step counts)
        replicate. Weight-update-sharded variables store their slots as
        FLAT 1/n shards over the data axis (row-major, zero-padded to
        ``wus_padded``) — the layout the fused shard-local update
        consumes, and the ~(n-1)/n opt-slot HBM saving the sharded
        update exists for."""
        var = self._graph_item.var_by_name(var_name)
        vplan = self._plan.var_plans.get(var_name)
        sharding = self._plan.var_sharding(var_name)
        repl = self._plan.replicated_sharding()
        wus = vplan is not None and getattr(vplan, 'update_sharded',
                                            False)

        def place(leaf):
            if hasattr(leaf, 'shape') and tuple(leaf.shape) == \
                    tuple(var.shape):
                if wus:
                    flat = jnp.ravel(jnp.asarray(leaf))
                    if vplan.wus_pad:
                        flat = jnp.pad(flat, (0, vplan.wus_pad))
                    return self._put(
                        flat, NamedSharding(self._mesh, P(AXIS_DATA)))
                return self._put(
                    self._plan.pad_host(var_name, jnp.asarray(leaf)),
                    sharding)
            return self._put(jnp.asarray(leaf), repl)

        return jax.tree.map(place, leafstate)

    def _slot_spec(self, var_name, leaf):
        vplan = self._plan.var_plans.get(var_name)
        if vplan is not None and getattr(vplan, 'update_sharded',
                                         False) and \
                hasattr(leaf, 'shape') and \
                tuple(leaf.shape) == (vplan.wus_padded,):
            return P(AXIS_DATA)   # flat weight-update shard layout
        # placed slots carry the variable's physical (padded) shape
        phys = self._plan.padded_shape(var_name)
        if phys is None:
            phys = self._graph_item.var_by_name(var_name).shape
        if hasattr(leaf, 'shape') and tuple(leaf.shape) == tuple(phys):
            return self._plan.var_spec(var_name)
        return P()

    # -- run --------------------------------------------------------------
    def run(self, fetches, feed_dict=None, options=None):
        """Execute fetches (reference WrappedSession.run, runner.py:117-132).

        Observability wrapper over :meth:`_run_fetches`: every executed
        train step records one uniform wall-time sample
        (:attr:`step_wall_series` + the ``step_wall_s`` telemetry
        series) and, with telemetry enabled, a ``step`` span tagged
        with its step id and worker. A
        :class:`~autodist_tpu.runtime.coord_client.FencedWriteError`
        surfacing here means this process is a zombie — the flight
        recorder dumps before the error propagates (the evidence the
        post-mortem needs is exactly what dies with the process).
        """
        import time as _time
        from autodist_tpu.runtime.coord_client import FencedWriteError
        t0 = _time.perf_counter()
        before = self._step_count
        try:
            results = self._run_fetches(fetches, feed_dict, options)
        except FencedWriteError:
            self._flight.record('fenced_write_error',
                                worker=self._worker_name,
                                step=self._step_count)
            self._flight.dump('fenced_write_error')
            raise
        if self._step_count > before:
            wall = _time.perf_counter() - t0
            self._step_walls.append(wall)
            if self._tel.enabled:
                self._tel.observe('step_wall_s', wall)
                self._tel.gauge('step', self._step_count)
                self._tel.record_span('step', t0, wall,
                                      step=self._step_count,
                                      worker=self._worker_name)
            if self._roofline_tracker is not None:
                # exposed comms for the regime split: in loose mode the
                # wall beyond the compiled step's execution is the
                # gate/pull/push wire time; inside one SPMD program
                # collectives are part of the device step, so None
                # (the regime then splits compute vs memory only)
                comms = max(0.0, wall - self._last_exec_wall) \
                    if self._loose and self._last_exec_wall else None
                rec = self._roofline_tracker.observe_step(
                    self._step_count, wall, cost=self._last_step_cost,
                    comms_s=comms)
                if rec is not None and self._monitor is not None:
                    self._monitor.observe_roofline(self._worker_name,
                                                   rec)
            if self._monitor is not None:
                self._monitor.observe_step(self._worker_name,
                                           self._step_count, wall)
                self._maybe_poll_monitor()
        return results

    @property
    def monitor(self):
        """The chief's :class:`~autodist_tpu.telemetry.monitor.
        CohortMonitor` (None off-chief, with telemetry disabled, or
        under ``AUTODIST_STRAGGLER_POLICY=off``). Operators wire its
        :meth:`metrics` into ``AutoscaleController(metrics_source=)``
        so the built-in ``step_time_target_s`` policy runs on the
        cohort's measured step time."""
        return self._monitor

    def _maybe_poll_monitor(self):
        """Chief-side monitor cadence: poll the cohort's new span
        batches every ``AUTODIST_TELEMETRY_PUSH_EVERY`` steps (the
        batches only land on that cadence, so polling faster buys
        nothing) and refit the cost model's link constants every
        ``AUTODIST_RECALIBRATE_EVERY`` steps. Never fatal — the
        sentry must not take down the training it observes."""
        mon = self._monitor
        if mon is None:
            return
        every = max(1, ENV.AUTODIST_TELEMETRY_PUSH_EVERY.val or 8)
        if self._step_count % every:
            return
        try:
            mon.poll()
            if self._recalibrate_every and \
                    self._step_count - self._last_recalibrate_step >= \
                    self._recalibrate_every:
                rs = getattr(self._cluster, '_resource_spec', None)
                from autodist_tpu.simulator.cost_model import \
                    CostModelParams
                base = CostModelParams.from_topology(rs.topology) \
                    if rs is not None else CostModelParams()
                cross = rs.topology.multi_node if rs is not None \
                    else False
                if mon.recalibrate(base, num_replicas=max(2, self._world),
                                   cross_node=cross,
                                   step=self._step_count) is not None:
                    self._last_recalibrate_step = self._step_count
        except Exception as e:  # noqa: BLE001 - advisory plane
            logging.warning('cohort monitor poll at step %d failed: '
                            '%s: %s', self._step_count,
                            type(e).__name__, e)

    def _run_fetches(self, fetches, feed_dict=None, options=None):
        if self._closed:
            raise RuntimeError('Session is closed')
        if ENV.AUTODIST_IS_TESTING.val and \
                self._user_node_count() != self._built_node_count:
            raise RuntimeError(
                'Graph modified after distributed session creation '
                '(%d nodes, built with %d)' %
                (self._user_node_count(), self._built_node_count))
        # staged executed re-plan (AUTODIST_EXECUTE_REPLAN): apply at
        # the step boundary, before anything touches the plan
        if self._pending_replan is not None:
            self._apply_pending_replan()
        # epoch-swap handshake: discover/ack staged plans and — once
        # the commit marker is armed and our counter reaches the
        # boundary — apply the cohort's new plan before this step
        if self._loose:
            self._poll_swap_stage()
            self._apply_pending_swap()
        feed_dict = feed_dict or {}
        single = not isinstance(fetches, (list, tuple))
        fetch_list = [fetches] if single else list(fetches)
        norm = [f.read() if isinstance(f, fe.Variable) else f
                for f in fetch_list]

        feed_nodes = sorted(feed_dict.keys(), key=lambda p: p.name)
        feed_vals = []
        split_flags = []
        for ph in feed_nodes:
            v = np.asarray(feed_dict[ph])
            if v.dtype == np.float64:
                v = v.astype(np.float32)
            feed_vals.append(v)
            split_flags.append(self._plan.feed_splittable(v, ph))

        # PS-resident optimizer: also fetch the synced gradients of
        # shared vars so they can be pushed raw (BSTEP applies the step
        # service-side with shared slots)
        shared_spec, extra_fetches = ([], [])
        if self._loose and self._shared_opt_vars:
            shared_spec, extra_fetches = self._shared_push_spec(norm)
        all_fetches = norm + extra_fetches

        key = (tuple(id(f) for f in all_fetches),
               tuple((id(p), v.shape, str(v.dtype), s)
                     for p, v, s in zip(feed_nodes, feed_vals, split_flags)))
        first_compile = key not in self._cache
        if first_compile:
            self._cache[key] = self._build_step(all_fetches, feed_nodes,
                                                split_flags)
        fn = self._cache[key]

        # a run is a training step only if it executes an optimizer
        # update; fetch-only runs (variable reads, eval) must not count
        # against the staleness window or push deltas
        is_train = any(isinstance(f, fe.ApplyGradients) for f in norm)

        pulled = None
        if self._loose:
            # local-SGD window position: under H>1 only the first train
            # step of a window touches the sync plane (join, gate,
            # pull); the H-1 steps after it run purely locally against
            # the window base, and fetch-only runs serve local state
            # (a mid-window pull would clobber the local progress the
            # window delta is computed from). H=1 takes the every-step
            # path below unchanged — bit-identical to legacy loose.
            h = self._local_steps
            window_start = self._step_count % h == 0
            sync_run = h == 1 or (is_train and window_start)
            prefetch = None
            if sync_run:
                # join any in-flight background push FIRST (pipeline
                # depth >= 2): its error surfaces here instead of
                # silently, and the pull below must observe our own
                # landed pushes (read-your-writes) — the prefetch
                # record it returns was only issued after the push
                # completed.
                prefetch = self._join_pipeline()
            # bounded-staleness window (reference token queues of size s,
            # ps_synchronizer.py:387-458): before running step s (1-based)
            # every worker must have completed >= s - staleness steps.
            # Under H>1 the same gate runs once per window over sync
            # ROUNDS: before round r every worker must have published
            # >= r - staleness rounds, so no reader ever observes state
            # older than H * staleness train steps. sync=False vars are
            # unconditional no-wait (ps_strategy.py:30-35); any sync
            # var imposes its (tightest) bound.
            self._coord.heartbeat(self._key(self._worker_name))
            if is_train and sync_run and self._plan.gate_enabled:
                gate_at = self._step_count + 1 if h == 1 \
                    else self._round_count + 1
                # membership is a CALLABLE: policy=exclude can shrink
                # the quorum while we are blocked inside this gate, and
                # the wait must re-bound against the new epoch's count
                with self._tel.span('staleness_gate',
                                    step=gate_at,
                                    worker=self._worker_name):
                    self._coord.staleness_gate(
                        gate_at,
                        self._plan.gate_staleness,
                        self._active_workers,
                        prefix=self._key('step/'),
                        failure_check=self._check_peers_alive)
                # the gate guarantees every peer completed >= step -
                # staleness; a prefetch taken while some peer was still
                # below that bound may lack pushes the gate just
                # guaranteed — discard it (the refetch pays the exposed
                # wire time serial mode would have paid anyway)
                if prefetch is not None and prefetch.get(
                        'peer_floor', -1) < \
                        gate_at - self._plan.gate_staleness:
                    self._account_prefetch_discard(prefetch)
                    prefetch = None
            if sync_run:
                pulled = self._pull_ps_vars(prefetch, train=is_train)
                if h > 1:
                    # the merged state just pulled is the base the
                    # whole window's delta is computed against
                    self._window_base = pulled

        placed = []
        for v, split in zip(feed_vals, split_flags):
            placed.append(self._put_feed(v, P(AXIS_DATA) if split
                                         else P()))

        # dump-graphs and the roofline cost pull share ONE extra
        # lowering of the step (re-tracing a large step costs real
        # host seconds — never pay it twice, and only ever once per
        # compile key)
        lowered = None
        need_cost = self._roofline_tracker is not None and \
            key not in self._roofline_costs
        if (first_compile and ENV.AUTODIST_DUMP_GRAPHS.val) or \
                need_cost:
            try:
                lowered = fn.lower(self._var_state, self._opt_state,
                                   self._aux_state, placed)
            except Exception as e:  # noqa: BLE001 - never fatal:
                # both consumers are observability, not execution
                logging.debug('step lowering for dump/roofline '
                              'failed (%s: %s)', type(e).__name__, e)
        if first_compile and ENV.AUTODIST_DUMP_GRAPHS.val and \
                lowered is not None:
            # final-phase program dump (reference '3-transformed' graph)
            from autodist_tpu.utils import visualization as viz
            viz.log_compiled(lowered,
                             '4-lowered-step-%d' % len(self._cache))

        if self._roofline_tracker is not None:
            # FLOPs + bytes-accessed once per compilation
            # (cost_analysis on the lowered program — no backend
            # compile; cost_of caches per program), so the per-step
            # sampling in run() is pure arithmetic. Graceful: a
            # backend without cost_analysis leaves flops None and
            # every sampled record explains its null MFU.
            if need_cost:
                from autodist_tpu.telemetry import roofline as _roofline
                self._roofline_costs[key] = _roofline.cost_of(lowered) \
                    if lowered is not None else \
                    {'flops': None, 'bytes_accessed': None}
            self._last_step_cost = self._roofline_costs[key]

        tracing = options is not None and \
            getattr(options, 'trace_level', 0) > 0
        if tracing:
            os.makedirs(options.trace_dir, exist_ok=True)
            jax.profiler.start_trace(options.trace_dir)
        import time as _time
        t_step = _time.perf_counter()
        try:
            outs, self._var_state, self._opt_state, self._aux_state = fn(
                self._var_state, self._opt_state, self._aux_state, placed)
            if tracing:
                jax.block_until_ready(outs)
        finally:
            if tracing:
                jax.profiler.stop_trace()
                logging.info('Profiler trace written to %s',
                             options.trace_dir)
        if is_train:
            self._step_count += 1
            self._last_exec_wall = _time.perf_counter() - t_step
            if self._loose:
                with self._stats_lock:
                    self._ps_phase['step_s'] += \
                        _time.perf_counter() - t_step
                    self._ps_phase['train_steps'] += 1
                if self._local_steps == 1:
                    self._dispatch_push(shared_spec, outs, pulled)
                elif self._step_count % self._local_steps == 0:
                    # window complete: one sync round ships the whole
                    # window's delta against the base pulled at the
                    # window's first step
                    base, self._window_base = self._window_base, None
                    self._dispatch_push(shared_spec, outs, base)
                if self._auto_ckpt is not None and \
                        self._step_count % self._auto_ckpt_every == 0:
                    self._auto_checkpoint()

        split_sizes = {v.shape[0] // self._plan.local_replicas
                       for v, s in zip(feed_vals, split_flags) if s}
        results = [self._contract(f, o, split_sizes)
                   for f, o in zip(norm, outs)]
        return results[0] if single else results

    # -- loose-mode PS data plane -----------------------------------------
    def _wire_nbytes(self, n_elems, push=False):
        """Wire bytes ``n_elems`` floats cost in the given direction.

        The i8 wire is push-only (deltas/gradients quantize under the
        session's error-feedback residual); pulls and stores ride f32
        under an i8 setting (coord_client._pull_wire), so pull-side
        accounting must price the downgraded dtype, not the env
        setting."""
        from autodist_tpu.runtime import coord_client as cc
        wire = cc._wire_dtype() if push else cc._pull_wire()
        return cc.wire_nbytes(n_elems, wire)

    def _join_pipeline(self):
        """Join the in-flight background push job (pipeline depth >= 2)
        and return its prefetch record (None when nothing is in
        flight). Any error the pipeline hit — push, publish, or
        pull-ahead — re-raises HERE, on the caller's thread, so a
        failed background push can never be silently lost. The wall
        time spent blocked is the wire time the pipeline failed to
        hide; it feeds ``overlap_frac``."""
        job = self._inflight
        if job is None:
            # a read-only access (get_variable_value) may have joined
            # the job early and stashed its still-valid prefetch
            stash, self._stashed_prefetch = self._stashed_prefetch, None
            return stash
        self._inflight = None
        import time as _time
        t0 = _time.perf_counter()
        try:
            return job.result()
        finally:
            blocked = _time.perf_counter() - t0
            with self._stats_lock:
                self._ps_phase['exposed_wait_s'] += blocked
            # the 'pipeline' phase span: wire time the background
            # pipeline FAILED to hide (the monitor's phase split and
            # trace_view's per-phase columns both read it)
            self._tel.record_span(
                'pipeline_wait', t0, blocked,
                step=self._step_count + 1, worker=self._worker_name)

    def _drain_pipeline(self, keep_prefetch=False):
        """Join any in-flight pipeline work: user-facing reads/writes
        (checkpointing, variable loads) must see their own session's
        pushes. With ``keep_prefetch`` (read-only callers — a read does
        not invalidate the prefetched pull) the record is stashed for
        the next ``run()`` instead of discarded, so per-step variable
        reads don't silently degrade depth 2 to serial pulls; a load
        supersedes the prefetch and discards it (the dropped record's
        wire traffic still counts — it moved)."""
        record = self._join_pipeline()
        if record is not None and not keep_prefetch:
            self._account_prefetch_discard(record)
            record = None
        self._stashed_prefetch = record if keep_prefetch else None

    def _dispatch_push(self, shared_spec, outs, pulled):
        """Ship the just-completed step's updates.

        Depth 1: serial push + publish on the calling thread — the
        bit-exact legacy data plane. Depth >= 2: the device->host
        readback of gradients/updated state, the delta push, the step
        publish and the NEXT step's variable pull-ahead all run on the
        single-threaded pipeline worker; ``run()`` joins the result at
        the next step's entry, so the wire time hides behind this
        step's host tail and the inter-step interval.

        Ordering invariants, both depths: push -> publish (the
        staleness gate must only count a step whose update landed) and
        push -> next pull (per-variable read-your-writes; the pipeline
        issues the pull-ahead strictly after every endpoint's push
        join). run() joins the pipeline BEFORE gating, so our own
        published counter is always current at the gate, and it
        discards a prefetch whose recorded peer floor is below the next
        step's staleness bound — the pipeline adds overlap inside the
        existing staleness bound, never extra staleness.

        Under a local-SGD window (H>1) a dispatch IS a sync round: the
        published counter, the gate and the pipeline floor all count
        rounds, and the pushed delta is the whole window's parameter
        delta against ``pulled`` (the window base), scaled by 1/W when
        AUTODIST_LOCAL_SGD_AVERAGE is on so the sum-based delta wire
        lands on the mean of the W workers' windows."""
        h = self._local_steps
        scale = None
        if h > 1:
            self._round_count += 1
            step = self._round_count
            if ENV.AUTODIST_LOCAL_SGD_AVERAGE.val:
                scale = 1.0 / max(1, len(self._live_members()))
        else:
            step = self._step_count
        tstep = self._step_count
        worker = self._worker_name
        prefix = self._key('step/')
        with self._stats_lock:
            self._ps_phase['sync_rounds'] += 1

        def shared_values():
            out = {}
            for name, idx, rule, params in shared_spec:
                g = self._local_stack(outs[idx])[0]
                out[name] = (np.asarray(g, np.float32), rule, params)
            return out

        if self._pipe is None:
            import time as _time
            t0 = _time.perf_counter()
            self._snap_round_open(self._coord, worker)
            self._push_ps_deltas(pulled, shared_values(), scale=scale)
            self._coord.publish_step(worker, step, prefix=prefix)
            self._snap_round_close(self._coord, worker)
            self._flight.record('step_publish', worker=worker,
                                step=step)
            with self._stats_lock:
                self._ps_phase['exposed_wait_s'] += \
                    _time.perf_counter() - t0
            self._maybe_push_telemetry(self._coord, tstep)
            return

        # snapshot the LIVE membership (launch quorum + joins, minus
        # exclusions) — the floor must range over every worker the next
        # gate will count, not the launch-time list
        members = self._live_members()

        def job(client):
            self._snap_round_open(client, worker)
            self._push_ps_deltas(pulled, shared_values(), scale=scale)
            client.publish_step(worker, step, prefix=prefix)
            self._snap_round_close(client, worker)
            self._flight.record('step_publish', worker=worker,
                                step=step)
            self._maybe_push_telemetry(client, tstep)
            # lower-bound what the pull-ahead below will observe: a
            # peer's published counter only advances AFTER its push
            # landed (push -> publish), so every push published by now
            # is visible to the pull. run() compares this floor against
            # the next step's staleness bound and discards the prefetch
            # if it was taken too early — the pipeline must never serve
            # values staler than the gate guarantees.
            floor = step if len(members) <= 1 else min(
                client.incr(prefix + 'p%d' % i, 0) for i in members)
            to_fetch = self._pull_to_fetch()
            parts, wire_s = self._fetch_var_parts(to_fetch)
            return {'names': to_fetch, 'parts': parts,
                    'wire_s': wire_s, 'peer_floor': floor}

        self._inflight = self._pipe.submit(0, job)

    def _pull_to_fetch(self):
        """The variables a per-step pull must actually fetch (proxy
        variables with a warm cache are served locally)."""
        return [name for name in self._graph_item.graph.variables
                if not (name in self._proxy_vars and
                        name in self._proxy_cache)]

    def _fetch_var_parts(self, names):
        """Batched authoritative fetch: ONE pipelined ``vmget`` per
        endpoint covers every (variable, shard) unit it serves — all
        request frames on the wire before the first reply is drained,
        endpoints in parallel on the TransferPool workers. Returns
        ``({name: [per-shard host array]}, wall seconds)``."""
        import time as _time
        variables = self._graph_item.graph.variables
        groups, shard_counts = self._transfer_groups(names)
        results = {name: [None] * c for name, c in shard_counts.items()}
        t0 = _time.perf_counter()

        def fetch_group(units):
            def go(client):
                specs = []
                for key, name, i, pc in units:
                    shp = variables[name].shape if pc is None else \
                        pc.shard_shapes(variables[name].shape)[i]
                    specs.append((self._key(key), shp))
                arrs = client.vmget(specs)
                return [(name, i, a) for (_, name, i, _), a
                        in zip(units, arrs)]
            return go

        for got in self._pool.run([(ep, fetch_group(units))
                                   for ep, units in groups.items()]):
            for name, i, a in got:
                results[name][i] = a
        return results, _time.perf_counter() - t0

    def _store_var_parts(self, values):
        """Batched authoritative store, `_fetch_var_parts`'s write twin:
        ONE pipelined ``vmset`` per endpoint covers every (variable,
        shard) unit in ``values`` (``{name: whole host value}``; shards
        are split here)."""
        groups, _ = self._transfer_groups(list(values))

        def store_group(units):
            def go(client):
                items = []
                for key, name, i, pc in units:
                    val = np.asarray(values[name])
                    if pc is not None:
                        val = pc.split(val)[i]
                    items.append((self._key(key), val))
                client.vmset(items)
            return go

        self._pool.run([(ep, store_group(units))
                        for ep, units in groups.items()])

    def _account_prefetch_discard(self, prefetch):
        """A discarded pull-ahead still moved its whole payload on the
        wire — account that traffic (bytes, seconds, per-endpoint
        split) so ``ps_stats`` reflects what the network actually
        carried, and count the discard so the pipeline block shows how
        often the peer-floor check fell back to an exposed refetch.
        The wasted wire seconds deliberately do NOT join the per-step
        ``pull_s`` phase: overlap_frac must not improve because hidden
        wire time was thrown away."""
        n_elems = 0
        for name in prefetch['names']:
            var = self._graph_item.var_by_name(name)
            n_elems += int(np.prod(var.shape)) if var.shape else 1
        with self._stats_lock:
            for name in prefetch['names']:
                self._account_ep_bytes(name)
            self._ps_seconds += prefetch['wire_s']
            self._ps_bytes += self._wire_nbytes(n_elems)
            self._ps_pull_bytes += self._wire_nbytes(n_elems)
            self._ps_phase['discarded_prefetches'] += 1

    def _pull_ps_vars(self, prefetch=None, train=True):
        """Refresh variable state from the authoritative PS copies (the
        worker's per-step PS read); each shard of a partitioned
        variable comes from its own endpoint. With ``prefetch`` (the
        pipeline's pull-ahead record, depth >= 2) the host values were
        already fetched in the background and only device placement
        remains on the critical path. Returns the pulled host values
        for delta computation. Fetch-only runs (``train=False``) keep
        the global wire accounting but stay out of the per-train-step
        phase averages ``ps_stats['pipeline']`` divides by
        ``train_steps``."""
        import time as _time
        t_fn = _time.perf_counter()
        variables = self._graph_item.graph.variables
        to_fetch = self._pull_to_fetch()
        fetched = None
        wire_s = exposed_s = 0.0
        if prefetch is not None and prefetch['names'] == to_fetch:
            fetched = prefetch['parts']
            wire_s = prefetch['wire_s']
        if fetched is None:
            # no (usable) prefetch: the fetch is fully exposed
            fetched, wire_s = self._fetch_var_parts(to_fetch)
            exposed_s = wire_s
        pulled = {}
        n_elems = 0
        with self._stats_lock:
            for name in fetched:
                self._account_ep_bytes(name)
        for name, var in variables.items():
            if name in fetched:
                parts = fetched[name]
                pc, _ = self._shard_info(name)
                served = parts[0] if pc is None else (
                    None if any(p is None for p in parts)
                    else pc.merge(parts))
                n_elems += int(np.prod(var.shape)) if var.shape else 1
                if served is None:  # pragma: no cover - init barrier
                    served = np.asarray(var.init_value, dtype=np.float32)
                served = served.astype(var.init_value.dtype)
            else:
                # proxy read: serve from the local cache, no PS
                # round-trip on the pre-step critical path
                served = self._proxy_cache[name]
                self._proxy_hits += 1
            pulled[name] = served
            self._var_state[name] = self._put(
                self._plan.pad_host(name, jnp.asarray(served)),
                self._plan.var_sharding(name))
        with self._stats_lock:
            self._ps_seconds += wire_s
            self._ps_bytes += self._wire_nbytes(n_elems)
            self._ps_pull_bytes += self._wire_nbytes(n_elems)
            if train:
                self._ps_phase['pull_s'] += wire_s
                self._ps_phase['exposed_wait_s'] += exposed_s
        self._tel.record_span(
            'pull_vars', t_fn, _time.perf_counter() - t_fn,
            step=self._step_count + 1, worker=self._worker_name,
            prefetched=exposed_s == 0.0 and wire_s > 0.0)
        return pulled

    def _shared_push_spec(self, norm):
        """Plan the PS-side optimizer pushes for the fetched train ops:
        returns ``[(var_name, fetch_idx, rule, params)]`` plus the extra
        (synced) gradient nodes to fetch. Optimizers without scalar
        ``ps_step_params`` (schedule-driven or exotic rules) fall back
        to worker-local slots with a one-time note."""
        spec = []
        extra = []
        node_pos = {id(f): i for i, f in enumerate(norm)}
        for f in norm:
            if not isinstance(f, fe.ApplyGradients):
                continue
            params = getattr(f.optimizer, 'ps_step_params', None)
            for gnode, var in f.grads_and_vars:
                if var.name not in self._shared_opt_vars:
                    continue
                if params is None:
                    if var.name not in self._shared_warned:
                        self._shared_warned.add(var.name)
                        logging.warning(
                            'shared_optimizer requested for %s but '
                            'optimizer %s has no PS-side update rule '
                            '(sgd/momentum/adam/adagrad with scalar '
                            'hyperparameters); its slots stay '
                            'worker-local', var.name, f.optimizer.name)
                    continue
                idx = node_pos.get(id(gnode))
                if idx is None:
                    idx = len(norm) + len(extra)
                    node_pos[id(gnode)] = idx
                    extra.append(gnode)
                spec.append((var.name, idx, params['rule'],
                             params['params']))
        return spec, extra

    def _classify_push(self, deltas):
        """Per-variable push mode for this step's deltas: the set of
        all-zero deltas (skipped outright — frozen/eval-only variables
        must not ship full zero tensors every push) and, for
        sparse-flagged 2-D variables, the touched-row index vector when
        the touched fraction is at or below
        ``AUTODIST_SPARSE_PUSH_MAX_FRAC``. Lossless by construction:
        a dropped row's delta is exactly zero, so the BSADD scatter-add
        lands bit-identically to the dense BADD."""
        frac = ENV.AUTODIST_SPARSE_PUSH_MAX_FRAC.val
        zero_skip = set()
        sparse_rows = {}
        for name, delta in deltas.items():
            if frac and name in self._sparse_vars:
                # one scan: the row mask also answers "all zero"
                touched = np.flatnonzero(
                    np.any(delta != 0, axis=1)).astype(np.int32)
                if touched.size == 0:
                    zero_skip.add(name)
                elif touched.size <= frac * delta.shape[0]:
                    sparse_rows[name] = touched
                continue
            if not delta.any():
                zero_skip.add(name)
        return zero_skip, sparse_rows

    def _shard_row_starts(self, name, pc):
        """Cumulative row offsets of an axis-0-partitioned variable's
        shards (sparse vars are forced to axis 0 by the builders)."""
        var = self._graph_item.var_by_name(name)
        rows = [int(s[0]) for s in pc.shard_shapes(var.shape)]
        starts = [0]
        for r in rows:
            starts.append(starts[-1] + r)
        return starts

    def _push_ps_deltas(self, pulled, shared_push=None, scale=None):
        """Push per-variable updates. Default: ``new - pulled`` deltas —
        the binary BADD is commutative, so concurrent workers' updates
        accumulate exactly like the reference's apply-per-push
        accumulators. Sparse-flagged variables whose delta touches few
        rows ship ONLY those rows (``vmsadd``/BSADD — O(batch) wire
        instead of O(vocab x dim)); all-zero deltas are skipped
        entirely. Vars in ``shared_push`` instead ship their raw
        gradient; the service applies the optimizer step with
        PS-resident shared slots (BSTEP). Partitioned variables push
        each shard's slice to that shard's own endpoint (the reference
        splits gradients per shard, kernel/partitioner.py:686-704).
        Endpoint groups push in parallel on the TransferPool workers,
        each as ONE pipelined ``vmadd`` + one ``vmsadd`` batch (plus
        serial ``vstep`` for shared-optimizer vars — the chunk-shared
        step index makes those inherently sequential). At pipeline
        depth >= 2 this whole method runs on the background pipeline
        thread, including the device->host readback of the updated
        state.

        Under the quantized push wire (``AUTODIST_PS_WIRE_DTYPE=i8``)
        every pushed delta/gradient carries error feedback: the
        residual the LAST push's block quantization dropped is added
        back before classification (so accumulated error flushes even
        through variables whose raw delta is zero this step), and the
        new residual — ``compensated - wire_roundtrip(compensated)``,
        bit-exactly the mass the service did not receive — is kept for
        the next push. BADD/BSADD accumulate at f32 rest, so only this
        push direction quantizes; pulls stay f32.

        ``scale`` (local-SGD window averaging, docs/design/local-sgd.md)
        multiplies every delta before classification and quantization:
        under H>1 ``pulled`` is the WINDOW base and scale=1/W turns the
        sum-based wire into the mean of the W workers' window deltas.
        Scaling before classification keeps the composition exact —
        the touched-row set is the window's union (a row scaled by 1/W
        is nonzero iff the raw row is), and the i8 error feedback
        tracks the scaled wire mass that was actually dropped. None
        (the H=1 path) is bit-identical to the pre-window plane."""
        import time as _time

        from autodist_tpu.runtime import coord_client as cc
        t0 = _time.perf_counter()
        shared_push = dict(shared_push or {})
        push_wire = cc._wire_dtype()
        lossy = push_wire == 'i8'
        afters = {name: np.asarray(self._local_value(name),
                                   dtype=np.float32)
                  for name in pulled if name not in shared_push}
        deltas = {name: after - np.asarray(pulled[name],
                                           dtype=np.float32)
                  for name, after in afters.items()}
        if scale is not None and scale != 1.0:
            deltas = {name: d * np.float32(scale)
                      for name, d in deltas.items()}
        if lossy:
            for name in list(deltas):
                res = self._push_residual.get(name)
                if res is not None:
                    deltas[name] = deltas[name] + res
            for name, (g, rule, params) in list(shared_push.items()):
                res = self._push_residual.get(name)
                if res is not None:
                    shared_push[name] = (g + res, rule, params)
        zero_skip, sparse_rows = self._classify_push(deltas)
        groups, _ = self._transfer_groups(list(pulled))

        # plan every endpoint's batch on THIS thread (the pool workers
        # only move bytes), accounting the exact wire cost as we go
        ep_jobs = {}
        ep_bytes = [0] * len(self._ps_addrs)
        wire_bytes = 0
        rows_pushed = 0
        bytes_avoided = 0
        # Residual bookkeeping quantizes each pushed array once here
        # (wire_roundtrip) and once more when the client encodes the
        # actual frames — a deliberate trade: sharing one encode pass
        # would thread pre-encoded blobs through vmadd/vmsadd/vstep's
        # framing, and the extra pass is host CPU the depth-2 pipeline
        # already hides, while the roundtrip helper guarantees the
        # residual is bit-exactly what the service decodes.
        res_parts = {}   # name -> [per-shard residual part] (dense)
        new_res = {}     # name -> full-shape residual (sparse path)
        for ep, units in groups.items():
            job = ep_jobs.setdefault(
                ep, {'steps': [], 'adds': [], 'sadds': []})
            for key, name, i, pc in units:
                if name in shared_push:
                    g, rule, params = shared_push[name]
                    if pc is not None:
                        g = pc.split(g)[i]
                    job['steps'].append(
                        (self._key(key), g, rule, params))
                    nb = self._wire_nbytes(g.size, push=True)
                    if lossy:
                        parts = res_parts.setdefault(
                            name,
                            [None] * len(self._shard_info(name)[1]))
                        parts[i] = g - cc.wire_roundtrip(g, push_wire)
                elif name in zero_skip:
                    full = deltas[name] if pc is None else \
                        pc.split(deltas[name])[i]
                    bytes_avoided += self._wire_nbytes(full.size,
                                                       push=True)
                    continue
                elif name in sparse_rows:
                    delta = deltas[name]
                    idx = sparse_rows[name]
                    if pc is None:
                        sel, local, rows = idx, idx, delta[idx]
                    else:
                        starts = self._shard_row_starts(name, pc)
                        lo, hi = starts[i], starts[i + 1]
                        sel = idx[(idx >= lo) & (idx < hi)]
                        dense_nb = self._wire_nbytes(
                            (hi - lo) * delta.shape[1], push=True)
                        if sel.size == 0:
                            bytes_avoided += dense_nb
                            continue
                        local = (sel - lo).astype(np.int32)
                        rows = delta[sel]
                    job['sadds'].append((self._key(key), local, rows))
                    nb = local.size * 4 + \
                        self._wire_nbytes(rows.size, push=True)
                    dense_elems = (delta.shape[0] if pc is None
                                   else hi - lo) * delta.shape[1]
                    bytes_avoided += self._wire_nbytes(
                        dense_elems, push=True) - nb
                    rows_pushed += local.size
                    if lossy:
                        res = new_res.setdefault(
                            name, np.zeros_like(delta))
                        res[sel] = rows - cc.rows_roundtrip(rows,
                                                            push_wire)
                else:
                    delta = deltas[name]
                    if pc is not None:
                        delta = pc.split(delta)[i]
                    job['adds'].append((self._key(key), delta))
                    nb = self._wire_nbytes(delta.size, push=True)
                    if lossy:
                        parts = res_parts.setdefault(
                            name,
                            [None] * len(self._shard_info(name)[1]))
                        parts[i] = delta - cc.wire_roundtrip(
                            delta, push_wire)
                wire_bytes += nb
                ep_bytes[ep] += nb
        if lossy:
            # Reassemble and retire residuals: a zero compensated delta
            # means the accumulated error was fully flushed (or never
            # existed); merge partitioned shards back to logical shape.
            for name in zero_skip:
                self._push_residual.pop(name, None)
            for name, parts in res_parts.items():
                pc, _ = self._shard_info(name)
                new_res[name] = parts[0] if pc is None else \
                    pc.merge(parts)
            for name, res in new_res.items():
                if np.any(res):
                    self._push_residual[name] = res
                else:
                    self._push_residual.pop(name, None)

        def push_group(job):
            def go(client):
                for key, g, rule, params in job['steps']:
                    client.vstep(key, g, rule, params)
                if job['adds']:
                    client.vmadd(job['adds'])
                if job['sadds']:
                    client.vmsadd(job['sadds'])
            return go

        self._pool.run([(ep, push_group(job))
                        for ep, job in ep_jobs.items()])
        self._shared_pushes += sum(1 for n in pulled if n in shared_push)

        # post-update assign (proxy_variable.py:163-190): refresh the
        # proxy from the PS after the push, off the pre-step path. A
        # sparse push refreshes only ITS rows (vmgetrows) — rows other
        # workers touched converge via the periodic full refresh
        # (AUTODIST_SPARSE_FULL_REFRESH_EVERY); a zero push leaves the
        # cache as is on the same schedule.
        push_only_bytes = wire_bytes
        refresh_bytes, refresh_ep = self._refresh_proxies(
            zero_skip, sparse_rows)
        wire_bytes += refresh_bytes
        for ep, nb in refresh_ep.items():
            ep_bytes[ep] += nb
        push_s = _time.perf_counter() - t0
        with self._stats_lock:
            if not self._ps_ep_bytes:
                self._ps_ep_bytes = [0] * len(self._ps_addrs)
            for ep, nb in enumerate(ep_bytes):
                self._ps_ep_bytes[ep] += nb
            self._ps_seconds += push_s
            self._ps_bytes += wire_bytes
            # direction split: the proxy refresh is READ traffic even
            # though it rides the push phase, so a quantized-push
            # A/B (tests/test_quantized_wire.py) compares pure push bytes
            self._ps_push_bytes += push_only_bytes
            self._ps_pull_bytes += refresh_bytes
            self._ps_phase['push_s'] += push_s
            ss = self._sparse_stats
            ss['sparse_pushes'] += len(sparse_rows)
            ss['rows_pushed'] += rows_pushed
            ss['zero_push_skips'] += len(zero_skip)
            ss['dense_bytes_avoided'] += bytes_avoided
        self._tel.record_span(
            'push_deltas', t0, push_s, step=self._step_count,
            worker=self._worker_name, bytes=wire_bytes,
            sparse=len(sparse_rows), zero_skips=len(zero_skip))
        return push_s

    def _refresh_proxies(self, zero_skip, sparse_rows):
        """Post-push proxy-cache refresh. Unpartitioned sparse-pushed
        vars with a warm cache refresh only their pushed rows
        (BGETROWS); every ``AUTODIST_SPARSE_FULL_REFRESH_EVERY``-th
        refresh falls back to a full fetch so other workers' rows
        converge; everything else takes the legacy full fetch. Returns
        (wire bytes moved, {endpoint: bytes})."""
        if not self._proxy_vars:
            return 0, {}
        refresh_every = ENV.AUTODIST_SPARSE_FULL_REFRESH_EVERY.val
        full_names = []
        row_specs = {}   # name -> touched row indices
        for name in self._proxy_vars:
            pc, _ = self._shard_info(name)
            sparse_capable = (pc is None and name in self._proxy_cache
                              and name in self._sparse_vars)
            rowset = sparse_rows.get(name)
            if rowset is None and sparse_capable and name in zero_skip:
                rowset = np.empty(0, np.int32)
            if rowset is None or not sparse_capable:
                full_names.append(name)
                continue
            cnt = self._sparse_refresh_count.get(name, 0) + 1
            if refresh_every and cnt >= refresh_every:
                self._sparse_refresh_count[name] = 0
                full_names.append(name)
            else:
                self._sparse_refresh_count[name] = cnt
                if rowset.size:
                    row_specs[name] = rowset
        wire = 0
        ep_bytes = {}
        full_refreshes = 0
        if full_names:
            refreshed, _ = self._fetch_var_parts(full_names)
            for name, parts in refreshed.items():
                pc, _ = self._shard_info(name)
                served = parts[0] if pc is None else (
                    None if any(p is None for p in parts)
                    else pc.merge(parts))
                if served is not None:
                    var = self._graph_item.var_by_name(name)
                    self._proxy_cache[name] = \
                        served.astype(var.init_value.dtype)
                    wire += self._wire_nbytes(served.size)
                    # the counter tracks the SPARSE plane's full-refresh
                    # fallback; dense proxy vars full-refresh every
                    # step by design and would drown the signal
                    if name in self._sparse_vars:
                        full_refreshes += 1
                    idxs = self._shard_endpoints(name, len(parts))
                    sizes = [served.size] if pc is None else \
                        [p.size for p in parts]
                    for ep_i, sz in zip(idxs, sizes):
                        ep_bytes[ep_i] = ep_bytes.get(ep_i, 0) + \
                            self._wire_nbytes(sz)
        if row_specs:
            by_ep = {}
            for name, idx in row_specs.items():
                _, keys = self._shard_info(name)
                ep = self._shard_endpoints(name, 1)[0]
                ncols = int(
                    self._graph_item.var_by_name(name).shape[1])
                by_ep.setdefault(ep, []).append(
                    (name, self._key(keys[0]), idx, ncols))

            def fetch_rows(specs):
                def go(client):
                    arrs = client.vmgetrows(
                        [(key, idx, ncols)
                         for _, key, idx, ncols in specs])
                    return [(name, idx, a) for (name, _, idx, _), a
                            in zip(specs, arrs)]
                return go

            for got in self._pool.run(
                    [(ep, fetch_rows(specs))
                     for ep, specs in by_ep.items()]):
                for name, idx, arr in got:
                    if arr is None:   # pragma: no cover - init race
                        continue
                    cache = self._proxy_cache[name]
                    cache[idx] = arr.astype(cache.dtype)
                    nb = idx.size * 4 + self._wire_nbytes(arr.size)
                    wire += nb
                    ep = self._shard_endpoints(name, 1)[0]
                    ep_bytes[ep] = ep_bytes.get(ep, 0) + nb
        with self._stats_lock:
            self._sparse_stats['row_refreshes'] += len(row_specs)
            self._sparse_stats['rows_refreshed'] += \
                sum(i.size for i in row_specs.values())
            self._sparse_stats['full_refreshes'] += full_refreshes
        return wire, ep_bytes

    def _contract(self, fetch, stacked, split_sizes):
        """Apply the reference fetch contract to the per-replica stack."""
        if isinstance(fetch, fe.ApplyGradients):
            return None
        if isinstance(stacked, list):  # list-valued fetch (Gradients)
            return [self._local_stack(s)[0] for s in stacked]
        val = self._local_stack(stacked)
        n = self._plan.local_replicas
        local = val[0]
        # Polymorphic-dim rule (remapper.py:125-185): feeds were split and
        # the fetch still carries a per-example leading dim -> concatenate
        # across replicas.
        if split_sizes and local.ndim >= 1 and n > 1 and \
                self._looks_batched(fetch, local, split_sizes):
            return np.concatenate(list(val), axis=0)
        return local

    def _looks_batched(self, fetch, local_val, split_sizes):
        """Polymorphic-dim detection: a declared None leading dim on the
        fetch's symbolic shape; for shape-unknown computed tensors, a
        leading dim equal to the local batch split."""
        shape = getattr(fetch, 'shape', None)
        if shape is not None:
            return bool(len(shape) >= 1 and shape[0] is None)
        return local_val.shape[0] in split_sizes

    # -- step compilation --------------------------------------------------
    def _build_step(self, fetch_nodes, feed_nodes, split_flags):
        plan = self._plan
        mesh = self._mesh
        graph_item = self._graph_item

        var_specs = {name: plan.var_spec(name)
                     for name in self._var_state}
        opt_specs = {
            uid: {vname: jax.tree.map(
                lambda leaf, vn=vname: self._slot_spec(vn, leaf), state)
                for vname, state in slots.items()}
            for uid, slots in self._opt_state.items()}
        # aux leaves carry a leading per-replica dim (see _init_state)
        aux_specs = jax.tree.map(lambda _: P(AXIS_DATA), self._aux_state)
        feed_specs = [P(AXIS_DATA) if s else P() for s in split_flags]

        sharded_vars = {name for name, p in plan.var_plans.items()
                        if p.state_sharded}

        def step(var_state, opt_state, aux_state, feeds):
            shards = dict(var_state)
            full = dict(var_state)
            for name in sharded_vars:
                p = plan.var_plans[name]
                full[name] = ShardedGrad(
                    var_state[name], p.shard_axis,
                    logical_dim=p.var.shape[p.shard_axis],
                    hier_groups=plan.gather_hier_groups(p)).gather()
            # strip the per-replica leading dim for in-step aux access
            aux_local = jax.tree.map(lambda x: x[0], aux_state)
            env = fe.Env(full, dict(zip(feed_nodes, feeds)),
                         grad_sync_fn=plan.sync_gradients,
                         opt_state=opt_state, aux_state=aux_local)
            env.var_shards = shards
            env.plan = plan
            def box(v):
                if isinstance(v, ShardedGrad):
                    v = v.gather()
                return jnp.asarray(v)[None]  # stack dim for P(data)

            outs = []
            for node in fetch_nodes:
                val = fe.evaluate(node, env)
                # list-valued fetches (a Gradients node) stay a list —
                # out_specs broadcast over the subtree as a pytree prefix
                outs.append([box(v) for v in val]
                            if isinstance(val, (list, tuple)) else box(val))
            new_vars = dict(var_state)
            for name, val in env.updates.items():
                new_vars[name] = val
            new_opt = jax.tree.map(lambda x: x, opt_state)
            for uid, slots in env.opt_updates.items():
                new_opt[uid] = {**new_opt.get(uid, {}), **slots}
            new_aux = dict(aux_state)
            for k, v in env.aux_updates.items():
                new_aux[k] = jax.tree.map(lambda x: x[None], v)
            return outs, new_vars, new_opt, new_aux

        out_fetch_specs = [P(AXIS_DATA) for _ in fetch_nodes]
        mapped = _shard_map(
            step, mesh,
            (var_specs, opt_specs, aux_specs, feed_specs),
            (out_fetch_specs, var_specs, opt_specs, aux_specs))
        jitted = jax.jit(mapped, donate_argnums=(0, 1, 2))
        logging.debug('Compiled new step for %d fetches, %d feeds',
                      len(fetch_nodes), len(feed_nodes))
        return jitted

    # -- lifecycle ---------------------------------------------------------
    def close(self):
        # stop heartbeats FIRST (ADVICE r4): a beat written after the
        # run-end purge would leak a stale hb/<ns>/ key on long-lived
        # endpoints.  Workers therefore go silent before incrementing
        # 'closed', and the purger's own thread is joined before it
        # deletes the hb namespace.
        if getattr(self, '_hb_stop', None) is not None:
            self._hb_stop.set()
            thread = getattr(self, '_hb_thread', None)
            if thread is not None and thread.is_alive():
                thread.join(timeout=15.0)
        drain_err = None
        if not self._closed and self._loose and self._coord is not None:
            # our last background push must land BEFORE the done
            # marker / step sentinel (a peer released by the sentinel
            # must still see our final update). A failed final push is
            # NOT swallowed with the best-effort bookkeeping below: it
            # re-raises after peers are released and the pools closed —
            # the PS copy is missing this worker's last step.
            try:
                self._drain_pipeline()
            except Exception as e:  # noqa: BLE001 - re-raised below
                drain_err = e
                logging.error(
                    'final background PS push failed in close(): %s: %s',
                    type(e).__name__, e)
            # telemetry: flush this worker's final span batch, and on
            # the chief assemble + export the cohort trace — BOTH
            # before the purge quorum below can erase the run's
            # telemetry namespace
            if self._tel.enabled:
                try:
                    self._maybe_push_telemetry(
                        self._coord, self._step_count, final=True)
                    if self._monitor is not None:
                        # final verdict refresh over the last batches
                        # so health_stats read after close() reflects
                        # the whole run
                        self._monitor.poll()
                    if self._is_chief:
                        self.export_chrome_trace()
                except Exception as e:  # noqa: BLE001 - advisory
                    logging.warning('telemetry flush/export in close() '
                                    'failed: %s: %s',
                                    type(e).__name__, e)
            if self._is_chief:
                # the telemetry namespace must not outlive the run
                # even when the purge quorum below is never reached (a
                # peer that crashed, or a harness peer that never
                # bumps 'closed'): a reused service would replay the
                # stale batches — the per-worker batch counter hands
                # the NEXT run's collector sequence numbers that
                # decode to THIS run's spans. Collection and export
                # happened above, so nothing is lost; batch keys AND
                # the atomic counters live under <ns>/telemetry/ and
                # go together.
                try:
                    self._coord.delete_namespace(
                        self._key('telemetry/'))
                except Exception:  # noqa: BLE001 - service may be gone
                    pass
                # staged epoch-swap plans must not outlive the run
                # either, even when the purge quorum below is never
                # reached: a restarted run (same deterministic ns)
                # must never validate — let alone apply — a dead
                # cohort's staged generation
                try:
                    from autodist_tpu.runtime import swap_keys
                    swap_keys.purge_all(self._coord, self._ns)
                except Exception:  # noqa: BLE001 - service may be gone
                    pass
            self._flight.record('close', worker=self._worker_name,
                                step=self._step_count,
                                clean=drain_err is None)
            if drain_err is not None:
                # an unclean close IS a failure trigger: the PS copy is
                # missing this worker's last step and the evidence of
                # how dies with the process
                self._flight.dump('unclean_close')
            # clean shutdown is not a crash: publish a done marker so
            # peers exclude us from dead-worker checks, and advance our
            # step counter past any reachable gate bound so a peer
            # blocked on the staleness window is released
            try:
                from autodist_tpu.runtime.coord_client import \
                    CLEAN_CLOSE_STEP
                self._coord.set(
                    'done/%s' % self._key(self._worker_name), '1')
                self._coord.publish_step(self._worker_name,
                                         CLEAN_CLOSE_STEP,
                                         prefix=self._key('step/'))
                # run-end cleanup (ADVICE r3): the LAST worker out
                # purges the run's namespace from the coord service and
                # every PS endpoint — a reused long-lived endpoint must
                # not accumulate dead runs' multi-hundred-MB tensors.
                # The atomic INCR makes exactly one process the purger,
                # and only after every peer has closed. Excluded
                # (fenced) peers can never increment this counter, so
                # the quorum is the ACTIVE membership — else a run that
                # excluded a dead worker would leak its namespace.
                # Adopt membership changes this process may never have
                # observed (it finished its last gated step before the
                # excluder's epoch bump): a closer counting a stale,
                # larger quorum would strand the 'closed' counter below
                # every threshold and silently skip the purge.
                epoch = self._coord.incr(self._key('epoch'), 0)
                if epoch != self._epoch_seen:
                    self._epoch_seen = epoch
                    self._refresh_membership()
                closed = self._coord.incr(self._key('closed'), 1)
                if closed >= self._active_workers():
                    purged = sum(self._pool.run(
                        [(ep, lambda c: c.delete_namespace(
                            self._ns + '/'))
                         for ep in range(len(self._pool))]))
                    coord_addr = tuple(getattr(self._coord, 'address',
                                               ()) or ())
                    if coord_addr not in [tuple(a)
                                          for a in self._ps_addrs]:
                        purged += self._coord.delete_namespace(
                            self._ns + '/')
                    for prefix in ('hb/%s/' % self._ns,
                                   'done/%s/' % self._ns):
                        self._coord.delete_namespace(prefix)
                    logging.debug('purged %d namespace entries for run '
                                  '%s', purged, self._ns)
            except Exception:  # noqa: BLE001 - service may be gone
                pass
        self._closed = True
        for pool in (getattr(self, '_pipe', None),
                     getattr(self, '_tel_pipe', None),
                     getattr(self, '_pool', None)):
            if pool is not None:
                pool.close()
        if getattr(self, '_auto_ckpt', None) is not None:
            try:
                self._auto_ckpt.close()   # drain the in-flight save
            except Exception as e:  # noqa: BLE001 - backstop teardown
                logging.warning('auto-checkpoint drain failed in '
                                'close(): %s: %s', type(e).__name__, e)
        if drain_err is not None:
            raise drain_err

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def step_count(self):
        return self._step_count

    # state access for savers / tests
    def _local_value(self, name):
        arr = self._var_state[name]
        if getattr(arr, 'is_fully_addressable', True):
            return np.asarray(self._plan.unpad_host(name, np.asarray(arr)))
        sharding = getattr(arr, 'sharding', None)
        if sharding is not None and sharding.is_fully_replicated:
            return np.asarray(arr.addressable_shards[0].data)
        # cross-process sharded state: gather (collective — every process
        # must make this call)
        from jax.experimental import multihost_utils
        return np.asarray(self._plan.unpad_host(
            name, np.asarray(multihost_utils.process_allgather(
                arr, tiled=True))))

    def get_variable_value(self, var):
        name = var.name if isinstance(var, fe.Variable) else var
        if self._loose:
            # read-your-writes at the API surface: our own background
            # push must land before the authoritative read (the
            # prefetch stays valid — a read pushes nothing)
            self._drain_pipeline(keep_prefetch=True)
            # authoritative copy lives on the variable's PS endpoint(s):
            # each shard of a partitioned variable on its own endpoint
            var_obj = self._graph_item.var_by_name(name)
            parts = self._fetch_var_parts([name])[0][name]
            pc, _ = self._shard_info(name)
            served = parts[0] if pc is None else pc.merge(parts)
            return served.astype(var_obj.init_value.dtype)
        return self._local_value(name)

    def load_variable_value(self, var, value):
        name = var.name if isinstance(var, fe.Variable) else var
        if self._loose:
            # the load supersedes both any in-flight push and the
            # prefetched pull (which would serve pre-load values)
            self._drain_pipeline()
        self._var_state[name] = self._put(
            self._plan.pad_host(name, jnp.asarray(value)),
            self._plan.var_sharding(name))
        if self._loose and self._is_chief:
            self._store_var_parts({name: value})
