"""User-facing engine: the :class:`AutoDist` object.

Reference parity (``autodist/autodist.py:297-322``): construct with a
resource-spec YAML + a strategy builder; capture the model under
``.scope()``; then either ``create_distributed_session()`` (TF1-style) or
``.function()`` (TF2-style). Chief/worker identity comes from the
``AUTODIST_WORKER`` env flag (autodist.py:40-41): the chief builds and
serializes the strategy, workers deserialize it by ``AUTODIST_STRATEGY_ID``
(autodist.py:100-109) and every process independently lowers it
(docs/design/architecture.rst:43-48).
"""
import atexit
import base64
import json
import os
import time

import numpy as np

from autodist_tpu.const import DEFAULT_COORD_PORT, ENV
from autodist_tpu.frontend import graph as fe
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.parallel.mesh import mesh_from_strategy
from autodist_tpu.parallel.plan import ExecutionPlan
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runtime.cluster import Cluster
from autodist_tpu.runtime.session import Session
from autodist_tpu.strategy import base as strategy_base
from autodist_tpu.strategy.builders import PSLoadBalancing
from autodist_tpu.utils import logging

IS_AUTODIST_WORKER = bool(ENV.AUTODIST_WORKER.val)
IS_AUTODIST_CHIEF = not IS_AUTODIST_WORKER

_DEFAULT_AUTODIST = {}


def set_default_autodist(o):
    """Register the process's AutoDist instance (one per process)."""
    if os.getpid() in _DEFAULT_AUTODIST:
        raise NotImplementedError(
            'Currently only one AutoDist instance is allowed in one process.')
    _DEFAULT_AUTODIST[os.getpid()] = o


def get_default_autodist():
    return _DEFAULT_AUTODIST.get(os.getpid(), None)


def _default_resource_info(devices=None):
    """Single-node spec over ``devices`` (default: the locally visible
    jax devices). Device entries are ORDINALS (what the DeviceResolver
    indexes), not jax device ids; a real accelerator names its
    ``device_kind`` so the topology takes that kind's row (or fails on
    an unknown one)."""
    import jax
    devs = list(devices) if devices is not None else jax.local_devices()
    node = {'address': 'localhost', 'chief': True, 'cpus': [0],
            'network_bandwidth': 100}
    info = {'nodes': [node]}
    if devs[0].platform == 'cpu':
        node['gpus'] = list(range(len(devs)))  # virtual CPU devices
    else:
        node['tpus'] = list(range(len(devs)))
        info['topology'] = {'device_kind': devs[0].device_kind}
    return info


class AutoDist:
    """Distributed-training engine with minimal-code-change ergonomics.

    Args:
        resource_spec_file: path to a resource spec YAML (reference format,
            plus optional ``tpus:`` / ``mesh:`` keys). Defaults to a
            single-node spec over all local devices.
        strategy_builder: a StrategyBuilder (default PSLoadBalancing, as in
            the reference autodist.py:70).
    """

    def __init__(self, resource_spec_file=None, strategy_builder=None,
                 resource_info=None):
        set_default_autodist(self)
        if resource_spec_file is None and resource_info is None and \
                ENV.SYS_RESOURCE_PATH.val:
            # reference const.py:55-89: SYS_RESOURCE_PATH supplies the
            # resource spec when the ctor doesn't
            resource_spec_file = ENV.SYS_RESOURCE_PATH.val
        if resource_spec_file is not None:
            self._resource_spec = ResourceSpec(
                resource_file=resource_spec_file)
        else:
            self._resource_spec = ResourceSpec(
                resource_info=resource_info or _default_resource_info())
        self._strategy_builder = strategy_builder or PSLoadBalancing()
        self._original_graph_item = None
        self._transformed = None      # (strategy, mesh, plan)
        self._session = None
        self._cluster = Cluster(self._resource_spec)
        self._built = False
        self._coord = None            # coord-service client (multi-process)
        self._coord_proc = None       # service process if we started it
        # captured BEFORE this object mutates the env: a launcher
        # (launch_cli / pod runtime) marks its processes with
        # AUTODIST_PROCESS_ID; the ssh-launch chief sets it later itself.
        self._ext_launched = \
            os.environ.get(ENV.AUTODIST_PROCESS_ID.name) is not None
        # ad.function state
        self._fn_cache = {}

    # -- capture -----------------------------------------------------------
    def scope(self):
        """Context manager capturing the code block to be distributed
        (reference autodist.py:309-322)."""
        self._original_graph_item = GraphItem(graph=fe.Graph())
        return self._original_graph_item.graph

    # -- strategy ----------------------------------------------------------
    def build_strategy(self):
        """Build the Strategy for the captured graph (autodist.py:91-98)."""
        return self._strategy_builder.build(
            self._original_graph_item, self._resource_spec)

    def _build_or_load_strategy(self):
        self._original_graph_item.prepare()
        if IS_AUTODIST_CHIEF:
            s = self.build_strategy()
            s.serialize()
            if self._coord is not None:
                # publish for same-binary (pod-style) workers that have no
                # pre-set strategy id (the coordinator's scp equivalent);
                # keys carry the launcher's run nonce so a stale/reused
                # service cannot serve a previous run's strategy
                ns = ENV.AUTODIST_RUN_ID.val
                blob = base64.b64encode(str(s).encode()).decode()
                self._coord.set('strategy/%s/blob' % ns, blob)
                self._coord.set('strategy/%s/id' % ns, s.id)
        else:
            strategy_id = ENV.AUTODIST_STRATEGY_ID.val
            if strategy_id:
                s = strategy_base.Strategy.deserialize(strategy_id)
            elif self._coord is not None:
                ns = ENV.AUTODIST_RUN_ID.val
                self._coord.wait_key('strategy/%s/id' % ns,
                                     timeout_s=120.0)
                blob = self._coord.get('strategy/%s/blob' % ns)
                d = json.loads(base64.b64decode(blob).decode())
                s = strategy_base.Strategy.from_dict(d)
            else:
                raise RuntimeError(
                    'Worker process needs AUTODIST_STRATEGY_ID set (or a '
                    'coord service to fetch the strategy from)')
        return s

    def _compile_strategy(self, strategy, resolver=None, compiler=None):
        logging.debug('Raw strategy: %s', strategy)
        if compiler is None:
            compiler = strategy_base.StrategyCompiler(
                self._original_graph_item)
        if resolver is not None:
            compiler.set_device_resolver(resolver)
        compiled = compiler.compile(strategy)
        logging.info('Compiled strategy: %s', compiled)
        return compiled

    @property
    def _externally_launched(self):
        """True when a launcher (launch_cli / pod runtime) already started
        one process per host — the chief must not re-launch over ssh."""
        return self._ext_launched

    def _ensure_control_plane(self):
        """Bring up / connect to the native coord service (multi-process
        runs only). The chief starts it; every process gets a client."""
        nodes = list(self._resource_spec.nodes)
        multi = ENV.AUTODIST_NUM_PROCESSES.val > 1 or len(nodes) > 1
        if not multi or self._coord is not None:
            return
        if IS_AUTODIST_CHIEF and not self._externally_launched:
            # ssh-launch mode: claim identity before workers exist
            os.environ.setdefault(ENV.AUTODIST_NUM_PROCESSES.name,
                                  str(len(nodes)))
            os.environ.setdefault(ENV.AUTODIST_PROCESS_ID.name, '0')
        from autodist_tpu.runtime import coord_client
        from autodist_tpu.runtime.cluster import is_local_address
        addr = ENV.AUTODIST_COORD_SERVICE_ADDR.val or \
            '%s:%d' % (self._resource_spec.chief, DEFAULT_COORD_PORT)
        host, port = addr.rsplit(':', 1)
        # The chief process runs on the chief node by definition (identity
        # is env-based), so it hosts the service whenever the configured
        # host names its own node — even if that NIC IP is not locally
        # recognizable (Debian 127.0.1.1-style hostname resolution).
        chief_hosts_service = IS_AUTODIST_CHIEF and (
            host == self._resource_spec.chief or is_local_address(host))
        all_local = all(is_local_address(n) for n in nodes)
        if chief_hosts_service:
            bind = '127.0.0.1' if all_local else '0.0.0.0'
            self._coord_proc = coord_client.ensure_service(
                int(port), bind=bind)
            if self._coord_proc is not None and \
                    not self._externally_launched:
                # ssh-launch mode: the chief owns the service lifetime.
                # Externally-launched runs (launch_cli / pod): the launcher
                # (or the next run, which reuses a still-listening service)
                # owns it — the chief may finish while workers still need
                # it, so it must not tear it down here.
                atexit.register(self._coord_proc.terminate)
        # all-local runs bind the service to loopback (ADVICE r1: don't
        # expose an unauthenticated service on the NIC), so every process
        # must also CONNECT via loopback even when the spec names the
        # node by its NIC IP
        connect_host = '127.0.0.1' if all_local else host
        self._coord = coord_client.connect_with_retry(
            (connect_host, int(port)))
        # PS data-plane endpoints (loose mode): every process brings up
        # the endpoints local to ITS host (ensure_service is idempotent,
        # so co-located processes race benignly) — endpoints on non-chief
        # PS nodes are started by the worker process running there;
        # variables land on the endpoint their reduction_destination maps
        # to (session.assign_ps_endpoints) — the reference's
        # one-tf.Server-per-PS-node layout (utils/server_starter.py:48-75).
        for ep_host, ep_port in coord_client.ps_endpoints():
            if is_local_address(ep_host):
                proc = coord_client.ensure_service(
                    ep_port, bind='127.0.0.1' if all_local else '0.0.0.0')
                if proc is not None and not self._externally_launched:
                    atexit.register(proc.terminate)
        if self._externally_launched and not ENV.AUTODIST_STRATEGY_ID.val:
            # Co-started processes (launch_cli / pod) exchange the
            # strategy through coord-service keys: clear any stale keys a
            # reused service may hold BEFORE anyone waits on them; the
            # barrier guarantees no worker reads until the chief's
            # deletes have landed. ssh-launched workers carry
            # AUTODIST_STRATEGY_ID and never touch these keys — and the
            # ssh chief (which launches them only later) is not a party,
            # so they must NOT join this barrier.
            ns = ENV.AUTODIST_RUN_ID.val
            if IS_AUTODIST_CHIEF:
                self._coord.delete('strategy/%s/id' % ns)
                self._coord.delete('strategy/%s/blob' % ns)
                # a reused service may hold a PREVIOUS run's init-done
                # marker: left in place it would let this run's workers
                # skip the barrier below and read strategy keys before
                # the deletes above have landed
                self._coord.delete('ctrl/init-done/%s' % ns)
                self._coord.barrier('ctrl/init/%s' % ns,
                                    ENV.AUTODIST_NUM_PROCESSES.val,
                                    timeout_s=120.0)
                # elastic rejoin: record that the init rendezvous
                # happened, so a supervised REPLACEMENT worker started
                # after a crash doesn't block on a barrier its original
                # cohort already passed (the strategy keys are stable
                # from here on)
                self._coord.set('ctrl/init-done/%s' % ns, '1')
            elif ENV.AUTODIST_ELASTIC_JOIN.val:
                # a live JOINer (elastic scale-up) starts, by
                # definition, after the cohort's init rendezvous: it is
                # not a party the chief counted, so joining the barrier
                # would poison its arrival count — wait for the marker
                # directly (the Session-level admit handshake then
                # waits for session/init-done the same way)
                self._coord.wait_key('ctrl/init-done/%s' % ns,
                                     timeout_s=120.0)
            else:
                # A worker cannot locally distinguish "fresh cohort
                # member" from "supervised replacement whose cohort
                # already passed this barrier", so it ALWAYS tries the
                # barrier first and consults the init-done marker only
                # between bounded slices. Reading the marker up front
                # would race the chief's stale-marker delete above: on
                # a reused service holding a previous run's marker, a
                # fresh worker arriving before the chief could skip the
                # rendezvous the chief is counting it into and read
                # strategy keys mid-delete. A replacement pays one
                # slice of latency before the marker releases it; a
                # replacement of a worker that died BEFORE the
                # rendezvous simply fills the dead slot (no marker
                # exists yet, and the cohort needs its arrival).
                deadline = time.time() + 120.0
                while True:
                    try:
                        self._coord.barrier(
                            'ctrl/init/%s' % ns,
                            ENV.AUTODIST_NUM_PROCESSES.val,
                            timeout_s=min(10.0, max(
                                1.0, deadline - time.time())))
                        break
                    except TimeoutError:
                        if self._coord.get(
                                'ctrl/init-done/%s' % ns) is not None:
                            break
                        if time.time() >= deadline:
                            raise

    @staticmethod
    def _strategy_is_loose(strategy):
        """True when every synchronizer is relaxed-consistency PS
        (staleness>0 or sync=False): processes then run independent local
        programs and meet only at the coord-service PS (the reference's
        between-graph execution with accumulator num_required=1,
        ps_synchronizer.py:387-458)."""
        syncs = []
        for node in strategy.node_config:
            syncs.extend(node.part_config if node.part_config
                         else [node.synchronizer])
        ps = [s for s in syncs
              if isinstance(s, strategy_base.PSSynchronizer)]
        if len(ps) != len(syncs) or not ps:
            return False
        return all(s.staleness > 0 or not s.sync for s in ps)

    def _setup(self, strategy):
        """Chief-side cluster bring-up + worker launch (reference
        autodist.py:120-128).

        Order matters: workers must be launched BEFORE the blocking
        ``jax.distributed.initialize`` in ``cluster.start()`` — the
        runtime only forms once the full quorum dials in."""
        nodes = list(self._resource_spec.nodes)
        if IS_AUTODIST_CHIEF and len(nodes) > 1 and \
                not self._externally_launched:
            from autodist_tpu.runtime.coordinator import Coordinator
            self._coordinator = Coordinator(
                strategy, self._resource_spec, self._cluster)
            self._coordinator.launch_clients()
            atexit.register(self._coordinator.terminate)

    def _build(self):
        from autodist_tpu.utils import visualization as viz
        self._ensure_control_plane()
        # phase dumps (reference graph_transformer.py:62-90 logs the graph
        # after each transform phase; AUTODIST_DUMP_GRAPHS gates ours)
        dumping = ENV.AUTODIST_DUMP_GRAPHS.val
        if dumping:
            viz.log_text('\n'.join(
                repr(n) for n in self._original_graph_item.graph.nodes),
                '0-original-capture')
        strategy = self._build_or_load_strategy()
        if dumping:
            viz.log_text(strategy, '1-strategy')
        self._setup(strategy)
        from autodist_tpu.runtime.device_resolver import DeviceResolver
        # prune BEFORE the loose/SPMD mode decision: nodes for vars this
        # graph doesn't have must not decide the execution mode
        compiler = strategy_base.StrategyCompiler(self._original_graph_item)
        strategy = compiler.prune(strategy)
        loose = ENV.AUTODIST_NUM_PROCESSES.val > 1 and \
            self._strategy_is_loose(strategy)
        if loose:
            # relaxed-consistency PS: independent local programs + host PS;
            # no global SPMD runtime to form
            import jax
            logging.info('Relaxed-consistency PS strategy: loose '
                         'multi-process mode (local mesh + coord-service '
                         'PS data plane)')
            devices = jax.local_devices()
        else:
            self._cluster.start()
            devices = None  # mesh_from_strategy uses the global view
        resolver = None if loose else DeviceResolver(self._resource_spec)
        compiled = self._compile_strategy(strategy, resolver=resolver,
                                          compiler=compiler)
        if resolver is not None and not self._resource_spec.mesh_hint:
            # the resolved replica list decides the mesh's device order
            # and subset (reference resolver.py:47-67 feeds TF placement)
            sel = resolver.jax_devices_for(compiled.graph_config.replicas)
            if sel is not None:
                devices = sel
        mesh = mesh_from_strategy(compiled, self._resource_spec,
                                  devices=devices)
        if dumping:
            viz.log_text(compiled, '2-compiled-strategy')
        plan = ExecutionPlan(compiled, self._original_graph_item, mesh,
                             loose=loose,
                             topology=self._resource_spec.topology)
        described = plan.describe()
        logging.info(described)
        if dumping:
            viz.log_text(described, '3-execution-plan')
        self._transformed = (compiled, mesh, plan)
        self._built = True

    def is_built(self):
        return self._built

    # -- execution ---------------------------------------------------------
    def create_distributed_session(self):
        """Create the distributed Session (reference autodist.py:191-198)."""
        if not self.is_built():
            self._build()
        _, _, plan = self._transformed
        self._session = Session(self._original_graph_item, plan,
                                cluster=self._cluster, coord=self._coord)
        atexit.register(self._session.close)
        return self._session

    def function(self, fn):
        """TF2-style wrapper (reference autodist.py:269-289): ndarray args
        become placeholders (first dim batch-polymorphic), the traced
        fetches run through a distributed session on every call."""
        def wrapper(*args, **kwargs):
            key = id(fn)
            if key not in self._fn_cache:
                # the entry holds a strong ref to fn: id() stays unique
                # for as long as the cache key exists (no id-reuse alias)
                self._fn_cache[key] = (fn,
                                       self._build_fn(fn, *args, **kwargs))
            return self._fn_cache[key][1](*args, **kwargs)
        return wrapper

    def _build_fn(self, fn, *args, **kwargs):
        # Later functions (session already live) extend the SAME graph and
        # share the session; the strategy was built from the variables seen
        # at first build, so a later trace may reuse variables but not
        # introduce new ones (the strategy has no node_config for them).
        # Snapshot FIRST (before placeholder creation) so a rejected trace
        # rolls back completely — orphan nodes would trip the mutation
        # guard and orphan variables break var-state iteration.
        graph = self._original_graph_item.graph
        extending = self._session is not None
        nodes_before = len(graph.nodes)
        vars_before = set(graph.variables)
        pairs_before = dict(graph.grad_target_pairs)
        opts_before = len(graph.optimizers)
        savers_before = len(graph.savers)
        ph_index = {}
        args_ph, kwargs_ph = [], {}
        for i, a in enumerate(args):
            if isinstance(a, np.ndarray):
                ph = fe.Placeholder((None,) + a.shape[1:],
                                    a.dtype, name='arg%d' % i)
                ph_index[ph] = i
                args_ph.append(ph)
            else:
                args_ph.append(a)
        for k, v in kwargs.items():
            if isinstance(v, np.ndarray):
                ph = fe.Placeholder((None,) + v.shape[1:], v.dtype,
                                    name='kwarg_%s' % k)
                ph_index[ph] = k
                kwargs_ph[k] = ph
            else:
                kwargs_ph[k] = v
        def _rollback():
            del graph.nodes[nodes_before:]
            for name in set(graph.variables) - vars_before:
                del graph.variables[name]
            graph.grad_target_pairs = pairs_before
            del graph.optimizers[opts_before:]
            # a Saver constructed inside a failed trace references
            # rolled-back variables — drop it with the trace
            del graph.savers[savers_before:]

        try:
            with graph:
                fetches = fn(*args_ph, **kwargs_ph)
        except Exception:
            # a partially-traced function must not poison the shared
            # graph: orphan nodes trip the mutation guard (extending) or
            # leave duplicate-variable landmines for a retried first trace
            _rollback()
            raise
        if extending:
            new_vars = set(graph.variables) - vars_before
            if new_vars:
                _rollback()
                raise ValueError(
                    "a later 'autodist.function' created new variables %s "
                    "after the strategy was built; create all variables "
                    "under the first traced function (or one scope) so "
                    "the strategy covers them" % sorted(new_vars))
            session = self._session
            session.refresh_mutation_guard()
        else:
            session = self.create_distributed_session()

        def run_fn(*args, **kwargs):
            feed = {}
            for ph, idx in ph_index.items():
                feed[ph] = args[idx] if isinstance(idx, int) \
                    else kwargs[idx]
            return session.run(fetches, feed)
        return run_fn
