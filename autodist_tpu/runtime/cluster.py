"""Cluster management: process identity + multi-host runtime bring-up.

Reference parity: ``autodist/cluster.py`` starts one ``tf.Server`` per node
over SSH and tracks chief/worker identity (:98-147). On TPU there is no
per-op RPC server — the runtime is SPMD program dispatch — so the cluster
layer's jobs reduce to:

1. identity: which process am I, who is chief (reference cluster.py:98-147);
2. bringing up ``jax.distributed`` across hosts (replacing grpc servers);
3. launching worker processes (see :mod:`autodist_tpu.runtime.coordinator`,
   the "re-run the user script on every host" trick, coordinator.py:46-90).
"""
import os
import socket

import jax

from autodist_tpu.const import DEFAULT_JAX_COORD_PORT, ENV
from autodist_tpu.utils import logging


def is_local_address(address):
    """Loopback/local-host detection (reference utils/network.py:22-57)."""
    if address in ('localhost', '0.0.0.0'):
        return True
    try:
        # any loopback /8 IP — but ONLY a literal IP ('127.foo.com' is
        # a legal remote hostname, not loopback)
        import ipaddress
        if ipaddress.ip_address(address).is_loopback:
            return True
    except ValueError:
        pass
    try:
        local = {socket.gethostname(), socket.getfqdn()}
        local_ips = set()
        try:
            local_ips.add(socket.gethostbyname(socket.gethostname()))
        except OSError:
            pass
        try:
            # primary-NIC IP (Debian-style hosts resolve the hostname to
            # 127.0.1.1, missing the real interface address); a UDP
            # connect() learns the outbound IP without sending packets
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.connect(('192.0.2.1', 9))   # TEST-NET, never routed to
            local_ips.add(s.getsockname()[0])
            s.close()
        except OSError:
            pass
        return address in local or address in local_ips
    except OSError:
        return False


class Cluster:
    """Identity + distributed-runtime bring-up for one process."""

    def __init__(self, resource_spec):
        self._resource_spec = resource_spec
        self._started = False
        worker_addr = ENV.AUTODIST_WORKER.val
        self._local_address = worker_addr or resource_spec.chief

    @property
    def is_chief(self):
        return not ENV.AUTODIST_WORKER.val

    def get_local_address(self):
        """This process's node address (reference cluster.py:98-147)."""
        return self._local_address

    @property
    def cluster_spec(self):
        """{'worker': [addr, ...]} with chief first (cluster.py:70-82)."""
        nodes = list(self._resource_spec.nodes)
        chief = self._resource_spec.chief
        ordered = [chief] + [n for n in nodes if n != chief]
        return {'worker': ordered}

    @property
    def num_nodes(self):
        return len(list(self._resource_spec.nodes))

    def start(self):
        """Initialize the distributed runtime if this is a multi-process run.

        Single-host (the common TPU-slice-per-host and all test cases):
        nothing to start — XLA owns the devices already.
        """
        if self._started:
            return
        num_procs = ENV.AUTODIST_NUM_PROCESSES.val
        if num_procs > 1:
            coord = (ENV.AUTODIST_COORDINATOR_ADDR.val or
                     self._resource_spec.coordinator_address or
                     '%s:%d' % (self._resource_spec.chief,
                                DEFAULT_JAX_COORD_PORT))
            pid = ENV.AUTODIST_PROCESS_ID.val
            # CPU backends need an explicit cross-process collectives
            # implementation (TPU ICI needs none). Must be set before
            # the backend initializes; harmless otherwise.
            jax.config.update('jax_cpu_collectives_implementation',
                              'gloo')
            logging.info('jax.distributed.initialize(%s, %d, %d)',
                         coord, num_procs, pid)
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=num_procs,
                process_id=pid)
        self._started = True

    def terminate(self):
        if self._started and ENV.AUTODIST_NUM_PROCESSES.val > 1:
            try:
                jax.distributed.shutdown()
            except Exception as e:   # noqa: BLE001 - best-effort teardown
                # best-effort, but never silent: a shutdown failure here
                # is the first clue when a later run's initialize hangs
                # on a half-dead coordinator
                logging.warning('jax.distributed.shutdown failed during '
                                'terminate (continuing): %s: %s',
                                type(e).__name__, e)
        self._started = False
