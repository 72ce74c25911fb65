"""Test configuration: 8 virtual CPU devices, as the multi-device substrate.

The reference emulates independent program lifecycles with forked processes
per case (tests/integration/test_all.py:55-70); under JAX a virtual 8-device
CPU mesh replaces that dance (SURVEY.md §4 implication note).

The suite is CPU-only by construction: the platform and device count are
pinned here, before jax's backend initializes, so a test run never takes
an accelerator whatever the caller's environment says. ``XLA_FLAGS`` also
carries the device count so subprocesses the tests start inherit it.
"""
import contextlib
import os
import shutil
import socket
import subprocess

os.environ.setdefault('AUTODIST_IS_TESTING', 'True')
if 'xla_force_host_platform_device_count' not in \
        os.environ.get('XLA_FLAGS', ''):
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '') +
        ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_process_state():
    """Each test gets a clean 'process': default-autodist slot + graph stack."""
    yield
    from autodist_tpu import autodist as ad_mod
    from autodist_tpu.frontend import graph as fe
    ad_mod._DEFAULT_AUTODIST.clear()
    if hasattr(fe._GRAPH_STACK, 'stack'):
        fe._GRAPH_STACK.stack.clear()


def free_port():
    """A loopback port that was free a moment ago. Nothing holds it
    once this returns, so whoever binds it next must be ready to lose
    it to another process in the gap."""
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def shutdown_service(addr):
    """Shut down the coord service at ``host:port`` if one is there: a
    test that plays launcher owns the lifetime of the service its
    processes started (launch_cli parity)."""
    from autodist_tpu.runtime.coord_client import CoordClient
    host, port = addr.rsplit(':', 1)
    try:
        CoordClient((host, int(port)), timeout=2.0).shutdown()
    except OSError:
        pass


@contextlib.contextmanager
def coord_service(port=None, attempts=5):
    """A native coord service of the caller's own: yields its port and
    shuts it down on exit (kills it if a test already took it down).

    Starts on ``port`` (a free one by default); when the start fails
    there (the port was taken in the gap, or the start deadline was
    missed under load) or something already answers on it (another
    module's service, which is not ours to share or to shut down),
    tries another free port, ``attempts`` starts in all."""
    from autodist_tpu.runtime.coord_client import (CoordClient,
                                                   ensure_service)
    if shutil.which('g++') is None:
        pytest.skip('g++ unavailable')
    failure = None
    for _ in range(attempts):
        port = port or free_port()
        try:
            proc = ensure_service(port=port)
        except RuntimeError as e:
            proc, failure = None, e
        # None: the answer came from a service that was there before;
        # a child that is gone lost the port to whoever answered
        if proc is not None and proc.poll() is None:
            break
        port = None
    else:
        raise RuntimeError('no coord service of our own after %d starts'
                           % attempts) from failure
    try:
        yield port
    finally:
        try:
            CoordClient(('127.0.0.1', port)).shutdown()
            proc.wait(timeout=5)
        except (OSError, subprocess.TimeoutExpired):
            proc.kill()
            proc.wait(timeout=5)


@pytest.fixture(scope='module')
def coord_port():
    """The port of a coord service that lives as long as the module."""
    with coord_service() as port:
        yield port


@pytest.fixture(scope='module')
def coord(coord_port):
    """``coord(**kw)``: a new client of the module's coord service."""
    from autodist_tpu.runtime.coord_client import CoordClient
    return lambda **kw: CoordClient(('127.0.0.1', coord_port), **kw)


@pytest.fixture
def service():
    """The port of a coord service that lives as long as one test."""
    with coord_service() as port:
        yield port


def _kernel_calls(jaxpr, times=1, counts=None):
    """``{kernel name: calls}`` of the ``pallas_call``s a jaxpr makes
    when run, those of nested jaxprs included (a scan's body counts
    ``length`` times)."""
    import collections

    from jax._src import core
    counts = collections.Counter() if counts is None else counts
    for eqn in getattr(jaxpr, 'jaxpr', jaxpr).eqns:
        if eqn.primitive.name == 'pallas_call':
            counts[eqn.params['name']] += times
            continue
        inner = times * eqn.params['length'] \
            if eqn.primitive.name == 'scan' else times
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                if isinstance(sub, (core.Jaxpr, core.ClosedJaxpr)):
                    _kernel_calls(sub, inner, counts)
    return dict(counts)


@pytest.fixture
def kernel_calls():
    """Counts the Pallas kernel calls of a jaxpr by name; interpret mode
    keeps the name that the compiled call carries."""
    return _kernel_calls


@contextlib.contextmanager
def _events_of(name):
    """A list that holds, once the block ends, the loop ring's records
    called ``name`` that the block left, read by ``id`` (a count of the
    process that outlives ``reset()``): the ring keeps ``LOOP_RING``
    records, so a count of them before and after does not move once a
    new record pushes an old one of that name out. The ring is first
    filled with such records, the state that a worker's earlier files
    may or may not have left."""
    from autodist_tpu import telemetry
    from autodist_tpu.telemetry.core import LOOP_RING
    ring = telemetry.get()
    for _ in range(LOOP_RING):
        ring.loop_event(name, filler=True)
    last = max(r['id'] for r in ring.loop_records())
    events = []
    yield events
    events.extend(r for r in ring.loop_records()
                  if r['id'] > last and r['name'] == name)


@pytest.fixture
def events_of():
    """``with events_of(name) as events``: the ring's records of one
    name that the block leaves, whatever ran in this process before."""
    return _events_of
