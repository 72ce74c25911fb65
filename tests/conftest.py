"""Test configuration: 8 virtual CPU devices, as the multi-device substrate.

The reference emulates independent program lifecycles with forked processes
per case (tests/integration/test_all.py:55-70); under JAX a virtual 8-device
CPU mesh replaces that dance (SURVEY.md §4 implication note).

The suite is CPU-only by construction: the platform and device count are
pinned here, before jax's backend initializes, so a test run never takes
an accelerator whatever the caller's environment says. ``XLA_FLAGS`` also
carries the device count so subprocesses the tests start inherit it.
"""
import os

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
