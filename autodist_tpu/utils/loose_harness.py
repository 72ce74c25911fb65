"""Single-process loose-mode harness bootstrap.

Loose mode is a multi-process mode; driving its PS data plane from ONE
process needs a subtle env dance: the strategy build must see 2
processes (the mode decision) while the session sees 1 (no peers to
barrier with) — the same data plane either way. ``chip_smoke.py``'s
loose-mode leg and the loose-mode tests (tests/test_async_ps.py and its
siblings) all ride this helper so the dance lives in exactly one place.

This module also hosts :func:`ack_staged_swaps`, the swap-handshake
half of a SIMULATED peer: tests that fake a cohort member
with a bare coord client (publish step, heartbeat, release) must also
speak the epoch-swap ack protocol or the chief's ack quorum would
never fill.  One helper, called from every simulated-peer loop, keeps
that protocol in one place too.
"""
import os
from contextlib import contextmanager

_KNOBS = ('AUTODIST_COORD_SERVICE_ADDR', 'AUTODIST_PS_PIPELINE_DEPTH',
          'AUTODIST_NUM_PROCESSES', 'AUTODIST_PROCESS_ID')


@contextmanager
def single_process_loose_env(coord_port, depth):
    """Environment bootstrap for a single-process loose-mode run
    against the coord service on localhost ``coord_port`` at PS
    pipeline ``depth``.

    Yields a zero-arg callable to invoke AFTER ``autodist._build()``
    (which must see 2 processes → loose mode) and BEFORE
    ``create_distributed_session()`` (which must see 1 → no peers to
    barrier with). Every touched knob is restored on exit, and any
    process-default AutoDist singleton is cleared so this instance
    owns the scope.
    """
    from autodist_tpu import autodist as ad_mod
    saved = {k: os.environ.get(k) for k in _KNOBS}
    ad_mod._DEFAULT_AUTODIST.clear()
    try:
        # an earlier AutoDist in this process claimed chief identity via
        # os.environ.setdefault(AUTODIST_PROCESS_ID, '0'); a leftover
        # value would make THIS instance look externally-launched and
        # join a 2-party ctrl/init barrier nobody else attends
        os.environ.pop('AUTODIST_PROCESS_ID', None)
        os.environ['AUTODIST_COORD_SERVICE_ADDR'] = \
            '127.0.0.1:%d' % coord_port
        os.environ['AUTODIST_PS_PIPELINE_DEPTH'] = str(depth)
        os.environ['AUTODIST_NUM_PROCESSES'] = '2'

        def session_sees_one_process():
            os.environ['AUTODIST_NUM_PROCESSES'] = '1'

        yield session_sees_one_process
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def ack_staged_swaps(client, ns, worker, seen):
    """One poll of the epoch-swap handshake for a SIMULATED peer.

    Call from the simulated peer's publish loop.  ``seen`` is a
    mutable set of generations this peer already acked (owned by the
    caller so the helper stays stateless).  Any newly staged
    generation is acked unconditionally — a bare-client peer has no
    mesh to validate the plan against, and these harness peers exist
    to exercise the chief's staging/arming machinery, not the
    validator.  Returns ``(gen, boundary)`` of the latest armed
    generation (``(0, 0)`` if none) so a caller that wants to stop
    publishing near the boundary can.
    """
    from autodist_tpu.runtime import swap_keys
    gen = swap_keys.current_gen(client, ns)
    if gen <= 0:
        return 0, 0
    if gen not in seen:
        # plan may already be cancelled by the time we look; only a
        # visible payload earns an ack (matches the real peer, which
        # keys every decision off the plan's presence)
        if swap_keys.read_plan(client, ns, gen) is not None:
            swap_keys.write_ack(client, ns, gen, worker)
            seen.add(gen)
    return gen, swap_keys.read_boundary(client, ns, gen)
