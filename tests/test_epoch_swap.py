"""Epoch-swap unit suite (PR 19, docs/design/epoch-swap.md).

The pieces of the strategy-distribution-epoch handshake that are pure
enough to pin without a cohort: the commit-boundary arithmetic against
the gate-staleness safety argument, quorum prefix-min under exclusion
(``Session._live_ack_peers`` over live membership), generation hygiene
of the ``swap/<g>/*`` key schema against a live coord service (stage
purges the previous generation, cancel deletes the subtree, purge_all
resets a restarted run to generation zero), and the tier-1
spec<->impl pins: ``swap_keys.MODEL_SYMBOLS`` against the verified
model's symbol table and the fence-lint classification of every swap
verb. The full-cohort handshake (kill-at-every-stage chaos matrix,
executed re-keying migration) lives in tests/test_chaos_recovery.py
and tests/test_reshard.py.
"""
import shutil

import pytest

from autodist_tpu.runtime import swap_keys


# -- boundary arithmetic --------------------------------------------------

class TestBoundaryArithmetic:
    def test_formula(self):
        # B = prefix_min(published) + staleness + 2
        assert swap_keys.compute_boundary([5, 7, 6], 1) == 8
        assert swap_keys.compute_boundary([0], 0) == 2
        assert swap_keys.compute_boundary([3], 4) == 9

    def test_prefix_min_not_mean_or_max(self):
        # the SLOWEST member's floor bounds the swap, not the fastest:
        # a boundary past min+staleness+1 is what makes the arm marker
        # observable to everyone before anyone starts step B
        assert swap_keys.compute_boundary([2, 100], 1) == 5

    def test_unreachable_at_arm_time(self):
        # the model's safety argument in miniature: a member executing
        # step s implies every member published >= s - staleness - 1,
        # so at arm time the fastest member runs at most
        # min(floors) + staleness + 1 — strictly before B for every
        # staleness
        for staleness in range(4):
            floors = [4, 6, 9]
            b = swap_keys.compute_boundary(floors, staleness)
            fastest_possible = min(floors) + staleness + 1
            assert fastest_possible < b

    def test_empty_floors_raise(self):
        # quorum re-evaluation dropped everyone: arming a boundary
        # over no live member is a caller bug, not a default
        with pytest.raises(ValueError, match='no live members'):
            swap_keys.compute_boundary([], 1)


# -- plan payload codec ---------------------------------------------------

class TestPlanCodec:
    def test_roundtrip(self):
        strategy = {'node_config': [1, 2], 'cost': {'builder': 'PS'}}
        payload = swap_keys.encode_plan(3, 2, strategy)
        # the coord KV value is the rest of one protocol line
        assert '\n' not in payload
        gen, world, out = swap_keys.decode_plan(payload)
        assert (gen, world, out) == (3, 2, strategy)


# -- spec <-> impl pins (tier-1: renames break here, not silently) --------

class TestSchemaPin:
    def test_key_schema_pins_to_model_symbols(self):
        from autodist_tpu.analysis import swap_conformance
        assert swap_conformance.check_schema_pin() == []

    def test_every_swap_verb_classified_in_fence_lint(self):
        from autodist_tpu.analysis import fence_lint
        assert fence_lint.check_swap_keys() == []

    def test_model_symbols_cover_the_handshake_keys(self):
        # one template per abstract symbol the model transitions on
        assert set(swap_keys.MODEL_SYMBOLS) == {
            'swap/<g>/plan', 'swap/<g>/ack/<w>', 'swap/<g>/nack/<w>',
            'swap/<g>/B'}
        assert len(set(swap_keys.MODEL_SYMBOLS.values())) == \
            len(swap_keys.MODEL_SYMBOLS)


# -- swap-conformance trace checker ---------------------------------------

class TestSwapConformance:
    def test_analyzer_self_checks_clean(self):
        # verified trace clean + every seeded trace still detected +
        # schema pin — the same contract analyze --all enforces
        from autodist_tpu.analysis import swap_conformance
        assert swap_conformance.analyze() == []

    def test_truncated_ring_suppresses_absence_rules(self):
        # an arm whose stage scrolled off a bounded ring is not a
        # violation — absence-based rules only fire on complete rings
        from autodist_tpu.analysis import swap_conformance
        events = [{'seq': 5, 'kind': 'swap_arm', 'gen': 1,
                   'boundary': 4}]
        assert swap_conformance.check_swap_events(events) == []

    def test_arm_without_stage_on_complete_ring(self):
        from autodist_tpu.analysis import swap_conformance
        events = [
            {'seq': 1, 'kind': 'run_start'},
            {'seq': 2, 'kind': 'swap_arm', 'gen': 1, 'boundary': 4},
        ]
        fs = swap_conformance.check_swap_events(events)
        assert len(fs) == 1 and '[arm-without-stage]' in fs[0]

    def test_run_start_resets_generation_tracking(self):
        # run B's generation 1 after run A's generation 3 is not a
        # regression: the ring is process-wide, runs are not
        from autodist_tpu.analysis import swap_conformance
        events = [
            {'seq': 1, 'kind': 'run_start'},
            {'seq': 2, 'kind': 'swap_stage', 'gen': 3, 'world': 2},
            {'seq': 3, 'kind': 'run_start'},
            {'seq': 4, 'kind': 'swap_stage', 'gen': 1, 'world': 2},
        ]
        assert swap_conformance.check_swap_events(events) == []

    def test_boundary_mismatch_detected(self):
        from autodist_tpu.analysis import swap_conformance
        events = [
            {'seq': 1, 'kind': 'run_start'},
            {'seq': 2, 'kind': 'swap_stage', 'gen': 1, 'world': 2},
            {'seq': 3, 'kind': 'swap_arm', 'gen': 1, 'boundary': 7},
            {'seq': 4, 'kind': 'swap_apply', 'gen': 1, 'worker': 'p0',
             'boundary': 9, 'step': 9},
        ]
        fs = swap_conformance.check_swap_events(events)
        assert any('[boundary-mismatch]' in f for f in fs)


# -- generation hygiene against a live coord service ----------------------

@pytest.mark.skipif(shutil.which('g++') is None,
                    reason='g++ unavailable')
class TestGenerationHygiene:
    @pytest.fixture()
    def client(self, service):
        from autodist_tpu.runtime.coord_client import CoordClient
        return CoordClient(('127.0.0.1', service))

    def test_stage_purges_previous_generation(self, client):
        ns = 'nsswap'
        swap_keys.stage_plan(client, ns, 1, 2, {'v': 1})
        swap_keys.write_ack(client, ns, 1, 1)
        swap_keys.arm(client, ns, 1, 9)
        swap_keys.stage_plan(client, ns, 2, 2, {'v': 2})
        # exactly one staged generation visible: gen 1's plan, acks
        # and armed marker are all gone, gen 2's plan is readable
        assert swap_keys.current_gen(client, ns) == 2
        assert swap_keys.read_plan(client, ns, 1) is None
        assert swap_keys.read_boundary(client, ns, 1) == 0
        acked, nacks = swap_keys.read_acks(client, ns, 1, [1])
        assert not acked and not nacks
        assert swap_keys.read_plan(client, ns, 2) == (2, 2, {'v': 2})

    def test_cancel_deletes_subtree_not_counter(self, client):
        ns = 'nscancel'
        swap_keys.stage_plan(client, ns, 1, 2, {'v': 1})
        swap_keys.write_ack(client, ns, 1, 1)
        swap_keys.write_nack(client, ns, 1, 2, 'no')
        swap_keys.arm(client, ns, 1, 6)
        swap_keys.cancel(client, ns, 1)
        # the subtree is gone; the counter survives so the retry
        # stages a NEW generation (monotonicity)
        assert swap_keys.current_gen(client, ns) == 1
        assert swap_keys.read_plan(client, ns, 1) is None
        assert swap_keys.read_boundary(client, ns, 1) == 0
        acked, nacks = swap_keys.read_acks(client, ns, 1, [1, 2])
        assert not acked and not nacks

    def test_purge_all_resets_generation_counter(self, client):
        # the restarted-run sweep: counter included, so a fresh run
        # starts from generation 0 and can never validate stale state
        ns = 'nspurge'
        swap_keys.stage_plan(client, ns, 1, 2, {'v': 1})
        swap_keys.stage_plan(client, ns, 2, 2, {'v': 2})
        swap_keys.arm(client, ns, 2, 11)
        swap_keys.purge_all(client, ns)
        assert swap_keys.current_gen(client, ns) == 0
        assert swap_keys.read_plan(client, ns, 2) is None
        assert swap_keys.read_boundary(client, ns, 2) == 0

    def test_read_acks_over_live_membership(self, client):
        # quorum re-evaluation: the caller passes the LIVE membership,
        # so an excluded peer's missing ack stops blocking the quorum
        ns = 'nsacks'
        swap_keys.stage_plan(client, ns, 1, 4, {'v': 1})
        swap_keys.write_ack(client, ns, 1, 1)
        swap_keys.write_nack(client, ns, 1, 2, 'bad plan')
        swap_keys.write_ack(client, ns, 1, 3)
        acked, nacks = swap_keys.read_acks(client, ns, 1, [1, 2, 3])
        assert acked == {1, 3} and nacks == {2: 'bad plan'}
        acked, nacks = swap_keys.read_acks(client, ns, 1, [1, 3])
        assert acked == {1, 3} and nacks == {}

    def test_garbage_boundary_reads_as_unarmed(self, client):
        ns = 'nsgarbage'
        client.set('%s/swap/1/B' % ns, 'notanint')
        assert swap_keys.read_boundary(client, ns, 1) == 0

    def test_ack_staged_swaps_helper(self, client):
        # the simulated-peer half used by the chaos matrix and bench
        from autodist_tpu.utils.loose_harness import ack_staged_swaps
        ns = 'nshelp'
        seen = set()
        assert ack_staged_swaps(client, ns, 1, seen) == (0, 0)
        swap_keys.stage_plan(client, ns, 1, 2, {'v': 1})
        gen, boundary = ack_staged_swaps(client, ns, 1, seen)
        assert (gen, boundary) == (1, 0) and seen == {1}
        acked, _ = swap_keys.read_acks(client, ns, 1, [1])
        assert acked == {1}
        swap_keys.arm(client, ns, 1, 5)
        assert ack_staged_swaps(client, ns, 1, seen) == (1, 5)

    def test_live_ack_peers_prefix_min_under_exclusion(self, client):
        # the quorum the chief polls: live membership minus self,
        # minus done markers, minus released step sentinels, minus
        # excluded ordinals — re-evaluated on every poll
        from autodist_tpu.runtime.coord_client import CLEAN_CLOSE_STEP
        from autodist_tpu.runtime.session import Session
        stub = Session.__new__(Session)
        stub._ns = 'nspeers'
        stub._world = 4
        stub._excluded = set()
        assert stub._live_ack_peers(client) == [1, 2, 3]
        client.set('done/nspeers/p2', '1')
        assert stub._live_ack_peers(client) == [1, 3]
        client.incr('nspeers/step/p3', CLEAN_CLOSE_STEP)
        assert stub._live_ack_peers(client) == [1]
        stub._excluded.add('nspeers/p1')
        assert stub._live_ack_peers(client) == []
