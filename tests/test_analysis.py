"""The static-analysis subsystem (ISSUE 10 + ISSUE 13): the control-
plane model checker re-derives the two costliest historical protocol
bugs as counterexample traces and explores HEAD's orderings clean; the
data-plane checker does the same for the PR 1 offset-0 abort, the
PR 5 disconnect wedge and the PR 11 telemetry-cursor race; the
epoch-swap model proves the ROADMAP 2 handshake contract (verified
ordering clean, tempting-but-wrong orderings counterexample); the
fence / env / schedule lints are pinned positive on HEAD and negative
against doctored inputs; ``tools/analyze.py --all`` is the tier-1
wiring.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- protocol model checker ----------------------------------------------

def _scenario(cfg, name):
    from autodist_tpu.analysis import protocol_model as pm
    return {s.name: s for s in pm.scenarios(cfg)}[name]


def test_model_checker_head_explores_clean():
    """Every scenario under HEAD's orderings: no safety violation on
    any interleaving (incl. a crash at every point), and from every
    reachable state the cohort can still finish (liveness)."""
    from autodist_tpu.analysis import explore, protocol_model as pm
    for result in explore.check_all(pm.HEAD):
        assert result.ok, '\n'.join(
            explore.format_violation(result, v)
            for v in result.violations)
        assert result.terminals > 0   # the suite actually finishes
        assert result.states > 100    # and actually explored


def test_model_rederives_pr4_resurrection():
    """Flipping the exclude path's release back to DELETE (the pre-
    PR 4 ordering) must produce the resurrection counterexample: a
    delta-0 INCR read recreates the deleted step key at 0 and wedges
    the MINWAIT prefix-min."""
    from autodist_tpu.analysis import explore, protocol_model as pm
    result = explore.explore(_scenario(pm.PR4_RESURRECTION, 'exclude'))
    assert 'resurrection' in result.kinds(), result.kinds()
    v = [v for v in result.violations if v.kind == 'resurrection'][0]
    text = explore.format_violation(result, v)
    print('\n' + text)          # the readable event sequence
    assert 'delta-0 INCR' in text
    assert 'exclude[release]' in text
    assert any('CRASHES' in label for _, label in v.trace)
    # the trace is a numbered, per-actor event sequence
    assert text.splitlines()[1].strip().startswith('1.')


def test_model_rederives_pr6_admit_inversion():
    """Flipping the admit handshake back to publish-floor-before-
    epoch-bump (the ordering PR 6's third review fixed) must produce
    a stall whose diagnosis names the invisible frozen counter."""
    from autodist_tpu.analysis import explore, protocol_model as pm
    result = explore.explore(
        _scenario(pm.PR6_ADMIT_INVERSION, 'admit'))
    assert 'stall' in result.kinds(), result.kinds()
    v = [v for v in result.violations if v.kind == 'stall'][0]
    text = explore.format_violation(result, v)
    print('\n' + text)
    assert 'invisible frozen counter' in text
    assert 'publish adopted step floor' in text
    assert any('CRASHES' in label for _, label in v.trace)
    # the crash lands between the publish and the (never-reached)
    # epoch bump: no 'bump membership epoch' event precedes it
    labels = [label for _, label in v.trace]
    assert 'admit: bump membership epoch' not in labels


def test_model_rederives_unfenced_exclude_and_cap_race():
    """The two extra seeded orderings of the same bug class: claim
    observable before the fence lets a zombie write commit; an
    un-retired cap-raced slot survives to the terminal state."""
    from autodist_tpu.analysis import explore, protocol_model as pm
    r = explore.explore(_scenario(pm.UNFENCED_EXCLUDE, 'zombie'))
    assert 'fenced-write-commit' in r.kinds(), r.kinds()
    r = explore.explore(_scenario(pm.UNRETIRED_CAP_RACE, 'cap_race'))
    assert 'cap-slot-unretired' in r.kinds(), r.kinds()


def test_model_self_test_guards_sensitivity():
    """explore.analyze() must fail loudly if a seeded bug stops
    re-deriving — a model that cannot find the known bugs proves
    nothing by exploring clean."""
    from autodist_tpu.analysis import explore
    # sabotage: point a seeded entry at a scenario where its bug
    # cannot manifest
    saved = explore.SEEDED_BUGS
    try:
        explore.SEEDED_BUGS = ((saved[0][0], saved[0][1], 'cap_race',
                                'resurrection'),)
        findings = explore.analyze()
        assert any('lost the sensitivity' in f for f in findings)
    finally:
        explore.SEEDED_BUGS = saved


# -- data-plane model checker (ISSUE 13) ----------------------------------

def _dp_scenario(cfg, name):
    from autodist_tpu.analysis import data_plane_model as dp
    return {s.name: s for s in dp.scenarios(cfg)}[name]


def test_data_plane_head_explores_clean():
    """Every data-plane scenario under HEAD's semantics: no torn read
    surfaces clean, no zombie frame commits, no stale prefetch is
    served, no decodable batch is skipped — across every interleaving
    including crashes — and every reader/worker can always finish."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    results = [explore.explore(sc) for sc in dp.scenarios(dp.HEAD)]
    assert {r.scenario for r in results} == {
        'torn_write', 'writer_death', 'zombie_sparse', 'pipeline',
        'telemetry', 'local_sgd', 'reader_fleet',
        'reader_fleet_swap'}
    for r in results:
        assert r.ok, '\n'.join(explore.format_violation(r, v)
                               for v in r.violations)
        assert r.terminals > 0
        assert r.states > 20


def test_data_plane_rederives_pr1_offset0_abort():
    """Golden trace: flipping abort_open_seq back to any-frame (the
    pre-PR 1 rule) re-derives the torn read — a malformed offset-0
    frame clears another writer's parity bit and a reader accepts
    half-written data as clean."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(dp.PR1_OFFSET0_ABORT,
                                     'torn_write'))
    assert 'torn-read-clean' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'torn-read-clean'][0]
    text = explore.format_violation(r, v)
    print('\n' + text)
    # the trace is a numbered event sequence with the exact mechanism
    assert text.splitlines()[1].strip().startswith('1.')
    assert 'malformed offset-0 frame is rejected' in text
    assert 'opens sequence, parity goes odd' in text
    assert 'still-open write sequence' in v.diagnosis
    # and the malformed frame lands BEFORE the accept
    labels = [label for _, label in v.trace]
    assert labels.index('malformed offset-0 frame is rejected (ERR '
                        'bad payload)') < len(labels) - 1


def test_data_plane_rederives_pr5_disconnect_wedge():
    """Golden trace + the liveness diagnosis: without the disconnect-
    time SeqAborter, a writer killed between chunks wedges the reader
    on odd parity forever — and the stall diagnosis NAMES the wedged
    reader and the stuck-odd key, the way the admit-inversion
    diagnosis names the invisible frozen counter."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(dp.PR5_DISCONNECT_WEDGE,
                                     'writer_death'))
    assert 'stall' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'stall'][0]
    text = explore.format_violation(r, v)
    print('\n' + text)
    assert any('CRASHES' in label for _, label in v.trace)
    assert 'reader R is WEDGED on key T' in v.diagnosis
    assert 'stuck odd' in v.diagnosis
    assert 'died mid-sequence' in v.diagnosis
    # HEAD's SeqAborter heals exactly this: same scenario, no stall
    r2 = explore.explore(_dp_scenario(dp.HEAD, 'writer_death'))
    assert r2.ok, r2.kinds()


def test_data_plane_rederives_pr11_cursor_race():
    """Golden trace: the counter-advance cursor rule re-derives the
    telemetry batch drop — a poll racing the bump-then-write window
    skips the in-flight batch forever."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(dp.PR11_CURSOR_RACE, 'telemetry'))
    assert 'cursor-skip' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'cursor-skip'][0]
    text = explore.format_violation(r, v)
    print('\n' + text)
    labels = [label for _, label in v.trace]
    # the racing poll lands between the counter bump and the write
    bump = next(i for i, l in enumerate(labels) if 'bumps the batch '
                'counter' in l)
    land = next(i for i, l in enumerate(labels) if 'bytes land' in l)
    polls = [i for i, l in enumerate(labels) if 'monitor poll' in l]
    assert any(bump < i < land for i in polls), labels
    assert 'skipped it permanently' in v.diagnosis


def test_data_plane_rederives_swap_silent_rekey():
    """Golden trace (PR 19): dropping the snapshot-parity bracket
    around the epoch-swap re-key (``swap_parity='silent'``) lets a
    serving replica revalidate — and accept — a snapshot that mixes
    the old and new shard layouts across the swap boundary."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(dp.SWAP_SILENT_REKEY,
                                     'reader_fleet_swap'))
    assert 'swap-torn-snapshot' in r.kinds(), r.kinds()
    v = [v for v in r.violations
         if v.kind == 'swap-torn-snapshot'][0]
    text = explore.format_violation(r, v)
    print('\n' + text)
    assert text.splitlines()[1].strip().startswith('1.')


@pytest.mark.parametrize('cfg,scenario,kind', [
    ('UNLOCKED_FENCE_RECHECK', 'zombie_sparse', 'zombie-frame-commit'),
    ('NO_FLOOR_DISCARD', 'pipeline', 'stale-prefetch'),
    ('FLOOR_AFTER_PULL', 'pipeline', 'stale-prefetch'),
])
def test_data_plane_extra_seeded_orderings(cfg, scenario, kind):
    """The non-historical seeded orderings of the same classes: the
    entry-only fence check lets a zombie BSADD frame commit; serving
    a prefetch without the floor discard (or scanning the floor after
    the pull it must lower-bound) violates the serial staleness
    bound."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(getattr(dp, cfg), scenario))
    assert kind in r.kinds(), r.kinds()
    if kind == 'zombie-frame-commit':
        v = [v for v in r.violations if v.kind == kind][0]
        assert any('BSADD' in label for _, label in v.trace)
        assert any('bumps its fence' in label for _, label in v.trace)


def test_data_plane_local_sgd_window():
    """The H-step local-SGD scenario (ISSUE 16): HEAD proves the
    staleness bound (no pull observes peer state older than
    H x gate_staleness rounds) and the window-mean invariant across
    every interleaving; the sum-not-average push re-derives the
    W-fold overshoot, and a gate target scoped to train steps while
    peers publish sync rounds deadlocks every worker at its first
    gate — the mixed-scope bug forwarding AUTODIST_LOCAL_STEPS
    prevents."""
    from autodist_tpu.analysis import data_plane_model as dp, explore
    r = explore.explore(_dp_scenario(dp.HEAD, 'local_sgd'))
    assert r.ok, r.kinds()
    assert r.terminals > 0
    r = explore.explore(_dp_scenario(dp.LOCAL_SGD_SUM, 'local_sgd'))
    assert 'window-sum-divergence' in r.kinds(), r.kinds()
    v = [v for v in r.violations
         if v.kind == 'window-sum-divergence'][0]
    assert 'overshoots W-fold' in v.diagnosis
    assert any('pushes the sum window delta' in label
               for _, label in v.trace)
    r = explore.explore(_dp_scenario(dp.LOCAL_SGD_STEP_GATE,
                                     'local_sgd'))
    assert 'stall' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'stall'][0]
    assert 'blocked at the round-1 gate' in v.diagnosis


def test_data_plane_sensitivity_guard():
    """data_plane_model.analyze() must fail loudly if a seeded bug
    stops re-deriving, exactly like the control-plane checker."""
    from autodist_tpu.analysis import data_plane_model as dp
    saved = dp.SEEDED_BUGS
    try:
        dp.SEEDED_BUGS = ((saved[0][0], saved[0][1], 'telemetry',
                           'torn-read-clean'),)
        findings = dp.analyze()
        assert any('lost the sensitivity' in f for f in findings)
    finally:
        dp.SEEDED_BUGS = saved
    # every exploration (8 HEAD scenarios + 10 seeds — two of which
    # share scenario+kind) gets its own stats entry: a blowup in the
    # second pipeline seed must not hide behind the first's count
    dp.analyze()
    assert len(dp.LAST_STATS['scenarios']) == 18, dp.LAST_STATS
    assert dp.LAST_STATS['states_explored'] == sum(
        dp.LAST_STATS['scenarios'].values())


# -- epoch-swap model (ISSUE 13: the ROADMAP 2 contract) -------------------

def _es_scenario(cfg, name):
    from autodist_tpu.analysis import epoch_swap_model as es
    return {s.name: s for s in es.scenarios(cfg)}[name]


def test_epoch_swap_verified_ordering_explores_clean():
    """The documented contract ordering (stage -> ack quorum with
    nack-cancel -> boundary at prefix-min + staleness + 2 -> swap at
    the boundary check, deaths degraded via exclusion) explores clean:
    no step is ever executed under two plan generations, the cohort
    never finishes split, and every branch (including a peer crash
    anywhere) terminates."""
    from autodist_tpu.analysis import epoch_swap_model as es, explore
    for sc in es.scenarios(es.VERIFIED):
        r = explore.explore(sc)
        assert r.ok, '\n'.join(explore.format_violation(r, v)
                               for v in r.violations)
        assert r.terminals > 0
    # and the swap actually HAPPENS on some branch (not vacuous): an
    # early arm puts the boundary inside the run
    sc = _es_scenario(es.VERIFIED, 'epoch_swap')
    r = explore.explore(sc)
    assert r.states > 1000


def test_epoch_swap_before_ack_quorum_counterexamples():
    """Arming the swap without the ack quorum swaps past a peer that
    NACKed: the chief crosses the boundary onto plan N+1 while the
    peer keeps executing plan N — the mixed-plan write the handshake
    exists to prevent."""
    from autodist_tpu.analysis import epoch_swap_model as es, explore
    r = explore.explore(_es_scenario(es.SWAP_BEFORE_ACK_QUORUM,
                                     'epoch_swap_nack'))
    assert 'mixed-plan-step' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'mixed-plan-step'][0]
    text = explore.format_violation(r, v)
    print('\n' + text)
    labels = [label for _, label in v.trace]
    assert 'chief arms the swap (publishes boundary step)' in labels
    assert 'BOTH plan' in v.diagnosis
    # the verified ordering on the SAME scenario is clean (the nack
    # cancels the swap instead)
    r2 = explore.explore(_es_scenario(es.VERIFIED, 'epoch_swap_nack'))
    assert r2.ok, r2.kinds()


def test_epoch_swap_naive_boundary_counterexamples():
    """Boundary = the chief's own next step assumes everyone is at
    the chief's step; under the staleness window a peer already
    executed that step under plan N."""
    from autodist_tpu.analysis import epoch_swap_model as es, explore
    r = explore.explore(_es_scenario(es.NAIVE_BOUNDARY, 'epoch_swap'))
    assert 'mixed-plan-step' in r.kinds(), r.kinds()
    v = [v for v in r.violations if v.kind == 'mixed-plan-step'][0]
    print('\n' + explore.format_violation(r, v))
    assert 'BOTH plan' in v.diagnosis


def test_epoch_swap_sensitivity_guard():
    from autodist_tpu.analysis import epoch_swap_model as es
    saved = es.SEEDED_BUGS
    try:
        # a scenario where the wrong ordering cannot manifest
        es.SEEDED_BUGS = ((saved[1][0], saved[1][1],
                           'epoch_swap_nack', 'mixed-plan-step'),)
        findings = es.analyze()
        assert any('lost the sensitivity' in f for f in findings)
    finally:
        es.SEEDED_BUGS = saved


# -- fence-coverage lint --------------------------------------------------

_DOCTORED = '''\
// test service
//   SET <k> <v>                 -> OK
//   GET <k>                     -> VAL
//   BADD <k> <n> <w>            -> VAL
//   NEWCMD <k>                  -> OK
// Writer fencing: once superseded,
// every mutating command on the connection — SET, BADD — is
// rejected with `ERR fenced`.
#include <string>
std::string handle(const std::string& line) {
  if (cmd == "SET") {
    g_store.kv[k] = v;            // no fence check!
    return "OK";
  }
  if (cmd == "GET") { return "VAL"; }
  if (cmd == "BADD") {
    if (is_fenced(*conn)) return kFencedErr;
    return "VAL";                 // no under-tensor-lock re-check
  }
  if (cmd == "NEWCMD") { return "OK"; }
  return "ERR unknown command";
}
'''


def test_fence_lint_head_clean():
    from autodist_tpu.analysis import fence_lint
    assert fence_lint.analyze() == []


def test_fence_lint_flags_doctored_dispatcher():
    from autodist_tpu.analysis import fence_lint
    findings = '\n'.join(fence_lint.analyze(_DOCTORED))
    # unfenced mutating command
    assert 'SET' in findings and 'no fence check' in findings
    # tensor-mutating command without the under-lock re-check
    assert 'reject_fenced_under_tensor_lock' in findings
    # dispatched-but-undocumented / unclassified new command
    assert 'NEWCMD' in findings
    # a mutating command missing from the header fencing enumeration
    # is reported (the doctored header lists only SET and BADD)
    assert 'writer-fencing paragraph' in findings


def test_fence_lint_flags_missing_err_fenced_path():
    from autodist_tpu.analysis import fence_lint
    text = open(fence_lint.SRC).read()
    # strip BSTEP's under-lock re-check: both the re-check finding and
    # (once kFencedErr vanishes from the block) the ERR path finding
    broken = text.replace(
        '''  if (cmd == "BSTEP") {
    std::string k, wire, rule;''',
        '''  if (cmd == "BSTEP") {
    std::string k, wire, rule; /* doctored */''')
    assert broken != text
    block = broken[broken.index('if (cmd == "BSTEP")'):]
    doctored = broken.replace(
        'reject_fenced_under_tensor_lock(conn, k, t.get(), off_decl)',
        'false /* doctored */') if \
        'reject_fenced_under_tensor_lock' in block else broken
    findings = '\n'.join(fence_lint.analyze(doctored))
    assert 'BSTEP' in findings


def test_fence_lint_payload_bounds():
    """The generalized PR 5 hardening (ISSUE 13): dropping a request-
    size cap from payload_size(), dropping the in-block reply bound,
    or adding an unclassified payload-bearing command are all
    findings; HEAD is clean (covered by test_fence_lint_head_clean)."""
    from autodist_tpu.analysis import fence_lint
    text = open(fence_lint.SRC).read()
    # every size-declaring command has a payload_size branch on HEAD
    assert set(fence_lint.payload_size_branches(text)) >= {
        'BSET', 'BADD', 'BSTEP', 'BSADD', 'BGETROWS'}
    # drop the shared BSET/BADD/BSTEP request cap
    d1 = text.replace(
        'if (in.fail() || nbytes > kMaxPayload) return kBadPayload;',
        'if (in.fail()) return kBadPayload;')
    assert d1 != text
    f1 = '\n'.join(fence_lint.check_payload_bounds(d1))
    assert 'BSET' in f1 and 'kMaxPayload' in f1, f1
    # drop the BGETROWS reply bound (the original PR 5 bug: a 256 GB
    # nrows*ncols declaration allocated before any check)
    d2 = text.replace(
        'constexpr uint64_t kMaxElems = kMaxPayload / sizeof(float);',
        'constexpr uint64_t kMaxElems = ~0ull;')
    assert d2 != text
    f2 = '\n'.join(fence_lint.check_payload_bounds(d2))
    assert 'BGETROWS' in f2 and 'reply' in f2, f2
    # a new dispatched command that touches the request payload
    # without a PAYLOAD_BOUNDED entry forces a decision
    d3 = text.replace(
        'if (cmd == "BSTAT") {',
        'if (cmd == "NEWBLOB") { if (payload.size()) {} return "OK"; '
        '}\n  if (cmd == "BSTAT") {')
    assert d3 != text
    f3 = '\n'.join(fence_lint.check_payload_bounds(d3))
    assert 'NEWBLOB' in f3 and 'PAYLOAD_BOUNDED' in f3, f3
    # a comment mentioning the bound must NOT satisfy the lint
    assert 'kMaxPayload' in fence_lint._strip_comments(
        fence_lint.dispatched_blocks(text)['BGETROWS'])
    # ...including a /* block comment */ (coord_service.cc uses them)
    assert fence_lint._strip_comments(
        'x; /* bounded by kMaxPayload upstream */ y;\n'
        'z; // kMaxPayload here too\n') == 'x;  y;\nz; \n'


# -- env-knob lint --------------------------------------------------------

def test_env_lint_head_clean():
    from autodist_tpu.analysis import env_lint
    assert env_lint.analyze() == []


def test_env_lint_flags_undeclared_read(tmp_path):
    from autodist_tpu.analysis import env_lint
    bad = tmp_path / 'rogue.py'
    # assembled from pieces so the repo-wide scan of THIS file's source
    # does not see the doctored read forms
    env = 'os.environ'
    bad.write_text(
        "import os\n"
        "x = " + env + ".get('AUTODIST_TOTALLY"
        "_NEW_KNOB', '1')\n"
        "y = " + env + "['AUTODIST_ANOTHER"
        "_ONE']\n" +
        env + "['AUTODIST_A"
        "_WRITE'] = '1'   # writes are fine\n"
        "del " + env + "['AUTODIST_A"
        "_DELETE']         # so are deletes\n"
        "z = " + env + ".get(\n"
        "    'AUTODIST_WRAPPED"
        "_READ')           # wrapped reads still count\n")
    findings = env_lint.analyze(files=[str(bad)])
    names = '\n'.join(findings)
    assert 'AUTODIST_TOTALLY_NEW_KNOB' in names
    assert 'AUTODIST_ANOTHER_ONE' in names
    assert 'AUTODIST_WRAPPED_READ' in names
    assert 'AUTODIST_A_WRITE' not in names
    assert 'AUTODIST_A_DELETE' not in names


def test_env_lint_forwarding_classification():
    """The knobs this PR registered/forwarded are really there, and
    every ENV member is either forwarded or exempt-with-reason."""
    from autodist_tpu.analysis import env_lint
    from autodist_tpu.const import ENV
    fwd = env_lint.forwarded_env()
    for name in ('AUTODIST_SPARSE_PUSH_MAX_FRAC',
                 'AUTODIST_SPARSE_FULL_REFRESH_EVERY',
                 'AUTODIST_PP_STASH_LIMIT_MB'):
        assert name in fwd, name
    for e in ENV:
        if not e.name.startswith('AUTODIST_'):
            continue
        assert (e.name in fwd) != (e.name in env_lint.FORWARD_EXEMPT), \
            e.name
    # the newly registered knobs parse with their documented defaults
    assert ENV.AUTODIST_PP_STASH_LIMIT_MB.val == 2048.0


def test_the_vision_switches_are_gone():
    """PR 44 deleted the space-to-depth stem, DenseNet's
    dynamic-update-slice form and the fused conv + BatchNorm kernel
    with their switches: none is an ENV member, so an export of one is
    an undeclared read to this lint, and the knob page lists none."""
    from autodist_tpu.const import ENV
    gone = ('AUTODIST_S2D_STEM', 'AUTODIST_DENSENET_DUS',
            'AUTODIST_FUSED_CONV', 'AUTODIST_FUSED_CONV_MAX_ROWS')
    with open(os.path.join(REPO, 'docs', 'usage', 'env-knobs.md')) as f:
        page = f.read()
    for name in gone:
        assert name not in ENV.__members__, name
        assert name not in page, name


def test_env_lint_docs_drift(tmp_path):
    """The docs-drift invariant (ISSUE 13): an undocumented knob, a
    choice the docs never name, and a choice the docs enumerate that
    the validator rejects are all findings naming the knob and the
    missing/stale side. HEAD is clean (test_env_lint_head_clean runs
    the full analyze(), docs included)."""
    from autodist_tpu.analysis import env_lint
    # only the TOP-LEVEL docs/api is the generated mirror: a
    # hand-written nested dir named 'api' still counts as docs
    (tmp_path / 'api').mkdir()
    (tmp_path / 'api' / 'gen.md').write_text('GENERATED_PAGE')
    (tmp_path / 'usage' / 'api').mkdir(parents=True)
    (tmp_path / 'usage' / 'api' / 'auth.md').write_text(
        'AUTODIST_NESTED_KNOB explained here')
    text = env_lint.docs_text(root=str(tmp_path))
    assert 'AUTODIST_NESTED_KNOB' in text
    assert 'GENERATED_PAGE' not in text
    # const.py's real choice sets are parsed, not hand-copied
    ch = env_lint.choice_sets()
    assert ch['AUTODIST_PEER_FAILURE_POLICY'] == \
        ('fail', 'exclude', 'restart')
    assert ch['AUTODIST_STRAGGLER_POLICY'] == ('off', 'warn', 'advise')
    # AST-parsed, so call formatting cannot silently drop a knob:
    # double quotes, a renamed lambda parameter, odd whitespace
    ch = env_lint.choice_sets(src=(
        'X = (lambda raw: _choice("AUTODIST_NEW_KNOB",\n'
        '                         raw, "a", ["a", "b"]),)\n'))
    assert ch == {'AUTODIST_NEW_KNOB': ('a', 'b')}
    # a non-literal choice set degrades to a FINDING, not a no-op
    ch = env_lint.choice_sets(
        src="Y = (lambda v: _choice('AUTODIST_DYN', v, 'a', ALL),)\n")
    assert ch == {'AUTODIST_DYN': None}
    f = env_lint.check_docs(declared=set(), choices=ch, docs='')
    assert any('AUTODIST_DYN' in x and 'not a static literal' in x
               for x in f), f
    probe = {'AUTODIST_STRAGGLER_POLICY': ('off', 'warn', 'advise')}
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY', 'AUTODIST_GHOST_KNOB'},
        choices=probe,
        docs='AUTODIST_STRAGGLER_POLICY accepts off | warn here.')
    text = '\n'.join(f)
    assert 'AUTODIST_GHOST_KNOB' in text and 'missing side: docs' in \
        text
    assert "never name the choice 'advise'" in text
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='AUTODIST_STRAGGLER_POLICY is one of '
             'off|warn|advise|verbose.')
    assert any("'verbose'" in x and 'stale side: docs' in x for x in f)
    # markdown table rows (the | cell delimiter) are not enumerations
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='| `AUTODIST_STRAGGLER_POLICY` | warn | one of off / '
             'warn / advise |')
    assert f == [], f
    # ...even when the NEXT cell starts with a lowercase word (an enum
    # run must not chain through the cell boundary and flag it)
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='| `AUTODIST_STRAGGLER_POLICY` | warn | off / warn / '
             'advise | emits warnings |')
    assert f == [], f
    # escaped \| separators INSIDE a cell are still an enumeration
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='| `AUTODIST_STRAGGLER_POLICY` | one of `off` \\| '
             '`warn` \\| `verbose` |')
    assert any("'verbose'" in x for x in f), f
    # a documented LONGER knob must not satisfy its undocumented
    # prefix (the registry has real prefix pairs, e.g.
    # AUTODIST_TELEMETRY / AUTODIST_TELEMETRY_DIR)
    f = env_lint.check_docs(
        declared={'AUTODIST_TELEMETRY'}, choices={},
        docs='Set AUTODIST_TELEMETRY_DIR to choose the output dir.')
    assert any('AUTODIST_TELEMETRY is registered' in x for x in f), f
    # overlapping per-mention windows must not duplicate one stale
    # token into N identical findings
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='AUTODIST_STRAGGLER_POLICY and AUTODIST_STRAGGLER_POLICY'
             ': one of off|warn|advise|verbose.')
    assert len([x for x in f if "'verbose'" in x]) == 1, f
    # a NEIGHBORING knob's enumeration inside the ±700-char window —
    # sharing 2+ choice tokens but on its own line — is not this
    # knob's choice list; its extra members must not read as stale
    f = env_lint.check_docs(
        declared={'AUTODIST_STRAGGLER_POLICY'}, choices=probe,
        docs='AUTODIST_STRAGGLER_POLICY: one of off|warn|advise.\n'
             'AUTODIST_OTHER_POLICY: one of off|warn|error.')
    assert f == [], f


# -- schedule/plan consistency lint ---------------------------------------

def test_schedule_lint_head_clean():
    from autodist_tpu.analysis import schedule_lint
    assert schedule_lint.analyze() == []


def test_schedule_lint_flags_emission_drift():
    """An emitter that stops routing through the shared IR lowering
    (the exact class of asymmetric edit the static==traced pin can
    miss on uncovered fixtures) must be a finding."""
    from autodist_tpu.analysis import schedule_lint
    src = open(schedule_lint.PLAN_SRC).read()
    # traced side inlines its own fusion key instead of the shared one
    drifted = src.replace(
        "fusable.setdefault(bucket_fusion_key(plan, grad.dtype),\n"
        "                                   []).append(i)",
        "fusable.setdefault((plan.group, str(grad.dtype)),\n"
        "                                   []).append(i)")
    assert drifted != src
    findings = schedule_lint.check_emission_predicates(drifted)
    assert any('bucket_fusion_key' in f for f in findings)
    # static side inlines its own fusable predicate
    drifted2 = src.replace(
        'elif bucket_fusable(plan, var.dtype, size):',
        'elif plan.is_ar and plan.group is not None:')
    assert drifted2 != src
    findings = schedule_lint.check_emission_predicates(drifted2)
    assert any('bucket_fusable' in f for f in findings)
    # a traced helper hand-rolling its collective bypasses the IR
    drifted3 = src.replace(
        'return sir.execute(prog, g, AXIS_DATA)',
        'return ring_all_reduce(g, AXIS_DATA) / n')
    assert drifted3 != src
    findings = schedule_lint.check_emission_predicates(drifted3)
    assert any('schedule_ir.execute' in f for f in findings)


def test_schedule_lint_ir_algebra_and_sensitivity():
    """The IR sweep explores clean on HEAD, and the seeded wrong
    schedule (int8 boundary requantize moved inside the ICI phase)
    still produces its finding — the sensitivity guard that justifies
    trusting the clean run."""
    from autodist_tpu.analysis import schedule_lint
    from autodist_tpu.parallel import schedule_ir as sir
    assert schedule_lint.check_ir_algebra() == []
    bad = schedule_lint.seeded_counterexample()
    findings = sir.verify(bad)
    assert any('requantize' in f for f in findings), findings
    assert schedule_lint.check_ir_sensitivity() == []
    # pricing parity: program_time over the IR tracks entry_time
    assert schedule_lint.check_pricing_parity() == []


def test_schedule_lint_flags_update_sharding_drift():
    """The weight-update-sharding cross-check (ISSUE 14 extension
    contract): an emission edited on one side only — static losing the
    wus psum_scatter/all_gather pair, or the traced side losing its
    choose_update_sharding routing — must be a finding, not just a
    fixture-pin gamble."""
    from autodist_tpu.analysis import schedule_lint
    src = open(schedule_lint.PLAN_SRC).read()
    # static side loses the wus tag on its emitted pair
    drifted = src.replace('spec, n, hier=hier, wus=True)',
                          'spec, n, hier=hier)')
    assert drifted != src
    findings = schedule_lint.check_emission_predicates(drifted)
    assert any('wus tag' in f for f in findings)
    # traced side stops routing through the shared decision
    drifted2 = src.replace(
        'if self._wus_for(nbytes, dtype, cname, spec, wknob):',
        'if False:')
    assert drifted2 != src
    findings = schedule_lint.check_emission_predicates(drifted2)
    assert any('choose_update_sharding' in f for f in findings)
    # static side stops emitting the param-phase all_gather half
    drifted3 = src.replace(
        "for kind, phase in (('psum_scatter', 'grad'),\n"
        "                                ('all_gather', 'param')):",
        "for kind, phase in (('psum_scatter', 'grad'),):")
    assert drifted3 != src
    findings = schedule_lint.check_emission_predicates(drifted3)
    assert any('param-phase all-gather' in f for f in findings)


def test_schedule_lint_reshard_preconditions():
    """The shape-algebra checker itself: an all_to_all over a padded
    layout (which its tiled split cannot lower) must be flagged."""
    from autodist_tpu.analysis import schedule_lint
    from autodist_tpu.parallel.reshard import ReshardOp
    src = {'sharded': True, 'axis': 0, 'padded_dim': 10, 'pad': 1}
    dst = {'sharded': True, 'axis': 1, 'padded_dim': 4, 'pad': 0}
    op = ReshardOp(var_name='v', kind='all_to_all', src=src, dst=dst)
    problems = schedule_lint._check_op(op, src, dst, (9, 4), 2, 'probe')
    assert any('cannot lower' in p for p in problems)
    # and a bogus zero-wire claim is caught
    op2 = ReshardOp(var_name='v', kind='shard', wire_bytes=64,
                    src={'sharded': False, 'axis': None,
                         'padded_dim': None, 'pad': 0}, dst=dst)
    problems = schedule_lint._check_op(
        op2, op2.src, dst, (8, 4), 2, 'probe')
    assert any('zero-wire kind claims' in p for p in problems)


# -- tier-1 wiring: the CLI -----------------------------------------------

def test_analyze_cli_all_json():
    """`tools/analyze.py --all` exits 0 on HEAD with zero findings and
    the --json report carries schema_version, per-analyzer wall time
    and (for the model checkers) states-explored counts."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--all', '--json'],
        capture_output=True, text=True,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'}, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report['clean'] is True
    assert report['findings'] == 0
    assert report['schema_version'] >= 2
    assert set(report['analyzers']) == {'protocol', 'data-plane',
                                        'epoch-swap', 'fence', 'env',
                                        'schedule', 'swap-conformance'}
    for rec in report['analyzers'].values():
        assert rec['findings'] == []
        assert rec['elapsed_s'] >= 0
    for checker in ('protocol', 'data-plane', 'epoch-swap'):
        rec = report['analyzers'][checker]
        assert rec['states_explored'] > 100, (checker, rec)
        assert rec['scenarios'], (checker, rec)


def test_analyze_cli_selective():
    """Single-analyzer selection stays cheap (no jax import on the
    fence/env path) and exits by findings."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--fence', '--env'],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert 'fence' in r.stdout and 'env' in r.stdout
    assert 'schedule' not in r.stdout.split('analysis')[0]


def test_analyze_cli_data_plane_epoch_swap():
    """The new passes select individually and report their state
    counts in the human-readable output."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--data-plane', '--epoch-swap'],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert 'data-plane' in r.stdout and 'epoch-swap' in r.stdout
    assert 'states' in r.stdout
    assert 'protocol' not in r.stdout.split('analysis')[0]


# -- trace conformance (ISSUE 11: the dynamic twin) ------------------------

def test_conformance_clean_exclusion_trace_passes():
    """A correctly-ordered exclusion trace (fence bump -> claim ->
    release -> epoch bump) with surviving-worker publishes replays
    clean."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'fence_bind', 'worker': 'p0',
         'generation': 0},
        {'seq': 2, 'kind': 'step_publish', 'worker': 'p0', 'step': 1},
        {'seq': 3, 'kind': 'step_publish', 'worker': 'p0', 'step': 2},
        {'seq': 4, 'kind': 'fence_bump', 'worker': 'p1', 'by': 'p0'},
        {'seq': 5, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1, 'by': 'p0'},
        {'seq': 6, 'kind': 'release', 'worker': 'p1', 'by': 'p0'},
        {'seq': 7, 'kind': 'epoch_bump', 'epoch': 1, 'by': 'p0'},
        {'seq': 8, 'kind': 'step_publish', 'worker': 'p0', 'step': 3},
        {'seq': 9, 'kind': 'close', 'worker': 'p0', 'clean': True},
    ]
    assert conformance.check_events(events) == []


def test_conformance_rejects_zombie_write_and_resurrection():
    """A step publish recorded for an excluded+released worker is a
    committed zombie mutation: BOTH the fenced-write-commit and the
    resurrection invariants fire, the latter through protocol_model's
    own _check_resurrection."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'fence_bump', 'worker': 'p1'},
        {'seq': 2, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1},
        {'seq': 3, 'kind': 'release', 'worker': 'p1'},
        {'seq': 4, 'kind': 'epoch_bump', 'epoch': 1},
        {'seq': 5, 'kind': 'step_publish', 'worker': 'p1', 'step': 4},
    ]
    findings = conformance.check_events(events)
    kinds = {f.split('[')[1].split(']')[0] for f in findings}
    assert kinds == {'fenced-write-commit', 'resurrection'}
    # the resurrection diagnosis is protocol_model's own wording
    assert any('MINWAIT prefix-min' in f for f in findings)


def test_conformance_rejects_unfenced_exclude():
    """An exclusion claim with no prior fence bump is the
    UNFENCED_EXCLUDE ordering the model checker counterexamples."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1},
    ]
    (finding,) = conformance.check_events(events)
    assert 'unfenced-exclude' in finding
    assert 'UNFENCED_EXCLUDE' in finding


def test_conformance_rejects_admit_inversion_and_names_invariant():
    """ISSUE 11 acceptance: a doctored out-of-order admit trace
    (epoch bump after floor publish) is rejected with the violated
    invariant named."""
    from autodist_tpu.analysis import conformance
    doctored = [
        {'seq': 1, 'kind': 'admit_claim', 'worker': 'p2', 'world': 3},
        {'seq': 2, 'kind': 'admit_fence_bind', 'worker': 'p2',
         'generation': 0},
        {'seq': 3, 'kind': 'admit_floor_publish', 'worker': 'p2',
         'floor': 2},
        {'seq': 4, 'kind': 'admit_epoch_bump', 'worker': 'p2',
         'epoch': 1},
    ]
    (finding,) = conformance.check_events(doctored)
    assert 'admit-inversion' in finding
    assert 'no invisible frozen counter' in finding


def test_conformance_truncated_ring_suppresses_absence_rules():
    """The flight ring is bounded: when the oldest events scrolled off
    (first retained seq > 1), absence-based rules must not fire — a
    fence bump that predates the window is not a violation. Presence-
    based rules (zombie write after an in-window claim) still do."""
    from autodist_tpu.analysis import conformance
    truncated = [
        {'seq': 500, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1},
        {'seq': 501, 'kind': 'admit_floor_publish', 'worker': 'p2',
         'floor': 2},
    ]
    assert conformance.check_events(truncated) == []
    # but a zombie publish after the in-window claim still fires
    bad = truncated + [{'seq': 502, 'kind': 'step_publish',
                        'worker': 'p1', 'step': 3}]
    assert any('fenced-write-commit' in f
               for f in conformance.check_events(bad))
    # and an in-window admit claim anchors the inversion rule even on
    # a truncated ring
    anchored = truncated + [
        {'seq': 503, 'kind': 'admit_claim', 'worker': 'p3',
         'world': 4},
        {'seq': 504, 'kind': 'admit_fence_bind', 'worker': 'p3',
         'generation': 0},
        {'seq': 505, 'kind': 'admit_floor_publish', 'worker': 'p3',
         'floor': 2},
    ]
    assert any('admit-inversion' in f
               for f in conformance.check_events(anchored))


def test_conformance_run_start_resets_per_run_tracking():
    """Back-to-back sessions share one process-wide ring: a run_start
    boundary resets the checker's tracking, so run B's step 1 after
    run A's step N is not a step regression (and A's exclusions do
    not fence B's workers)."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'run_start', 'ns': 'a', 'worker': 'p0'},
        {'seq': 2, 'kind': 'step_publish', 'worker': 'p0', 'step': 11},
        {'seq': 3, 'kind': 'fence_bump', 'worker': 'p1'},
        {'seq': 4, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1},
        {'seq': 5, 'kind': 'release', 'worker': 'p1'},
        {'seq': 6, 'kind': 'epoch_bump', 'epoch': 1},
        {'seq': 7, 'kind': 'run_start', 'ns': 'b', 'worker': 'p0'},
        {'seq': 8, 'kind': 'step_publish', 'worker': 'p0', 'step': 1},
        {'seq': 9, 'kind': 'step_publish', 'worker': 'p1', 'step': 1},
    ]
    assert conformance.check_events(events) == []
    # without the boundary the same tail IS a violation set
    no_boundary = [e for e in events if e['kind'] != 'run_start']
    assert conformance.check_events(no_boundary)
    # a retained run_start ENDS truncation: everything after it is
    # complete by construction, so absence-based rules re-arm
    truncated_then_fresh = [
        {'seq': 600, 'kind': 'step_publish', 'worker': 'p0',
         'step': 9},
        {'seq': 601, 'kind': 'run_start', 'ns': 'c', 'worker': 'p0'},
        {'seq': 602, 'kind': 'exclude_claim', 'worker': 'p1',
         'claim': 1},
    ]
    (f,) = conformance.check_events(truncated_then_fresh)
    assert 'unfenced-exclude' in f


def test_conformance_admit_trail_after_run_start_still_judged():
    """Session records run_start BEFORE the elastic admit, so a real
    joiner dump carries [run_start, admit_*...] — the boundary reset
    must not swallow the only live admit trail (an inversion after
    the boundary still fires)."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'run_start', 'ns': 'n'},
        {'seq': 2, 'kind': 'admit_claim', 'worker': 'p2', 'world': 3},
        {'seq': 3, 'kind': 'admit_fence_bind', 'worker': 'p2',
         'generation': 0},
        {'seq': 4, 'kind': 'admit_floor_publish', 'worker': 'p2',
         'floor': 2},
        {'seq': 5, 'kind': 'admit_epoch_bump', 'worker': 'p2',
         'epoch': 1},
    ]
    (finding,) = conformance.check_events(events)
    assert 'admit-inversion' in finding


def test_conformance_malformed_event_is_a_finding_not_a_crash():
    """A truncated/hand-edited event missing its worker field is
    reported as malformed; the checker never dies with a traceback on
    the evidence it exists to read."""
    from autodist_tpu.analysis import conformance
    events = [
        {'seq': 1, 'kind': 'step_publish', 'step': 2},
        {'seq': 2, 'kind': 'exclude_claim', 'claim': 1},
    ]
    findings = conformance.check_events(events)
    assert len(findings) == 2
    assert all('malformed-event' in f for f in findings)


def test_conformance_monotonicity_rules():
    from autodist_tpu.analysis import conformance
    step_back = [
        {'seq': 1, 'kind': 'step_publish', 'worker': 'p0', 'step': 5},
        {'seq': 2, 'kind': 'step_publish', 'worker': 'p0', 'step': 3},
    ]
    (f,) = conformance.check_events(step_back)
    assert 'step-regression' in f
    epoch_back = [
        {'seq': 1, 'kind': 'epoch_bump', 'epoch': 2},
        {'seq': 2, 'kind': 'epoch_adopt', 'epoch': 1, 'worker': 'p0'},
    ]
    (f,) = conformance.check_events(epoch_back)
    assert 'epoch-regression' in f


def test_conformance_cli_dump_roundtrip(tmp_path):
    """`tools/analyze.py --conformance` exits by findings and the
    --json report carries them (the CI/chaos wiring)."""
    clean = {'reason': 'exclusion:p1', 'context':
             {'ns': 'n', 'worker': 'p0'},
             'events': [
                 {'seq': 1, 'kind': 'fence_bump', 'worker': 'p1'},
                 {'seq': 2, 'kind': 'exclude_claim', 'worker': 'p1',
                  'claim': 1},
                 {'seq': 3, 'kind': 'release', 'worker': 'p1'},
                 {'seq': 4, 'kind': 'epoch_bump', 'epoch': 1}]}
    good = tmp_path / 'good.json'
    good.write_text(json.dumps(clean))
    bad_events = list(clean['events'])
    bad_events.append({'seq': 5, 'kind': 'step_publish',
                       'worker': 'p1', 'step': 2})
    bad = tmp_path / 'bad.json'
    bad.write_text(json.dumps(dict(clean, events=bad_events)))
    env = {**os.environ, 'JAX_PLATFORMS': 'cpu'}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', str(good), '--json'],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout)['clean'] is True
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', str(bad), '--json'],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report['clean'] is False
    assert any('fenced-write-commit' in f for f in
               report['analyzers']['conformance']['findings'])
    # unreadable dump = a finding, not a crash
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', str(tmp_path / 'missing.json')],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1
    assert 'unreadable' in r.stdout
    # valid JSON that is NOT a dump (a span-record batch list — the
    # other file type this toolchain produces) is also a finding
    not_dump = tmp_path / 'records.json'
    not_dump.write_text(json.dumps([{'name': 'step', 't0': 1.0}]))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'analyze.py'),
         '--conformance', str(not_dump)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert 'unreadable' in r.stdout and 'Traceback' not in r.stderr
