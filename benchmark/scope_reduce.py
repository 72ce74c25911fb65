"""Device time by what the program was doing, read from the names the
program gives its compiled step (PR 24).

A device event of the trace carries the HLO text of its operation, not
its metadata; the tie from an operation's head (``%fusion.12``) to a
name is the compiled step's text (``run['hlo']``), whose lines end in
``metadata={op_name="jit(step_fn)/..."}``. What the name looks like on
this installation (jax 0.9.0; lines of the real TPU step are in
``tests/benchmark_harness/test_benchmark_scopes.py``):

* ``jit(step_fn)/jvp(embed)/...``: the loss's first forward pass. JAX
  wraps the OUTERMOST ``jax.named_scope`` of the differentiated
  function in ``jvp(...)``; inside the layer scan the wrapper is empty
  and the scopes are path components:
  ``jit(step_fn)/jvp()/while/body/closed_call/block/mlp/dot_general``;
* ``jit(step_fn)/transpose(jvp(...))/...``: the backward pass;
* ``.../transpose(jvp())/while/body/closed_call/checkpoint/
  rematted_computation/block/...``: inside the backward pass, the
  forward operations that ``jax.checkpoint`` runs again. That path
  component, and nothing else, tells recomputation from backward;
* ``jit(step_fn)/optimizer/...``: outside the gradient;
* ``.../block/attention/flash_fwd/pallas_call``: a kernel, named by
  ``pallas_call(name=...)``, which also makes its head ``%flash_fwd.14``.

An operation is attributed by its OWN ``op_name``: a fusion that XLA
built from operations of two classes counts whole under the name XLA
kept for it. The names below are the benchmark's own list; it imports
nothing from the program.

Time is summed as ``trace_reduce`` sums it: the union of the events'
intervals inside the window, containers (``while``, ``call``) left
out, per chip, mean over the chips. The synchronous operations of one
chip do not overlap once the containers are out, so the classes add up
to the busy time.
"""
import re

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import flash_ms_per_step as flash
from benchmark.layer_metrics.flash_roofline_pct import call_cost

PHASES = ('forward', 'recompute', 'backward', 'optimizer')
COMPONENTS = ('embed', 'attention', 'mlp', 'head_loss')
KERNELS = ('flash_fwd', 'flash_dq', 'flash_dkv')
REMAT = 'rematted_computation'

_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_SCOPE_SEP = re.compile(r'[/()]')

STALE = ('the compiled step carries none of the program\'s scope names '
         '(a program without them, or its executable served from the '
         'compile cache): nothing to read')


def classify(op_name):
    """``(phase, component, kernel)`` of one ``op_name``; each is
    ``None`` where the name says nothing of it. The component is the
    innermost of :data:`COMPONENTS` on the path."""
    if not op_name:
        return None, None, None
    # XLA joins the names of merged operations with ';': the first
    op_name = op_name.split(';', 1)[0]
    # the last path component is the primitive (`transpose`, `add`)
    scopes = _SCOPE_SEP.split(op_name.rpartition('/')[0])
    if REMAT in scopes:
        phase = 'recompute'
    elif 'transpose(' in op_name:
        phase = 'backward'
    elif 'jvp(' in op_name:
        phase = 'forward'
    elif 'optimizer' in scopes:
        phase = 'optimizer'
    else:
        phase = None
    component = next((s for s in reversed(scopes) if s in COMPONENTS),
                     None)
    kernel = next((s for s in scopes if s in KERNELS), None)
    return phase, component, kernel


def op_classes(hlo):
    """``{head: (phase, component, kernel)}`` for every instruction of a
    compiled step's text."""
    classes = {}
    for line in hlo.splitlines():
        line = line.strip()
        if line.startswith('ROOT '):
            line = line[len('ROOT '):]
        if not line.startswith('%') or ' = ' not in line:
            continue
        m = _OP_NAME.search(line)
        classes[tr.op_head(line)] = classify(m.group(1) if m else None)
    return classes


def has_program_scopes(classes):
    """Whether any operation carries one of the names the program
    gives (not just JAX's ``jvp``/``transpose`` wrappers). A step served
    from a compile cache that an older program filled has none: JAX's
    cache key leaves the names out."""
    return any(phase == 'optimizer' or component or kernel
               for phase, component, kernel in classes.values())


def split_ns(trace, classes, label):
    """``{label: nanoseconds}`` inside the window, mean over the chips;
    ``label`` maps an event's ``(phase, component, kernel)`` to its
    class (``None`` collects the rest)."""
    nothing = (None, None, None)
    total = {}
    for chip in trace.ops:
        groups = {}
        for e in tr.work_ops(trace, chip):
            key = label(classes.get(tr.op_head(e.name), nothing))
            groups.setdefault(key, []).append(e)
        for key, events in groups.items():
            total[key] = total.get(key, 0.0) + tr.union_ns(
                tr.clip(events, trace.window))
    return {key: ns / len(trace.ops) for key, ns in total.items()}


def phase_ms(trace, run, phase):
    """Milliseconds a step of one of :data:`PHASES`. Forward, recompute
    and backward read JAX's own wrappers and survive an executable
    without the program's names; the optimizer needs its scope."""
    if not trace.ops:
        return None
    classes = op_classes(run['hlo'])
    split = split_ns(trace, classes, lambda c: c[0])
    if phase == 'optimizer' and not has_program_scopes(classes):
        run['say']('optimizer_ms_per_step: ' + STALE)
        return None
    if not any(p in split for p in PHASES):
        run['say']('%s_ms_per_step: no operation of the trace has a '
                   'jvp(...), transpose(...) or optimizer name' % phase)
        return None
    return split.get(phase, 0.0) / trace.steps / 1e6


def unattributed_pct(trace, run):
    """Share of the busy time in operations that belong to none of
    :data:`PHASES`."""
    if not trace.ops:
        return None
    classes = op_classes(run['hlo'])
    split = split_ns(trace, classes, lambda c: c[0])
    if not has_program_scopes(classes):
        run['say']('unattributed_device_pct: the compiled step carries '
                   'none of the program\'s scope names, so the '
                   'optimizer\'s operations count as unattributed')
    busy = sum(split.values())
    run['say']('phases, ms a step: %s of %.3f busy'
               % (', '.join('%s %.3f' % (p or 'unattributed',
                                         ns / trace.steps / 1e6)
                            for p, ns in sorted(
                                split.items(), key=lambda kv: -kv[1])),
                  busy / trace.steps / 1e6))
    return 100.0 * split.get(None, 0.0) / busy


def component_ms(trace, run, component):
    """Milliseconds a step under one of :data:`COMPONENTS`, all phases,
    kernels included."""
    if not trace.ops:
        return None
    classes = op_classes(run['hlo'])
    if not has_program_scopes(classes):
        run['say']('%s_ms_per_step: %s' % (component, STALE))
        return None
    split = split_ns(trace, classes, lambda c: c[1])
    return split.get(component, 0.0) / trace.steps / 1e6


def kernel_roofline_pct(trace, run, kernel, matmuls, tensors):
    """Share of its roofline one of :data:`KERNELS` reaches, as
    ``flash_roofline_pct`` for that kernel alone: a call needs
    ``matmuls`` score-sized matrix products (half under a causal mask)
    and moves ``tensors`` tensors of q's size; sizes from
    ``flash_roofline_pct.call_cost``, whose forward call is 2 and 4."""
    name = '%s_roofline_pct' % kernel
    classes = op_classes(run['hlo'])
    calls = tr.pallas_heads(run['hlo'])
    if not calls or not trace.ops:
        return None
    named = {h for h in calls if classes[h][2] in KERNELS}
    if named != calls:
        run['say']('%s: kernel calls without one of the names %s: %s'
                   % (name, ', '.join(KERNELS), sorted(calls - named)))
        return None
    mine = {h for h in calls if classes[h][2] == kernel}
    ns = flash.kernel_ns(trace, mine)
    if not ns:
        return None
    config, traffic = run['config'], run['traffic']
    n_calls = len(flash.kernel_events(trace, min(trace.ops), mine)) \
        / trace.steps
    f_flops, f_bytes = call_cost(
        batch=traffic['global_batch'] // run['chips'],
        heads=config['num_attention_heads'], seq=traffic['seq'],
        head_dim=config['hidden_size'] // config['num_attention_heads'],
        causal=config['causal'], itemsize=2, backward=False)
    flops = n_calls * matmuls * f_flops / 2
    nbytes = n_calls * tensors * f_bytes / 4
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    ms = ns / trace.steps / 1e6
    run['say']('%s: %.6g ms a step in %g calls, %.4g FLOPs, %.4g bytes; '
               'bound by %s' % (kernel, ms, n_calls, flops, nbytes,
                                'compute' if t_flops >= t_bytes
                                else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)
