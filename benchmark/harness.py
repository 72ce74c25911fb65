"""One run of one cell: set-up, the measured window, the checks, and
the contract's last line. ``run.py`` is the command; the tests call
:func:`rehearse`.

Everything that belongs to one cell, configuration, traffic mix, data
generator, model family, engine or per-layer metric is a file of its own
found by name (``workloads/``, ``configs/``, ``traffic/``,
``generators/``, ``models/``, ``engines/``, ``layer_metrics/``), so a
later PR adds files and entries of ``BENCHMARK.json`` and edits nothing
here.
"""
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

WARMUP_STEPS = 2
# The loss check compares the first five steps with the last five, so a run
# has ten or more. A cell's file may ask for more (``min_steps``) where ten
# steps' fall is inside the batches' noise: its traced run is then that
# long too, with the last ``trace_steps`` of them traced.
MIN_STEPS = 10
PROBE_PER_CHIP = 2      # sequences per chip the reference is compared on

# The program computes in bf16 (8 significant bits, a relative rounding
# of 2^-9 = 0.2% per product) and the reference in f32 at 'highest'. The
# loss is a mean over >= 1024 positions of logits that each sum 1024
# rounded products, so its error averages down: over the 42 runs of the
# three one-chip cells at full width the largest difference was 0.013%
# (PERF.md §6, PR 22), and the tolerance is 0.1%. The gradient norm sums
# squared per-element errors, which average less well: 0.16% was the
# largest, the tolerance is 2%. Both are far under what a wrong
# architecture moves: without the attention scale, the causal mask or the
# final LN the gradient norm moves by 7% to 10% even at tiny widths
# (tests/benchmark_harness/test_benchmark_reference.py).
LOSS_RTOL = 1e-3
GRAD_NORM_RTOL = 2e-2


# -- files by name ---------------------------------------------------------

def load_json(kind, name):
    path = os.path.join(HERE, kind, name + '.json')
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    return importlib.import_module('benchmark.%s.%s' % (kind, name))


def load_benchmark():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def load_peaks(device_kind):
    with open(os.path.join(HERE, 'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table:
        raise RuntimeError('device kind %r is not in benchmark/peaks.json '
                           '(has %s); add it with its source, there is no '
                           'default' % (device_kind, sorted(table)))
    return table[device_kind]


def metrics_for(cell_name, entries):
    """Names of the ``BENCHMARK.json`` metric entries that exist in this
    cell."""
    return [m['name'] for m in entries
            if 'workloads' not in m or cell_name in m['workloads']]


# -- counters and the feed -------------------------------------------------

class CompileCounter:
    """Backend compile requests, their seconds and persistent-cache hits,
    from ``jax.monitoring`` (a hit still counts as a request; it is a
    short one). Copied from ``chip_smoke.py``, PR 22."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.requests += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1

    def snapshot(self):
        return {'requests': self.requests, 'seconds': self.seconds,
                'cache_hits': self.cache_hits}


class Feed:
    """Iterator of fresh host batches that notes when ``fit`` asks.

    ``fit(prefetch=2)`` asks for batches 0, 1 and 2 before it dispatches
    step 1 and for batch ``j`` (``j >= 3``) right after it has read step
    ``j - 2``'s loss, so the times of requests 0, 3, 4, ... are the
    starts of steps 1, 2, 3, ... on the host's clock.

    With ``trace_dir`` the profiler starts at the request that opens step
    ``trace_from`` (1-based), and every step from there is a
    ``fit.step`` span with the request itself as ``data.next`` inside,
    so host spans and device operations sit on one clock.
    """

    def __init__(self, batches, trace_dir=None, trace_from=None):
        self._batches = batches
        self._asked = 0
        self.step_times = []
        self._trace_dir = trace_dir
        self._trace_request = None if trace_dir is None else (
            0 if trace_from == 1 else trace_from + 1)
        self._span = None
        self.tracing = False

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        i = self._asked
        if i >= len(self._batches):
            raise StopIteration
        self._asked += 1
        if i == self._trace_request:
            # device operations and the benchmark's own spans only: the
            # Python tracer would record every call of the host loop,
            # which slows it and makes the trace hundreds of megabytes
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=options)
            self.tracing = True
        opens_step = i == 0 or i >= 3
        if self.tracing and opens_step:
            self._close_span()
            self._span = jax.profiler.TraceAnnotation('fit.step')
            self._span.__enter__()
        if opens_step:
            self.step_times.append(time.perf_counter())
        if self.tracing:
            with jax.profiler.TraceAnnotation('data.next'):
                return self._batches[i]
        return self._batches[i]

    def _close_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def finish(self):
        """``fit`` has returned: the last step ends here."""
        import jax
        self.step_times.append(time.perf_counter())
        self._close_span()
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False


def median_step_s(step_times):
    """The median interval between two of ``step_times``: one turn of the
    user's loop as it mostly is. The device's part of a step repeats to
    0.01% and the host's part is a few milliseconds that a busy host
    stretches now and then by tens; the mean over the call carries every
    such stall into the rate (one run in 25 lost 0.25% to 0.46% on a
    quiet machine, two runs in six over 1% in the driver's check), the
    median none while fewer than half the steps are hit. A change that
    slows every step moves both alike."""
    return statistics.median(b - a for a, b in
                             zip(step_times, step_times[1:]))


# -- the run ---------------------------------------------------------------

def pick_devices(chips, platform):
    """The first ``chips`` devices of ``platform``; anything else is an
    error, never a fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError('the cell needs %d %s chip(s); JAX found only '
                           '%s devices' % (chips, platform,
                                           devices[0].platform))
    if len(devices) < chips:
        raise RuntimeError('the cell needs %d chips; JAX found %d'
                           % (chips, len(devices)))
    return devices[:chips]


def setup_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if that is
    set, else at a fixed path inside the checkout (the path is part of
    the cache key); every compile is stored, however short."""
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir',
                          os.path.join(ROOT, '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)


def hlo_facts(hlo):
    """What check (c) reads from the compiled step's text: the lines of
    the Pallas custom calls and of the collectives."""
    import re
    collective = re.compile(
        r' (all-reduce|all-gather|reduce-scatter|collective-permute|'
        r'all-to-all)(?:-start|-done)?\(')
    kernels = [line.strip() for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    collectives = [line.strip() for line in hlo.splitlines()
                   if collective.search(line)]
    return {'pallas_custom_calls': len(kernels),
            'collectives': sorted({collective.search(line).group(1)
                                   for line in collectives}),
            'lines': kernels + collectives}


def close(a, b, rtol):
    """A Python ``bool`` whatever number types it is handed: a comparison
    of numpy scalars is a ``numpy.bool``, which ``json`` refuses."""
    return bool(math.isfinite(a) and abs(a - b) <= rtol * abs(b))


def compared(reference, losses):
    """``{name: [number, limit]}`` of what ``checks`` compares with a
    limit, for the run's last lines: the loss's and the gradient norm's
    distance from the reference's as shares of it (the family may have
    raised that norm by its worst leaf, in units of the leaf's limit), and
    the fall of the loss from the first five steps' mean to the last
    five's, which has to be over nothing."""
    def apart(a, b):
        return abs(a - b) / abs(b) if b else math.inf
    out = {'loss_apart': [apart(reference['loss'],
                                reference['reference_loss']), LOSS_RTOL],
           'grad_norm_apart': [apart(reference['grad_norm'],
                                     reference['reference_grad_norm']),
                               GRAD_NORM_RTOL]}
    if len(losses) >= MIN_STEPS:
        out['loss_fall'] = [statistics.fmean(losses[:5])
                            - statistics.fmean(losses[-5:]), 0.0]
    # (a number that is not finite as ``None``: the last line stays JSON)
    return {name: [float(x) if math.isfinite(x) else None, float(limit)]
            for name, (x, limit) in out.items()}


def run_cell(workload, seed, seconds, trace, t_start, out_dir, say,
             keep_trace=False, rehearsal=None):
    """The whole run; returns the last line's object. ``rehearsal``
    (the tests' only) gives ``cell``, ``config``, ``traffic`` and
    ``peaks`` in place of the named files' contents, tiny sizes on a
    device the table has no row for, and makes the platform the CPU."""
    import jax
    import numpy as np

    from benchmark import trace_reduce

    phases, clock = {}, [t_start]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - clock[0]
        clock[0] = now

    bench = load_benchmark()
    if rehearsal:
        platform = 'cpu'
        cell, config, traffic, peaks = (rehearsal[k] for k in (
            'cell', 'config', 'traffic', 'peaks'))
    else:
        platform = 'tpu'
        cell = load_json('workloads', workload)
        config = load_json('configs', cell['config'])
        traffic = load_json('traffic', cell['traffic'])
    chips = cell['chips']
    family = load_module('models', config['family'])
    generator = load_module('generators', traffic['generator'])
    devices = pick_devices(chips, platform)
    if not rehearsal:
        peaks = load_peaks(devices[0].device_kind)
    compiles = CompileCounter()
    phase('import_and_devices')

    # -- set-up ------------------------------------------------------------
    engine = load_module('engines', cell['engine']).Engine(
        family.build(config), cell['parallel'], devices)
    state = engine.init(seed)
    jax.block_until_ready(state)
    phase('init')
    data = generator.batches(traffic, config, seed)
    compiled = engine.compile(state, next(data))
    phase('compile_step')
    hlo = compiled.as_text()
    memory = compiled.memory_analysis()
    memory = {'argument': memory.argument_size_in_bytes,
              'output': memory.output_size_in_bytes,
              'temp': memory.temp_size_in_bytes,
              'alias': memory.alias_size_in_bytes}
    facts = hlo_facts(hlo)
    facts['params_span_mesh'] = engine.params_span_mesh(state)

    probe = next(generator.batches(traffic, config, seed,
                                   batch=PROBE_PER_CHIP * chips, stream=1))
    got_loss, got_norm = engine.loss_and_grad_norm(state, probe)
    phase('probe_program')
    ref_params = family.to_reference_params(
        jax.device_put(state.params, devices[0]))
    ref_loss, ref_norm = family.reference_loss_and_grad_norm(
        config, ref_params, probe)
    del ref_params
    phase('probe_reference')
    # the report's types are the harness's to keep, whatever a family or
    # an engine hands back: every value of ``reference`` a float
    reference = {'loss': float(got_loss), 'reference_loss': float(ref_loss),
                 'grad_norm': float(got_norm),
                 'reference_grad_norm': float(ref_norm)}

    warm = Feed([next(data) for _ in range(WARMUP_STEPS + 2)])
    state, _ = engine.fit(state, warm, WARMUP_STEPS)
    warm.finish()
    step_s = warm.step_times[-1] - warm.step_times[-2]
    phase('warmup')

    trace_steps = cell['trace_steps']
    min_steps = cell.get('min_steps', MIN_STEPS)
    if trace:
        steps = max(min_steps, trace_steps + 1)
    else:
        steps = max(min_steps, int(seconds / step_s))
    trace_dir = os.path.join(out_dir, 'trace')
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    feed = Feed([next(data) for _ in range(steps + 2)],
                trace_dir=trace_dir if trace else None,
                trace_from=steps - trace_steps + 1)
    setup_compiles = compiles.snapshot()
    phase('make_batches')

    # -- the window --------------------------------------------------------
    t0 = time.perf_counter()
    state, losses = engine.fit(state, feed, steps)
    t1 = time.perf_counter()
    feed.finish()
    window_compiles = compiles.requests - setup_compiles['requests']

    # -- checks ------------------------------------------------------------
    finite = [math.isfinite(x) for x in losses]
    expects = cell['expects']
    numbers = compared(reference, losses)
    fall = numbers.get('loss_fall', [None])[0]
    checks = {
        'reference_loss': close(got_loss, ref_loss, LOSS_RTOL),
        'reference_grad_norm': close(got_norm, ref_norm, GRAD_NORM_RTOL),
        'steps_ran': len(losses) == steps,
        'losses_finite': all(finite),
        'loss_falls': all(finite) and fall is not None and fall > 0.0,
        'pallas_custom_calls':
            (facts['pallas_custom_calls'] > 0) ==
            bool(expects['pallas_custom_calls']),
        'collectives': set(expects['collectives']) <=
        set(facts['collectives']) and
        (bool(expects['collectives']) or not facts['collectives']),
        'params_span_mesh': facts['params_span_mesh'],
        'no_compile_in_window': window_compiles == 0,
    }
    # ... and every value of ``checks`` a bool, or the last line is not
    # written (``json`` refuses a ``numpy.bool``)
    checks = {name: bool(ok) for name, ok in checks.items()}

    # -- metrics -----------------------------------------------------------
    tokens = generator.tokens_per_step(traffic)
    rate = tokens / median_step_s(feed.step_times) / chips
    end_to_end = {
        'tokens_per_s_per_chip': (rate, 'tokens/s/chip'),
        'mfu_pct': (100.0 * rate * family.flops_per_token(
            config, traffic['seq']) / peaks['bf16_flops_per_s'], '%'),
        'setup_s': (t0 - t_start, 's'),
    }
    # The allocator's peak_bytes_in_use misses what a loaded program
    # reserves for its temporaries; peak_bytes_reserved has it.
    stats = [d.memory_stats() or {} for d in devices]
    device = {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': jax.device_count(),
        'memory_peak_bytes': max(s.get('peak_bytes_in_use', 0)
                                 + s.get('peak_bytes_reserved', 0)
                                 for s in stats),
    }

    metrics = {}
    breakdown = None
    readers_s = {}      # seconds each per-layer reader took, for the report
    if trace:
        xplane = find_xplane(trace_dir)
        reduced = trace_reduce.load_file(xplane)
        run = {'cell': cell, 'config': config, 'traffic': traffic,
               'chips': chips, 'peaks': peaks, 'compile': setup_compiles,
               'memory': memory, 'memory_stats': stats, 'hlo': hlo,
               'say': say,
               'step_times': feed.step_times[-(trace_steps + 1):]}
        for name in metrics_for(cell['name'], bench['per_layer']):
            module = load_module('layer_metrics', name)
            began = time.perf_counter()
            value = module.reduce(reduced, run)
            readers_s[name] = time.perf_counter() - began
            if value is not None:
                metrics[name] = {'value': value, 'unit': module.UNIT}
        if not reduced.ops and platform == 'tpu':
            raise RuntimeError('the trace has no %s line of a device '
                               'plane' % trace_reduce.OPS_LINE)
        lo, hi = reduced.window
        device['busy_s'] = (trace_reduce.chip_mean(
            reduced, lambda c: trace_reduce.busy_ns(reduced, c)) or 0) / 1e9
        device['window_s'] = (hi - lo) / 1e9
        breakdown = trace_reduce.breakdown(reduced)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for name in metrics_for(cell['name'], bench['end_to_end']):
            value, unit = end_to_end[name]
            metrics[name] = {'value': value, 'unit': unit}

    report = {
        'workload': cell['name'], 'seed': seed, 'trace': int(trace),
        'steps': steps, 'window_s': t1 - t0, 'warmup_step_s': step_s,
        'losses': losses, 'reference': reference, 'checks': checks,
        'compared': numbers,
        'hlo': facts, 'memory_analysis': memory,
        'memory_stats': stats, 'setup_phases_s': phases,
        'compile_setup': setup_compiles,
        'compile_requests_in_window': window_compiles,
        'readers_s': readers_s,
        'end_to_end': {k: v[0] for k, v in end_to_end.items()},
        # the rate over the whole call, stalls included, for the record
        'whole_call_tokens_per_s_per_chip':
            steps * tokens / (t1 - t0) / chips,
        'step_intervals_ms': [1e3 * float(x)
                              for x in np.diff(feed.step_times)],
    }
    say(json.dumps(report))
    with open(os.path.join(out_dir, 'report.json'), 'w') as f:
        json.dump(report, f, indent=1)

    result = {'correct': all(checks.values()), 'attempted': steps,
              'failed': steps - sum(finite), 'metrics': metrics,
              'device': device}
    if breakdown is not None:
        result['breakdown'] = breakdown
    # each number compared beside its limit: the run's last lines on
    # standard error, and the last key of its last line
    for name, (x, limit) in numbers.items():
        print('compared %s %r limit %r' % (name, x, limit), file=sys.stderr,
              flush=True)
    result['compared'] = numbers
    return result


def find_xplane(trace_dir):
    found = [os.path.join(d, f) for d, _, files in os.walk(trace_dir)
             for f in files if f.endswith('.xplane.pb')]
    if len(found) != 1:
        raise RuntimeError('expected one .xplane.pb under %s, found %s'
                           % (trace_dir, found))
    return found[0]


def main(argv, t_start):
    import argparse
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), required=True)
    parser.add_argument('--out', default=os.path.join(ROOT, 'benchmark_out'),
                        help='directory for the report and the trace')
    parser.add_argument('--keep-trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    out_dir = os.path.join(args.out, args.workload,
                           'seed%d.trace%d' % (args.seed, args.trace))
    os.makedirs(out_dir, exist_ok=True)
    setup_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start, out_dir,
                      say=lambda line: print(line, flush=True),
                      keep_trace=bool(args.keep_trace))
    print(json.dumps(result), flush=True)
    return 0


def rehearse(cell, config, traffic, peaks, seed, trace, out_dir,
             seconds=0.0):
    """The whole run loop at tiny widths on the CPU's virtual devices,
    for the tests: it proves control flow, never speed, and what it
    returns says ``cpu``. Not reachable from the command line."""
    lines = []
    result = run_cell(cell['name'], seed, seconds, trace,
                      time.perf_counter(), out_dir, say=lines.append,
                      rehearsal={'cell': cell, 'config': config,
                                 'traffic': traffic, 'peaks': peaks})
    return result, lines
