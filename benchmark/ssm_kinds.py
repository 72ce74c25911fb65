"""The Mamba-2 layers of a step, read from the names the program gives
them (PR 41), and what the chunked scan's kernels NEED, from shapes: the
cost functions of their roofline shares.

Kernels: ``ssd_fwd`` and ``ssd_bwd``
(``autodist_tpu/kernels/ssd_scan.py``): the chunked form of the
selective state-space recurrence at ``chunk_size`` positions a chunk,
``H`` heads of ``P`` lanes, ``G`` groups of ``N`` state lanes. Scopes:
``ssm`` (a Mamba-2 layer whole: its norm, the mixer, the residual) and
inside it ``ssm_mixer`` (the two projections, the conv, the gate norm:
everything of the mixer but the kernels and the running sums round
them). The expert layers' scopes are ``moe_kinds``' three and
``mla_kinds``' ``moe_shared``.

What a call needs, a token (``Q = chunk_size``; a position sees ``Q /
2`` positions of its chunk on average, the causal half; a multiply-add
is two FLOPs):

* ``ssd_fwd``: ``C B^T`` (``G Q/2 N``), the masked product with ``dt x``
  (``H Q/2 P``), the chunk's state and what the entering state adds
  (``H P N`` each), the state carried on (``H P N / Q``); reads x, B,
  C, dt, writes y.
* ``ssd_bwd``: ``C B^T`` again and its two gradients (``3 G Q/2 N``),
  ``dM = dY (dt x)^T`` and ``M^T dY`` (``2 H Q/2 P``), through the
  states ``dB``, ``d(dt x)``, ``dC`` and the entering state's gradient
  (``4 H P N``), the carry (``H P N / Q``); reads x, B, C, dt, dy,
  writes their four gradients.

Bytes in bf16, one number a head and position for ``dt``; the states
the forward keeps for the backward (f32, a chunk) are the
implementation's and are not counted, nor is the forward the backward's
checkpoint runs again. A program without the names gives nothing to
read: every function returns ``None`` and says why.
"""
from benchmark import mla_kinds
from benchmark.layer_metrics import flash_ms_per_step as flash

KERNELS = ('ssd_fwd', 'ssd_bwd')


def dims(config):
    """``(H, P, G, N, Q)`` of a configuration's Mamba-2 layers, or
    ``None`` where it has none."""
    try:
        return (config['mamba_num_heads'], config['mamba_head_dim'],
                config['n_groups'], config['ssm_state_size'],
                config['chunk_size'])
    except KeyError:
        return None


def ssm_layers(config):
    """Mamba-2 layers among those run."""
    letters = config.get('hybrid_override_pattern', '')
    return letters[:config['num_hidden_layers']].count('M')


def call_cost(kernel, batch, seq, heads, head_dim, groups, state, chunk):
    """(FLOPs, HBM bytes) ONE call of ``kernel`` needs (the module's
    docstring)."""
    half = chunk / 2
    gram, masked, by_state = (groups * half * state, heads * half * head_dim,
                              heads * head_dim * state)
    tokens = batch * seq
    inner, bc = heads * head_dim, 2 * groups * state
    macs, widths = {
        'ssd_fwd': (gram + masked + 2 * by_state + by_state / chunk,
                    2 * inner + bc + heads),
        'ssd_bwd': (3 * gram + 2 * masked + 4 * by_state + by_state / chunk,
                    3 * inner + 2 * bc + 2 * heads),
    }[kernel]
    return 2 * tokens * macs, 2 * tokens * widths


def kernels_ms(trace, run, kernel=None):
    """Milliseconds a step in the scan's calls (one of them, or both),
    mean over chips."""
    names = KERNELS if kernel is None else (kernel,)
    # the Mosaic calls alone: XLA gives a copy that re-tiles a call's
    # small output the call's own op_name
    heads = set().union(*(mla_kinds.kernel_heads(run['hlo'], name)
                          for name in names))
    if not heads or not trace.ops:
        run['say']('%s: the compiled step has no such operation by name: '
                   'nothing to read' % ' + '.join(names))
        return None
    ns = flash.kernel_ns(trace, heads)
    return None if ns is None else ns / trace.steps / 1e6


def roofline_pct(trace, run, kernel):
    """Share of its roofline the calls of ``kernel`` reach."""
    config, traffic = run['config'], run['traffic']
    shape = dims(config)
    ms = kernels_ms(trace, run, kernel)
    if not ms or shape is None:
        return None
    heads = mla_kinds.kernel_heads(run['hlo'], kernel)
    calls = len(flash.kernel_events(trace, min(trace.ops), heads)) \
        / trace.steps
    layers = ssm_layers(config)
    if calls < layers or calls != int(calls):
        raise ValueError('%s: %.2f calls a step are not whole calls of %d '
                         'layers' % (kernel, calls, layers))
    h, p, g, n, q = shape
    flops, nbytes = call_cost(kernel, traffic['global_batch'] // run['chips'],
                              traffic['seq'], h, p, g, n, q)
    flops, nbytes = calls * flops, calls * nbytes
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    run['say']('%s: %.6g ms a step in %d calls, %.4g FLOPs (%.4g ms at '
               'peak), %.4g bytes (%.4g ms at peak); bound by %s'
               % (kernel, ms, calls, flops, 1e3 * t_flops, nbytes,
                  1e3 * t_bytes,
                  'compute' if t_flops >= t_bytes else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)
