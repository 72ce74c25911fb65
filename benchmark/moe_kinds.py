"""The expert layer of a step, read from the names the program gives it
(PR 33), and what its kernels and the grouped flash calls NEED, from
shapes: the cost functions of the new kernels' roofline shares.

Scopes inside ``mlp`` (``autodist_tpu/models/moe.py``): ``moe_route``
(router, top-k, the order of the held pairs, counts), ``moe_dispatch``
(gather of the rows, scatter-add back with the weights) and
``moe_experts`` (the grouped products ``moe_gmm``, ``moe_gmm_dx``,
``moe_gmm_dw`` and the activation between them). Counters: the
``Trainer`` reads back with the loss what the model counted in the step
and leaves it in the loop ring as ``trainer.counters`` (``moe_rows_here``,
``moe_load_max``, ``moe_load_mean``: means over the layers).

A program without the scopes or the counters (the parent of PR 33, a
configuration without experts) gives nothing to read: every function
returns ``None`` and says why.
"""
import re

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import flash_ms_per_step as flash

SCOPES = ('moe_route', 'moe_dispatch', 'moe_experts')
COUNTERS = 'trainer.counters'

_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_SCOPE_SEP = re.compile(r'[/()]')


def scope_of(op_name):
    """The innermost of :data:`SCOPES` on an ``op_name``'s path."""
    if not op_name:
        return None
    parts = _SCOPE_SEP.split(op_name.split(';', 1)[0])
    return next((p for p in reversed(parts) if p in SCOPES), None)


def scope_heads(hlo, scope):
    """Heads of the compiled step's instructions under ``scope``."""
    heads = set()
    for line in hlo.splitlines():
        line = line.strip()
        if line.startswith('ROOT '):
            line = line[len('ROOT '):]
        if not line.startswith('%') or ' = ' not in line:
            continue
        m = _OP_NAME.search(line)
        if m and scope_of(m.group(1)) == scope:
            heads.add(tr.op_head(line))
    return heads


def scope_ms(trace, run, scope):
    """Milliseconds a step under ``scope``, all phases, mean over the
    chips; ``None`` where the step has no such operation."""
    heads = scope_heads(run['hlo'], scope)
    if not heads or not trace.ops:
        run['say']('%s: the compiled step has no operation under that '
                   'scope: nothing to read' % scope)
        return None
    ns = flash.kernel_ns(trace, heads)
    return None if ns is None else ns / trace.steps / 1e6


def counters(trace, run):
    """``{name: mean over the traced steps}`` of the step counters the
    program left in its loop ring; ``None`` without them."""
    try:
        from autodist_tpu import telemetry
        records = telemetry.get().loop_records()
    except (ImportError, AttributeError):
        records = []
    tags = [r.get('tags') or {} for r in records if r['name'] == COUNTERS]
    tags = [t for t in tags if 'moe_rows_here' in t][-max(trace.steps, 1):]
    if not tags:
        run['say']('no %r event with the expert layer\'s counters in the '
                   'program\'s loop ring: nothing to read' % COUNTERS)
        return None
    return {name: sum(t[name] for t in tags) / len(tags) for name in tags[0]}


def pairs_per_step(run):
    """``tokens x experts a token`` of a step on a chip."""
    traffic, config = run['traffic'], run['config']
    return traffic['global_batch'] * traffic['seq'] // run['chips'] \
        * config['num_experts_per_tok']


def experts_cost(config, rows):
    """(FLOPs, HBM bytes) the held experts of ONE layer need for a
    training step over ``rows`` live rows: forward ``rows x (hidden x 2
    moe + moe x hidden)`` multiply-adds and twice that backward (dx and
    dw of both products; the forward that the backward runs again is the
    implementation's, not counted); bytes: a row in and out forward, x,
    dy in and dx out backward (bf16), the held experts' three matrices
    read once forward and twice backward (bf16) and their gradients
    written (f32)."""
    d, f = config['hidden_size'], config['moe_intermediate_size']
    weights = config['num_experts_held'] * 3 * d * f
    flops = 3 * rows * 2 * 3 * d * f
    nbytes = rows * 5 * d * 2 + weights * (3 * 2 + 4)
    return flops, nbytes


def experts_roofline_pct(trace, run):
    ms = scope_ms(trace, run, 'moe_experts')
    counted = counters(trace, run)
    if not ms or not counted:
        return None
    config = run['config']
    layers = config['num_hidden_layers']
    flops, nbytes = experts_cost(config, counted['moe_rows_here'])
    flops, nbytes = layers * flops, layers * nbytes
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    run['say']('moe_experts: %.6g ms a step for %.1f live rows a layer in '
               '%d layers, %.4g FLOPs (%.4g ms at peak), %.4g bytes (%.4g '
               'ms at peak); bound by %s'
               % (ms, counted['moe_rows_here'], layers, flops, 1e3 * t_flops,
                  nbytes, 1e3 * t_bytes,
                  'compute' if t_flops >= t_bytes else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)


# -- grouped flash calls ---------------------------------------------------

def gqa_layers(config, kind):
    """Layers of ``kind`` (``'global'``: full causal, ``'window'``: the
    causal band) among those run; ``None`` for a configuration without
    ``layer_types``."""
    types = config.get('layer_types')
    if not types:
        return None
    name = {'global': 'full_attention', 'window': 'sliding_attention'}[kind]
    return types[:config['num_hidden_layers']].count(name)


def gqa_call_cost(batch, heads, kv_heads, seq, keys, head_dim, backward):
    """(FLOPs, HBM bytes) one grouped forward call, or one backward
    (both of its kernels), needs when a query sees ``keys`` keys: 2 (5
    backward) matmuls of ``2 b h seq keys d``; q and o (backward: q, o,
    do in, dq out) of ``b h seq d`` elements and k and v (backward: and
    dk, dv) of ``b kv seq d``, k and v read once a group, in bf16."""
    matmuls, q_sized, kv_sized = (5, 4, 4) if backward else (2, 2, 2)
    return (matmuls * 2 * batch * heads * seq * keys * head_dim,
            2 * batch * seq * head_dim * (q_sized * heads
                                          + kv_sized * kv_heads))


def gqa_roofline_pct(trace, run, kind):
    """Share of their roofline the grouped flash calls of one kind reach
    together, as ``flash_kinds.roofline_pct`` with a grouped call's
    costs: keys seen ``sliding_window`` in the band, half the sequence
    under the causal mask."""
    from benchmark import flash_kinds
    name = 'flash_gqa_%s_roofline_pct' % (
        'causal' if kind == 'global' else 'band')
    config, traffic = run['config'], run['traffic']
    layers = gqa_layers(config, kind)
    ms = flash_kinds.kind_ms(trace, run, kind)
    if not ms or not layers or 'num_key_value_heads' not in config:
        run['say']('%s: no such flash calls by name in the step, or a '
                   'configuration without grouped layers of that kind: '
                   'nothing to read' % name)
        return None
    heads = flash_kinds.kind_heads(run['hlo'], kind)
    calls = len(flash.kernel_events(trace, min(trace.ops), heads)) \
        / trace.steps
    fwd_calls = calls - flash_kinds.BWD_KERNELS * layers
    if fwd_calls < layers or fwd_calls != int(fwd_calls):
        raise ValueError('%s: %.2f calls a step do not split into %d '
                         'backward pairs and whole forward calls'
                         % (name, calls, layers))
    seq = traffic['seq']
    shape = dict(batch=traffic['global_batch'] // run['chips'],
                 heads=config['num_attention_heads'],
                 kv_heads=config['num_key_value_heads'], seq=seq,
                 keys=seq / 2 if kind == 'global'
                 else min(seq, config['sliding_window']),
                 head_dim=config['head_dim'])
    f_flops, f_bytes = gqa_call_cost(backward=False, **shape)
    b_flops, b_bytes = gqa_call_cost(backward=True, **shape)
    flops = fwd_calls * f_flops + layers * b_flops
    nbytes = fwd_calls * f_bytes + layers * b_bytes
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    run['say']('%s: %.6g ms a step in %d forward calls + %d backward pairs '
               'over %g keys a query, %.4g FLOPs (%.4g ms at peak), %.4g '
               'bytes (%.4g ms at peak); bound by %s'
               % (name, ms, fwd_calls, layers, shape['keys'], flops,
                  1e3 * t_flops, nbytes, 1e3 * t_bytes,
                  'compute' if t_flops >= t_bytes else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)
