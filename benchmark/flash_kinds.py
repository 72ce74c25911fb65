"""The flash kernels of a step by KIND: the calls over the whole score
square (``flash_fwd``, ``flash_dq``, ``flash_dkv``: a global layer's)
and the band calls (``flash_fwd_band``, ``flash_dq_band``,
``flash_dkv_band``: a window layer's), told apart by the name the
program gives each ``pallas_call`` (PR 26). Time per kind, and the share
of its roofline each kind reaches.

What a call needs, from its shapes and not from what the implementation
spends (as ``flash_roofline_pct``): a forward call is QK^T and PV, 2
matmuls of ``2 b h seq keys d`` FLOPs, and moves q, k, v, o (4 tensors
of ``b h seq d`` elements); a backward (both of its kernels) is the 5
matmuls of flash-attention 2 and 8 tensors (reads q k v o do, writes dq
dk dv). ``keys`` is what a query sees: ``seq`` in a global layer, the
band's ``local_attention + 1`` (never more than ``seq``) in a window
layer, so a band call is bound by memory where a global one is bound by
compute. Per layer and step the trace shows one backward pair and one
forward call, or two where remat runs the forward again; the forward
calls are counted from the trace.

A program without the names (or a configuration without window layers)
gives nothing to read: every function returns ``None`` and says why.
"""
import re

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import flash_ms_per_step as flash

KERNELS = ('flash_fwd', 'flash_dq', 'flash_dkv')
BAND = '_band'
BWD_KERNELS = 2   # dq and dkv
FWD = (2, 4)      # score-sized matmuls, q-sized tensors of a forward call
BWD = (5, 8)      # of a backward (both kernels)

_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def kernel_names(hlo):
    """``{head: name}`` of the Mosaic calls in a compiled step's text:
    the ``pallas_call(name=...)`` each was given, read from its
    ``op_name`` (``.../attention/flash_fwd_band/pallas_call``)."""
    names = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line \
                or ' = ' not in line:
            continue
        m = _OP_NAME.search(line)
        parts = m.group(1).split(';', 1)[0].split('/') if m else []
        names[tr.op_head(line.strip().replace('ROOT ', '', 1))] = next(
            (p for p in reversed(parts)
             if p in KERNELS or p[:-len(BAND)] in KERNELS), None)
    return names


def kind_heads(hlo, kind):
    """Heads of the calls of one kind (``'global'`` or ``'window'``)."""
    wanted = {k + (BAND if kind == 'window' else '') for k in KERNELS}
    return {head for head, name in kernel_names(hlo).items()
            if name in wanted}


def kind_ms(trace, run, kind):
    """Milliseconds a step in the calls of one kind, mean over the
    chips; ``None`` where the step has none."""
    heads = kind_heads(run['hlo'], kind)
    if not heads or not trace.ops:
        return None
    ns = flash.kernel_ns(trace, heads)
    return None if ns is None else ns / trace.steps / 1e6


def layers_of(config, kind):
    """How many of the configuration's layers are of ``kind``; ``None``
    for a configuration that does not say."""
    every = config.get('global_attn_every_n_layers')
    if not every:
        return None
    n = config['num_hidden_layers']
    n_global = len(range(0, n, every))
    return n_global if kind == 'global' else n - n_global


def keys_seen(config, seq, kind):
    """Keys a query attends to in a layer of ``kind``."""
    if kind == 'global':
        return seq
    return min(seq, config['local_attention'] + 1)


def call_cost(batch, heads, seq, keys, head_dim, itemsize, backward):
    """(FLOPs, HBM bytes) one forward call, or one backward (both of its
    kernels), needs when every query sees ``keys`` keys."""
    matmuls, tensors = BWD if backward else FWD
    return (matmuls * 2 * batch * heads * seq * keys * head_dim,
            tensors * batch * heads * seq * head_dim * itemsize)


def roofline_pct(trace, run, kind):
    """Share of their roofline the calls of one kind reach together."""
    name = 'flash_%s_roofline_pct' % kind
    ms = kind_ms(trace, run, kind)
    config, traffic = run['config'], run['traffic']
    layers = layers_of(config, kind)
    if not ms or not layers:
        run['say']('%s: the step has no %s flash calls by name, or the '
                   'configuration no such layers: nothing to read'
                   % (name, kind))
        return None
    heads = kind_heads(run['hlo'], kind)
    calls = len(flash.kernel_events(trace, min(trace.ops), heads)) \
        / trace.steps
    fwd_calls = calls - BWD_KERNELS * layers
    if fwd_calls < layers or fwd_calls != int(fwd_calls):
        raise ValueError('%s: %.2f calls a step do not split into %d '
                         'backward pairs and whole forward calls'
                         % (name, calls, layers))
    seq = traffic['seq']
    shape = dict(batch=traffic['global_batch'] // run['chips'],
                 heads=config['num_attention_heads'], seq=seq,
                 keys=keys_seen(config, seq, kind),
                 head_dim=config['hidden_size']
                 // config['num_attention_heads'], itemsize=2)
    f_flops, f_bytes = call_cost(backward=False, **shape)
    b_flops, b_bytes = call_cost(backward=True, **shape)
    flops = fwd_calls * f_flops + layers * b_flops
    nbytes = fwd_calls * f_bytes + layers * b_bytes
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    run['say']('%s flash calls: %.6g ms a step in %d forward calls + %d '
               'backward pairs over %d keys a query, %.4g FLOPs (%.4g ms '
               'at peak), %.4g bytes (%.4g ms at peak); bound by %s'
               % (kind, ms, fwd_calls, layers, shape['keys'], flops,
                  1e3 * t_flops, nbytes, 1e3 * t_bytes,
                  'compute' if t_flops >= t_bytes else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)
