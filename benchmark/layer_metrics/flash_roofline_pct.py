"""Share of its roofline the flash-attention kernels reach: the least
time the chip could take for the calls executed (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s) over the time they took.

Operations and bytes are what the algorithm needs for each call, from
its shapes (q, k, v of ``[batch, heads, seq, head]``), not what this
implementation spends: a forward call is QK^T and PV (4 b h s^2 d
FLOPs), a backward is the five matmuls of flash-attention 2 (10 b h s^2
d; here two kernels, dq and dkv, which each recompute the scores), and
under a causal mask half of each. Per layer and step the trace shows one
backward pair and one forward call, or two where remat runs the forward
again; the forward calls are counted from the trace.
"""
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import flash_ms_per_step as flash

LAYER = 'kernels'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'tokens_per_s_per_chip'

BWD_KERNELS = 2   # dq and dkv


def call_cost(batch, heads, seq, head_dim, causal, itemsize, backward):
    """(FLOPs, HBM bytes) one forward call, or one backward (both of its
    kernels), needs."""
    matmul = 2 * batch * heads * seq * seq * head_dim
    if causal:
        matmul //= 2
    tensor = batch * heads * seq * head_dim * itemsize
    if backward:
        # reads q k v o do, writes dq dk dv
        return 5 * matmul, 8 * tensor
    # reads q k v, writes o
    return 2 * matmul, 4 * tensor


def reduce(trace, run):
    heads = tr.pallas_heads(run['hlo'])
    ns = flash.kernel_ns(trace, heads)
    if not ns:
        return None
    config, traffic = run['config'], run['traffic']
    layers = config['num_hidden_layers']
    chip = min(trace.ops)
    calls = len(flash.kernel_events(trace, chip, heads)) / trace.steps
    fwd_calls = calls - BWD_KERNELS * layers
    if fwd_calls < layers or fwd_calls != int(fwd_calls):
        raise ValueError('%.2f kernel calls a step do not split into %d '
                         'backward pairs and whole forward calls'
                         % (calls, layers))
    shape = dict(batch=traffic['global_batch'] // run['chips'],
                 heads=config['num_attention_heads'], seq=traffic['seq'],
                 head_dim=config['hidden_size']
                 // config['num_attention_heads'],
                 causal=config['causal'], itemsize=2)
    f_flops, f_bytes = call_cost(backward=False, **shape)
    b_flops, b_bytes = call_cost(backward=True, **shape)
    flops = fwd_calls * f_flops + layers * b_flops
    nbytes = fwd_calls * f_bytes + layers * b_bytes
    peaks = run['peaks']
    t_flops = flops / peaks['bf16_flops_per_s']
    t_bytes = nbytes / peaks['hbm_bytes_per_s']
    run['say']('flash kernels: %d forward calls + %d backward pairs a '
               'step, %.4g FLOPs, %.4g bytes; bound by %s'
               % (fwd_calls, layers, flops, nbytes,
                  'compute' if t_flops >= t_bytes else 'memory'))
    return 100.0 * max(t_flops, t_bytes) / (ns / trace.steps / 1e9)
