"""The block-diffusion step, read from the names the program gives it
(PR 45), and what its flash kernels NEED, from shapes: the cost
functions of the three kernels' roofline shares.

Kernels: ``flash_fwd_bd``, ``flash_dq_bd``, ``flash_dkv_bd``
(``autodist_tpu/kernels/flash_attention.py``): grouped kv heads under
the block-diffusion mask over the ``2 L`` rows of a sequence's noised
and clean copies. Scope: ``qk_norm`` (inside ``attention``: the per-head
RMSNorm of q and k). Counters: the expert layers' three
(``moe_kinds.counters``) and ``bd_mask_rows``, the share of the step's
``2 b L`` rows whose id is the mask's.

What a call needs: the mask leaves ``L^2 + L B`` live (query, key) pairs
a sequence and query head (``B``: the block length), each a ``head_dim``
deep multiply-add in every score-sized product:

* ``flash_fwd_bd``: QK^T and PV, 2 products; reads q, k, v, writes o.
* ``flash_dq_bd``: the scores again, dP = dO V^T, dQ = dS K, 3 products;
  reads q, k, v, do, o, writes dq.
* ``flash_dkv_bd``: the scores again, dV = P^T dO, dP, dK = dS^T Q, 4
  products; reads q, k, v, do, writes dk, dv.

Bytes in bf16, k and v (dk, dv) once a GROUP of query heads, as
``moe_kinds.gqa_call_cost`` counts them, the row statistics left out.
The tiles a kernel multiplies beyond the live pairs (the three
diagonals' masked halves) are the implementation's, not the need's. A
program without the names gives nothing to read: every function returns
``None`` and says why.
"""
from benchmark import mla_kinds, moe_kinds
from benchmark.layer_metrics import flash_ms_per_step as flash

KERNELS = ('flash_fwd_bd', 'flash_dq_bd', 'flash_dkv_bd')
ROWS_PER_TOKEN = 2   # the noised copy and the clean one

# (score-sized products, [b, 2 L, heads x d] tensors moved, [b, 2 L, kv
# heads x d] tensors moved)
NEEDS = {'flash_fwd_bd': (2, 2, 2), 'flash_dq_bd': (3, 4, 2),
         'flash_dkv_bd': (4, 2, 4)}


def live_pairs(seq, block):
    """Live (query, key) pairs of one sequence and head: ``L B`` noised
    on noised, ``(L^2 - L B) / 2`` noised on clean, ``(L^2 + L B) / 2``
    clean on clean."""
    return seq * seq + seq * block


def call_cost(kernel, batch, heads, kv_heads, seq, block, head_dim):
    """(FLOPs, HBM bytes) ONE call of ``kernel`` needs over ``batch``
    sequences of ``seq`` positions (``2 seq`` rows each), in bf16."""
    products, q_sized, kv_sized = NEEDS[kernel]
    return (products * 2 * batch * heads * live_pairs(seq, block) * head_dim,
            2 * batch * ROWS_PER_TOKEN * seq * head_dim
            * (q_sized * heads + kv_sized * kv_heads))


def kernels_ms(trace, run, kernel=None):
    """Milliseconds a step in one of the three calls, or in all three
    together, mean over the chips."""
    heads = set()
    for name in KERNELS if kernel is None else (kernel,):
        heads |= mla_kinds.kernel_heads(run['hlo'], name)
    if not heads or not trace.ops:
        run['say']('%s: the compiled step has no such call by name: '
                   'nothing to read' % (kernel or 'flash_*_bd'))
        return None
    ns = flash.kernel_ns(trace, heads)
    return None if ns is None else ns / trace.steps / 1e6


def roofline_pct(trace, run, kernel):
    """Share of its roofline the calls of ``kernel`` reach."""
    ms = kernels_ms(trace, run, kernel)
    config, traffic = run['config'], run['traffic']
    if not ms or 'block_length' not in config:
        return None
    heads = mla_kinds.kernel_heads(run['hlo'], kernel)
    calls = len(flash.kernel_events(trace, min(trace.ops), heads)) \
        / trace.steps
    layers = config['num_hidden_layers']
    if calls < layers or calls != int(calls):
        raise ValueError('%s: %.2f calls a step are not whole calls of %d '
                         'layers' % (kernel, calls, layers))
    flops, nbytes = call_cost(
        kernel, traffic['global_batch'] // run['chips'],
        config['num_attention_heads'], config['num_key_value_heads'],
        traffic['seq'], config['block_length'], config['head_dim'])
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


def counters(trace, run):
    """The step counters' means over the traced steps
    (``moe_kinds.counters``), or None where the program left no
    ``bd_mask_rows`` among them."""
    counted = moe_kinds.counters(trace, run)
    if not counted or 'bd_mask_rows' not in counted:
        run['say']('no bd_mask_rows among the step counters: nothing to '
                   'read')
        return None
    return counted


def pairs_per_step(run):
    """``rows x experts a row`` of a step on a chip: both copies' rows
    are routed."""
    return ROWS_PER_TOKEN * moe_kinds.pairs_per_step(run)
