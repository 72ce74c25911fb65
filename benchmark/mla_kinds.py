"""The latent-attention layer of a step, read from the names the
program gives it (PR 39), and what its flash kernels NEED, from shapes:
the cost functions of the three kernels' roofline shares.

Kernels: ``flash_fwd_mla``, ``flash_dq_mla``, ``flash_dkv_mla``
(``autodist_tpu/kernels/flash_attention.py``): a q/k head of
``qk_nope_head_dim`` lanes of its own + ``qk_rope_head_dim`` rotary
lanes whose key all the heads share, a v head of ``v_head_dim``, causal.
Scopes: ``mla_latent`` (inside ``attention``: the three projections, the
latent's norm and the split) and ``moe_shared`` (inside ``mlp``: the
always-on expert); the routed experts are ``moe_kinds``' three scopes
together.

What a call needs, a query seeing half of ``seq`` under the causal
mask (``keys``), ``M = 2 b heads seq keys`` FLOPs a lane of contraction:

* forward: QK^T at ``qk`` = nope + rope lanes and PV at ``v``: ``M (qk +
  v)``; reads q, k_nope, v and the one rotary key, writes o.
* ``flash_dq_mla``: the scores again (``qk``), dP = dO V^T (``v``), dQ =
  dS K (``qk``): ``M (2 qk + v)``; reads q, k_nope, v, the key, do, o,
  writes dq.
* ``flash_dkv_mla``: the scores again (``qk``), dV = P^T dO (``v``), dP
  (``v``), dK = dS^T Q (``qk``): ``M (2 qk + 2 v)``; reads q, k_nope, v,
  the key, do, writes dk_nope, dv and the key's gradient.

Each kernel is held to what IT has to compute (both backward kernels
need the scores and dP), bytes in bf16, the row statistics left out. A
program without the names gives nothing to read: every function returns
``None`` and says why.
"""
import functools
import re

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import flash_ms_per_step as flash

KERNELS = ('flash_fwd_mla', 'flash_dq_mla', 'flash_dkv_mla')
ROUTED_SCOPES = ('moe_route', 'moe_dispatch', 'moe_experts')

_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_SCOPE_SEP = re.compile(r'[/()]')


@functools.lru_cache(maxsize=16)
def _named_heads(hlo, wanted, kernels_only):
    """Heads of the compiled step's instructions whose ``op_name`` has
    one of ``wanted`` (a tuple) among its path's components; kept, since
    every metric of a run asks of the same text."""
    heads = set()
    for line in hlo.splitlines():
        line = line.strip()
        if line.startswith('ROOT '):
            line = line[len('ROOT '):]
        if not line.startswith('%') or ' = ' not in line:
            continue
        if kernels_only and 'custom_call_target="tpu_custom_call"' \
                not in line:
            continue
        m = _OP_NAME.search(line)
        if m and set(_SCOPE_SEP.split(m.group(1).split(';', 1)[0])) \
                & set(wanted):
            heads.add(tr.op_head(line))
    return frozenset(heads)


def kernel_heads(hlo, kernel=None):
    """Heads of the Mosaic calls named ``kernel`` (all three by
    default)."""
    return _named_heads(hlo, KERNELS if kernel is None else (kernel,), True)


def _ms(trace, run, heads, what):
    if not heads or not trace.ops:
        run['say']('%s: the compiled step has no such operation by name: '
                   'nothing to read' % what)
        return None
    ns = flash.kernel_ns(trace, heads)
    return None if ns is None else ns / trace.steps / 1e6


def kernels_ms(trace, run, kernel=None):
    """Milliseconds a step in the latent flash calls, mean over chips."""
    return _ms(trace, run, kernel_heads(run['hlo'], kernel),
               kernel or 'flash_*_mla')


def scopes_ms(trace, run, *scopes):
    """Milliseconds a step under any of ``scopes``, all phases."""
    return _ms(trace, run, _named_heads(run['hlo'], scopes, False),
               ' + '.join(scopes))


def call_cost(kernel, batch, heads, seq, nope, rope, v):
    """(FLOPs, HBM bytes) ONE call of ``kernel`` needs (the module's
    docstring), causal, in bf16."""
    qk = nope + rope
    lanes, tensors = {
        # (lanes of contraction, [b, s, .] widths moved)
        'flash_fwd_mla': (qk + v, heads * (qk + nope + 2 * v) + rope),
        'flash_dq_mla': (2 * qk + v,
                         heads * (2 * qk + nope + 3 * v) + rope),
        'flash_dkv_mla': (2 * qk + 2 * v,
                          heads * (qk + 2 * nope + 3 * v) + 2 * rope),
    }[kernel]
    return (2 * batch * heads * seq * (seq // 2) * lanes,
            2 * batch * seq * tensors)


def roofline_pct(trace, run, kernel):
    """Share of its roofline the calls of ``kernel`` reach."""
    ms = kernels_ms(trace, run, kernel)
    config, traffic = run['config'], run['traffic']
    if not ms or 'kv_lora_rank' not in config:
        return None
    heads = kernel_heads(run['hlo'], kernel)
    calls = len(flash.kernel_events(trace, min(trace.ops), heads)) \
        / trace.steps
    layers = config['num_hidden_layers']
    if calls < layers or calls != int(calls):
        raise ValueError('%s: %.2f calls a step are not whole calls of %d '
                         'layers' % (kernel, calls, layers))
    flops, nbytes = call_cost(
        kernel, traffic['global_batch'] // run['chips'],
        config['num_attention_heads'], traffic['seq'],
        config['qk_nope_head_dim'], config['qk_rope_head_dim'],
        config['v_head_dim'])
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
