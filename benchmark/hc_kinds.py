"""The residual streams' connections of a step, read from the names the
program gives them (PR 48): manifold-constrained hyper-connections,
``autodist_tpu/models/hyper_connections.py``.

Scopes: ``hc`` (inside ``block``, BESIDE ``attention`` and ``mlp``, so
that those two keep their meaning and the four of ``attention``, ``mlp``,
``hc`` and ``head_loss`` account for the step), with ``hc_coeff`` (the
coefficients' norm, the ``phi`` product, the Sinkhorn rounds) and
``hc_mix`` (the read, the write-back and the stream mix) inside it. No
kernel: the connections run under XLA, and what they cost is what these
metrics are for. Counters: the expert layers' three
(``moe_kinds.counters``) and ``hc_res_col_sum_err``, the mean over layers,
sublayers and tokens of ``max_j |sum_i H_res[i, j] - 1|`` after the last
round: whether the rounds converge at the weights of the step.

The scopes are read by ``mla_kinds.scopes_ms``, the latent kernels by
``mla_kinds.kernels_ms`` and, each against what its shape needs, by
``mla_kinds.roofline_pct`` (``hc_flash_mla_{fwd,dq,dkv}_roofline_pct``:
the only cell at 4096 keys), the expert layers' counters by
``moe_kinds``', and the read-back of the step's four counters by
``host_gap_counters_ms``' reader, under names of this cell's own. A program
without the names gives nothing to read: every function returns ``None``
and says why.
"""
from benchmark import mla_kinds, moe_kinds

MOE_SCOPES = mla_kinds.ROUTED_SCOPES + ('moe_shared',)
COUNTER = 'hc_res_col_sum_err'


def counters(trace, run):
    """The step counters' means over the traced steps
    (``moe_kinds.counters``), or None where the program left no
    ``hc_res_col_sum_err`` among them."""
    counted = moe_kinds.counters(trace, run)
    if not counted or COUNTER not in counted:
        run['say']('no %s among the step counters: nothing to read'
                   % COUNTER)
        return None
    return counted
