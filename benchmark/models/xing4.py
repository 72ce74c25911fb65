"""Model family ``xing4``: XingChen-AGI's Xing4.0-29B-A4B (``config.json``
of ``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type: xing4_0``: DeepSeek-V3's
block, arXiv:2412.19437, with a q down-projection and YaRN under latent
attention, on FOUR residual streams mixed by manifold-constrained
hyper-connections, mHC, arXiv:2512.24880, after Hyper-Connections,
arXiv:2409.19606), on ONE CHIP'S SHARE of a deployment that divides each
layer over several chips: ``num_experts_held`` of the ``n_routed_experts``
routed experts (the first ones), attention, the dense MLP, the shared
expert and the connections whole, a slice of the vocabulary.

The four things a family gives (see ``transformer.py``): :func:`build`,
the plain reference (:func:`reference_loss`,
:func:`reference_loss_and_grad_norm`), :func:`to_reference_params` and
:func:`flops_per_token`.

The architecture as the reference computes it, in float32 under
``jax.default_matmul_precision('highest')``, sharing no code with
``autodist_tpu.models`` (``n = hc_mult``, ``C = hidden_size``; a token's
state is ``x [n, C]``; no linear layer has a bias; ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g``):

* ``x = [E[token]] * n`` (the embedding row copied to every stream). Layer
  ``i``: ``x = HC(x, MLA)``, ``x = HC(x, MLP_i)``, each ``HC`` with
  parameters of its own. Then ``h = sum over the streams``, ``RMSNorm``,
  ``logits = h W_head`` (untied, over the slice), mean next-token
  cross-entropy.
* ``HC(x, F)``: ``v = vec(x) / sqrt(mean(vec(x)^2) + hc_eps)`` over the ``n
  C`` numbers (no weight); ``P = alpha_pre (v phi_pre) + b_pre [n]``, ``Q =
  alpha_post (v phi_post) + b_post [n]``, ``R = alpha_res mat(v phi_res) +
  b_res [n, n]``; ``H_pre = sigmoid(P)``, ``H_post = 2 sigmoid(Q)``,
  ``H_res = SK(clip(R, mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` where
  ``SK`` starts from ``M = exp(.)`` and does ``hc_sinkhorn_iters`` rounds
  of "every column divided by its sum + hc_eps, then every row by its sum +
  hc_eps" (a Python loop); ``u = H_pre x [C]``, ``y = F(RMSNorm(u))``
  (the sublayer's own pre-norm, ``rms_norm_eps``), ``HC = H_res x +
  H_post^T y``.
* ``MLA(x)``: ``q = RMSNorm(x W_qa) W_qb`` (``q_lora_rank``) in
  ``num_attention_heads`` heads, each ``q_nope [qk_nope_head_dim] | q_rope
  [qk_rope_head_dim]``; ``x W_kva = c_kv [kv_lora_rank] | k_rope
  [qk_rope_head_dim]``, ONE rotary key for all heads; ``RMSNorm(c_kv)
  W_kvb`` in heads, each ``k_nope | v [v_head_dim]``. ``q_rope`` and
  ``k_rope`` are rotated as the family's published code rotates them (the
  adjacent pairs moved to the two halves, then ``x cos + rotate_half(x)
  sin``) at YaRN's frequencies: ``rope_theta^(-2j / d)`` divided by
  ``factor`` where pair ``j`` turns fewer than ``beta_slow`` times in
  ``original_max_position_embeddings``, left where it turns more than
  ``beta_fast`` times, a linear ramp over ``j`` between; ``cos`` and
  ``sin`` times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
  (1 here), ``mscale(f, m) = 0.1 m ln f + 1``. Head n: ``softmax((q_nope_n
  k_nope_n^T + q_rope_n k_rope^T) qk_head_dim^-0.5 mscale(factor,
  mscale_all_dim)^2)`` over the keys ``j <= i``, times ``v_n``; the heads
  side by side times ``W_o``.
* ``MLP_i``, ``i < first_k_dense_replace``: ``(silu(x W_gate) * (x W_up))
  W_down`` at ``intermediate_size`` (of these leading layers the cut in
  depth runs ``num_dense_layers_run``: they count once); after them
  kanana-2's expert layer
  (``kanana2.reference_expert_layer``: sigmoid scores over all
  ``n_routed_experts``, ``num_experts_per_tok`` chosen by ``s + b``,
  weights the chosen scores renormalised x ``routed_scaling_factor``, the
  HELD experts' part of the sum, plus the shared expert).

Departures from the published description: the configuration's
``assumed`` lists what ``config.json`` does not say (the streams' entry
and exit, the order and the ``eps`` of a Sinkhorn round, the clamp before
the exponential, no weight in the coefficients' norm) and what is left out
(the multi-token-prediction module; ``b``'s update; any balancing loss).

How it fits beside the parameters (``engines/trainer_leaves_parked.py``
takes the optimizer's slots off the chip for the comparison): one sequence
at a time, its gradient summed on the host, each layer computed again in
the backward pass, attention a block of queries against every key, the experts one at a time, the four
expert layers scanned over their stack, the logits ``LOSS_ROWS`` positions
at a time.
"""
import math

from benchmark.models import kanana2

QUERY_BLOCK = 512
LOSS_ROWS = 2048

# The limits on one leaf of the gradient, |program - reference| /
# |reference| in L2, a layer at a time (held_to_every_leaf). Each stands
# between two readings taken ON THE CHIP AT THE CELL'S OWN LOAD (my chip
# runs, PR 48: five layers, two sequences of 4,096, eight held experts; the
# probe of four seeds, PERF.md section 6): the largest a sound run gave
# over the seeds, and what the same runs gave with every product's
# operands of the reference held to float8_e4m3's mantissa, the nearest
# precision below the program's bfloat16 (the control: it comes out not
# correct by each of the first four limits, on every seed). A leaf not
# behind a router and not a connection's: sound 1.69-1.81% (the worst leaf
# of each seed), the control's worst 16.4-18.0%.
LEAF_RTOL = 0.05
# ... a leaf whose gradient comes through the routed experts (the program
# routes on bf16 activations, so near a tie its four of 64 differ from the
# reference's for a few tokens in a hundred; a held expert sees 512 rows):
# sound 9.7-10.9%, the control's worst 27.5-28.7%; one of eight held
# experts' rows left out is 35% of a layer's leaf.
ROUTED_LEAF_RTOL = 0.17
ROUTED = ('ln_mlp', 'w_gate_up', 'w_down')
# ... the router's own leaf, which turns on which experts a token near a
# tie gets: sound 13.1-18.3%, the control's worst 38.4-42.3% (its least
# 34.4%).
ROUTER_LEAF_RTOL = 0.27
# ... a connection's write-back leaves (``phi_post``, ``b_post`` and the gate
# ``alpha_post``, pooled into ONE L2 reading a connection and layer:
# ``connection_differences``): H_post multiplies the sublayer's output
# itself. Two pairs of readings, both on the chip at the cell's own load:
# * until PR 50 ``phi_post`` was held by its own ratio and ``b_post`` and
#   the gate each by theirs over the walk's size, limit 0.12: the worst of
#   them sound 0.023-0.116 over eight runs of the parent (PR 49's builder's,
#   13 seeds with the change's: ``b_post`` of an expert layer's MLP
#   connection, 0.967 of the limit on seed 4300001003), 3.2-7.3% over PR
#   48's four seeds; the control's worst 18.7-37.2%. A remainder under
#   cancelling signs swings fivefold with the seed, and 0.116 against 0.187
#   leaves no limit with room on both sides;
# * pooled (my chip runs, PR 50): sound 0.0303-0.0414 at the worst
#   connection of each of eighteen runs on eighteen seeds, fourteen untraced
#   and four traced (an expert layer's MLP connection every time: what routing
#   near a tie moves; ``phi_post``'s ``n C x n`` numbers lead the reading and
#   repeat from seed to seed; the attention connections 0.012-0.016), the
#   float8 control's worst through the harness 0.1532-0.1572 on four seeds
#   (an attention connection's; its least connection, the dense MLP's,
#   0.073-0.080), 3.7 times the sound worst. The limit stands between: the
#   sound worst at 0.52 of it, the control's at 1.9; the control comes out
#   not correct by this limit as by each of the other three (0.164-0.180 /
#   0.275-0.287 / 0.384-0.412 against 0.05 / 0.17 / 0.27).
# The gate's and the bias's own readings are printed beside
# (``hc_post_apart``: sound 0.023-0.116 on the same eighteen), held to
# nothing.
HC_LEAF_RTOL = 0.08
# ... and a connection's read and stream-mix leaves (``phi``, ``b`` and the
# gate of ``pre`` and of ``res``, pooled: ``connection_differences``), which
# are held by ANOTHER reading: |1 - the component of the program's gradient
# along the reference's, over the reference's norm|. Why not the L2
# difference: what reaches these leaves is the DIFFERENCE between streams,
# each of which is mostly the embedding row (N(0, 16^2), the
# configuration's file says why) beside sublayer outputs of order one, and
# the program keeps the streams in bf16: at 16 a bf16 step is 0.06-0.125,
# so a difference of a few tenths is known to two or three bits. The L2
# difference reads 0.11-0.72 sound on the chip and the same under the
# control (0.18-0.76: the noise is the program's own streams'), and a
# missing gradient only 1: no limit lies between with room. But that noise
# is unbiased and at right angles to the reference, so the component ALONG
# the reference is known well: sound 0.0000-0.0295 from 1 over the
# connections of four seeds on the chip, a halved gradient 0.5, a missing
# one 1, a wrong sign 2. It does not tell the control from a sound run
# (0.0006-0.042: the control fails by the other four limits), and it
# cannot see a fault at right angles to the reference; the tiny-width f32
# tests hold these leaves' L2 difference to 3e-4. The L2 readings are
# printed beside (``hc_mix_l2``), held to nothing.
HC_MIX_LEAF_RTOL = 0.12
HC = ('hc_attn', 'hc_mlp')
HC_MIX = ('_pre', '_res')
# The FIRST connection reads streams that are still copies of one row:
# H_pre scales what the sublayer's norm takes out again, and H_res, whose
# rows sum to one, mixes equal streams, so nothing reaches their parameters
# but rounding, on either side. A ratio of two roundings says nothing, and
# there is no reference to lie along: those six leaves are HELD TO NOTHING.
# Their difference is still read, over the reference's SAME leaves of the
# next connection (``connection_differences``), where the streams have
# parted, and printed (``held_to_nothing``): the read's 0.33-0.37 (what the
# program's bf16 leaves of a gradient that is nothing, beside the next
# connection's: the same noise as that one's L2 reading), the mix's under
# 1e-3; a program whose streams had parted before the first connection
# would read about 1 there, and shows in every other leaf.
ENTRY = 'dense/hc_attn/'
NOTHING_AT_ENTRY = tuple(leaf + kind for leaf in ('phi', 'b', 'alpha')
                         for kind in HC_MIX)


def _dims(config):
    return (config['qk_nope_head_dim'], config['qk_rope_head_dim'],
            config['v_head_dim'])


def mscale(factor, m):
    """YaRN's magnitude factor as the family computes it."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_of(config):
    """The program's ``rope_yarn`` mapping for the configuration's
    ``rope_scaling``: the frequencies' four numbers, the tables' factor
    and what multiplies the softmax scale."""
    scaling = config['rope_scaling']
    m_all = mscale(scaling['factor'], scaling['mscale_all_dim'])
    return dict(
        factor=scaling['factor'],
        original_max_position_embeddings=scaling[
            'original_max_position_embeddings'],
        beta_fast=scaling['beta_fast'], beta_slow=scaling['beta_slow'],
        attention_factor=mscale(scaling['factor'], scaling['mscale']) / m_all,
        score_factor=m_all * m_all)


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if config['hidden_act'] != 'silu' or config['tie_word_embeddings'] \
            or config['attention_bias'] or not config['norm_topk_prob'] \
            or config['rope_scaling']['type'] != 'yarn':
        raise ValueError('family xing4: silu, an untied head, no attention '
                         'bias, norm_topk_prob and YaRN only')
    if (config['scoring_func'], config['topk_method'], config['n_group'],
            config['topk_group'], config['moe_layer_freq']) != (
                'sigmoid', 'noaux_tc', 1, 1, 1):
        raise ValueError('family xing4: sigmoid scores, noaux_tc, one '
                         'group, every layer after the dense ones sparse')
    nope, rope, v = _dims(config)
    n = config['hc_mult']
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=config['hidden_size'],
        n_layers=config['num_hidden_layers'],
        n_heads=config['num_attention_heads'],
        max_len=config['max_position_embeddings'], causal=True,
        tied_embeddings=False, dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'],
        positions='rotary', rope_theta=float(config['rope_theta']),
        rope_yarn=yarn_of(config),
        latent_rank=config['kv_lora_rank'],
        latent_q_rank=config['q_lora_rank'], qk_nope_dim=nope,
        qk_rope_dim=rope, v_head_dim=v,
        mlp_dim=config['moe_intermediate_size'], gated_mlp=True,
        gelu='silu', norm='rms', norm_eps=config['rms_norm_eps'],
        mlp_bias=False, dense_lead=config['num_dense_layers_run'],
        dense_mlp_dim=config['intermediate_size'],
        moe_experts=config['n_routed_experts'],
        moe_top_k=config['num_experts_per_tok'],
        moe_held=config['num_experts_held'],
        moe_aux_coef=config['moe_aux_coef'], moe_scoring='sigmoid',
        moe_scale=config['routed_scaling_factor'],
        moe_shared_dim=config['n_shared_experts']
        * config['moe_intermediate_size'],
        embed_init_scale=config['embed_init_scale'],
        hc_streams=n, hc_iters=config['hc_sinkhorn_iters'],
        hc_clamp=(config['mhc_h_res_clamp_min'],
                  config['mhc_h_res_clamp_max']),
        hc_eps=config['hc_eps'])

    class Drawn(TransformerLM):
        """The program's model with its connections drawn as the
        configuration's file says (``assumed``: the draw, and why): the
        gates at ``hc_alpha_init``, the biases N(0, ``hc_bias_init_scale``
        ^2) around 0 (``H_pre`` about 1/2, ``H_post`` about 1) and, for the
        stream mix, around ``hc_res_diag_init`` on the diagonal, so that
        the three sets of coefficients differ from token to token and from
        the plain residual path. A draw, applied once to what ``init``
        returns; ``phi`` and every other matrix as the program draws
        them; the step is ``TransformerLM``'s."""

        def init(self, rng):
            params = super().init(rng)
            keys = iter(jax.random.split(jax.random.fold_in(rng, 48), 4))
            diag = jnp.concatenate([jnp.zeros((2 * n,)),
                                    jnp.eye(n).ravel()])

            def drawn(hc):
                bias = hc['bias']
                return dict(
                    hc, alpha=jnp.full_like(hc['alpha'],
                                            config['hc_alpha_init']),
                    bias=config['hc_res_diag_init'] * diag
                    + config['hc_bias_init_scale']
                    * jax.random.normal(next(keys), bias.shape, bias.dtype))
            for block in (params['block_000'], params['blocks']['global']):
                for name in HC:
                    block[name] = drawn(block[name])
            return params

    return Drawn(cfg)


def flops_per_token(config, seq):
    """Model FLOPs one training token requires on this chip: 3 x forward,
    where forward = 2 x (a layer's attention matrices, the q path through
    its rank; the dense layers' MLP whole; in an expert layer the router,
    the shared expert whole and the held experts at the pairs a token is
    EXPECTED to have among them, ``num_experts_per_tok x num_experts_held /
    n_routed_experts``; the connections' products, two a layer: ``v phi``
    at ``n C x n (n + 2)`` and the three mixes at ``C x n (n + 2)``; the
    head's matmul over the vocabulary slice) + QK^T at ``qk_head_dim`` and
    PV at ``v_head_dim`` over half of ``seq``, the keys a query sees under
    the causal mask. Norms, the softmaxes and Sinkhorn's divisions are not
    counted."""
    d, heads = config['hidden_size'], config['num_attention_heads']
    nope, rope, v = _dims(config)
    rank, q_rank = config['kv_lora_rank'], config['q_lora_rank']
    n = config['hc_mult']
    attention = d * q_rank + q_rank * heads * (nope + rope) \
        + d * (rank + rope) + rank * heads * (nope + v) + heads * v * d
    connections = 2 * (n * d + d) * n * (n + 2)
    layers = config['num_hidden_layers']
    dense = config['num_dense_layers_run']
    moe = config['moe_intermediate_size']
    pairs = config['num_experts_per_tok'] * config['num_experts_held'] \
        / config['n_routed_experts']
    expert_layer = d * config['n_routed_experts'] \
        + 3 * d * moe * (config['n_shared_experts'] + pairs)
    fwd = 2 * (layers * (attention + connections)
               + dense * 3 * d * config['intermediate_size']
               + (layers - dense) * expert_layer
               + d * config['vocab_size']) \
        + layers * 2 * (seq / 2) * heads * (nope + rope + v)
    return 3 * fwd


# -- the program's layout under the published one ---------------------------

def _connection(hc):
    """A connection's three leaves under the equations' names:
    ``phi``'s columns are ``pre | post | res`` (``res`` row-major)."""
    n = math.isqrt(hc['bias'].shape[-1] + 1) - 1      # n (n + 2) columns
    phi, alpha, bias = hc['phi'], hc['alpha'], hc['bias']
    return {'phi_pre': phi[..., :n], 'phi_post': phi[..., n:2 * n],
            'phi_res': phi[..., 2 * n:],
            'alpha_pre': alpha[..., 0], 'alpha_post': alpha[..., 1],
            'alpha_res': alpha[..., 2],
            'b_pre': bias[..., :n], 'b_post': bias[..., n:2 * n],
            'b_res': bias[..., 2 * n:]}


def to_reference_params(params):
    """The program's tree under the reference's names and in the
    published column order (kanana-2's three fixed gathers of an
    attention's columns a layer, the q one on the up-projection's; the
    experts, which are most of the bytes, are not copied). The head's
    parts are read from the shapes."""
    import numpy as np
    lead, stack = params['block_000'], params['blocks']['global']
    attn = lead['attn']
    rank = attn['kv_norm']['scale'].shape[-1]
    rope = attn['kv_a']['kernel'].shape[-1] - rank
    q, kv, o = (attn[k]['kernel'].shape[i] for k, i in (
        ('q', -1), ('kv_b', -1), ('out', -2)))
    heads = (q - kv + o) // rope
    dims = ((kv - o) // heads, rope, o // heads)
    q_cols = np.asarray(kanana2.published_q_columns(heads, dims))
    kva_cols = np.asarray(kanana2.published_kva_columns(rank, rope))
    kvb_cols = np.asarray(kanana2.published_kvb_columns(heads, dims))

    def shared(b):
        return {'hc_attn': _connection(b['hc_attn']),
                'hc_mlp': _connection(b['hc_mlp']),
                'ln_attn': b['ln1']['scale'],
                'w_qa': b['attn']['q_a']['kernel'],
                'ln_q': b['attn']['q_norm']['scale'],
                'w_qb': b['attn']['q']['kernel'][..., q_cols],
                'w_kva': b['attn']['kv_a']['kernel'][..., kva_cols],
                'ln_kv': b['attn']['kv_norm']['scale'],
                'w_kvb': b['attn']['kv_b']['kernel'][..., kvb_cols],
                'w_o': b['attn']['out']['kernel']}
    dense = dict(shared(lead), ln_ffn=lead['ln2']['scale'],
                 w_ffn_gate_up=lead['mlp']['up']['kernel'],
                 w_ffn_down=lead['mlp']['down']['kernel'])
    mlp = stack['mlp']
    layers = dict(shared(stack), ln_mlp=stack['ln2']['scale'],
                  w_router=mlp['router']['kernel'],
                  b_select=mlp['select_bias'],
                  w_gate_up=mlp['up'], w_down=mlp['down'],
                  ws_gate_up=mlp['shared']['up']['kernel'],
                  ws_down=mlp['shared']['down']['kernel'])
    return {'embed': params['embed']['table'], 'dense': dense,
            'layers': layers, 'ln_final': params['ln_f']['scale'],
            'head': params['lm_head']['kernel']}


# -- the plain reference -----------------------------------------------------

def yarn_inv_freq(config):
    """The rotary pairs' frequencies under ``rope_scaling`` (the module's
    docstring), ``[qk_rope_head_dim / 2]`` in float64."""
    import numpy as np
    scaling, dim = config['rope_scaling'], config['qk_rope_head_dim']
    base = float(config['rope_theta'])
    pairs = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * pairs / dim)
    original = scaling['original_max_position_embeddings']

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair_of(scaling['beta_fast'])), 0)
    high = min(math.ceil(pair_of(scaling['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    return extra / scaling['factor'] * ramp + extra * (1.0 - ramp)


def reference_sinkhorn(r, config, iters=None, row_first=False):
    """``H_res [s, n, n]`` from the logits ``r [s, n, n]`` (``[., i,
    j]``: to stream ``i`` from stream ``j``): the clamp, ``exp``, and the
    rounds as a Python loop."""
    import jax.numpy as jnp
    eps = config['hc_eps']
    m = jnp.exp(jnp.clip(r, config['mhc_h_res_clamp_min'],
                         config['mhc_h_res_clamp_max']))

    def columns(m):
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    def rows(m):
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    for _ in range(config['hc_sinkhorn_iters'] if iters is None else iters):
        m = columns(rows(m)) if row_first else rows(columns(m))
    return m


def reference_connection(w, x, sublayer, config, lo=lambda t: t,
                         sinkhorn_iters=None, row_first=False,
                         post_factor=2.0, pre='sigmoid'):
    """``HC(x, F)`` for ``x [s, n, C]`` (the module's docstring);
    ``sublayer``: ``u [s, C] -> y [s, C]``, its pre-norm inside. The
    caller sets the matmul precision."""
    import jax
    import jax.numpy as jnp
    s, n, _ = x.shape
    v = x.reshape(s, -1)
    v = v / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                     + config['hc_eps'])
    p = w['alpha_pre'] * (lo(v) @ lo(w['phi_pre'])) + w['b_pre']
    q = w['alpha_post'] * (lo(v) @ lo(w['phi_post'])) + w['b_post']
    r = w['alpha_res'] * (lo(v) @ lo(w['phi_res'])) + w['b_res']
    h_pre = jax.nn.softmax(p, -1) if pre == 'softmax' else jax.nn.sigmoid(p)
    h_post = post_factor * jax.nn.sigmoid(q)
    h_res = reference_sinkhorn(r.reshape(s, n, n), config, sinkhorn_iters,
                               row_first)
    y = sublayer(jnp.einsum('si,sic->sc', h_pre, x))
    return jnp.einsum('sij,sjc->sic', h_res, x) \
        + h_post[:, :, None] * y[:, None, :]


def reference_loss(ref_params, tokens, targets, config, matmul_dtype=None,
                   q_norm=True, score_factor=True, yarn=True, exit='sum',
                   **connection):
    """Mean cross-entropy of ONE sequence (``tokens``, ``targets``:
    ``[s]``) in float32.

    The switches exist to show what the comparison tells apart (the
    tests, and once on the chip): one Sinkhorn round for the twenty
    (``sinkhorn_iters=1``), a round as rows then columns (``row_first``),
    ``H_post`` without its 2 (``post_factor=1.0``), ``H_pre`` by a softmax
    over the streams (``pre='softmax'``), the first stream alone at the
    exit (``exit='first'``; ``exit='mean'``, the streams averaged, is the
    SAME function under the final norm and reads as the sound reference:
    the tests say so), q without its norm, the softmax scale without
    ``mscale^2`` (``score_factor=False``), the rotary frequencies without
    YaRN, the operands of every product held to a lower precision's
    mantissa (``matmul_dtype``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = config['rms_norm_eps']
    heads, n = config['num_attention_heads'], config['hc_mult']
    nope, rope, vd = _dims(config)
    rank = config['kv_lora_rank']
    scaling = config['rope_scaling']
    s = tokens.shape[0]

    inv_freq = yarn_inv_freq(config) if yarn else float(
        config['rope_theta']) ** (
            -2.0 * np.arange(rope // 2, dtype=np.float64) / rope)
    m_all = mscale(scaling['factor'], scaling['mscale_all_dim'])
    table_factor = mscale(scaling['factor'], scaling['mscale']) / m_all
    angle = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    both = np.concatenate([angle, angle], -1)                    # [s, rope]
    cos = jnp.asarray(np.cos(both) * table_factor, jnp.float32)
    sin = jnp.asarray(np.sin(both) * table_factor, jnp.float32)
    scale = (nope + rope) ** -0.5 * (m_all * m_all if score_factor else 1.0)

    def rms_norm(x, g):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * g

    lo = kanana2._held_in(matmul_dtype)

    def rotate(x, cos, sin):
        """The published rotation of ``x [..., rope]``: the adjacent
        pairs to the two halves, then by halves."""
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attend(q_nope, q_rope, k_nope, k_rope, v):
        """``[s, heads * v]``, a block of queries at a time."""
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError('seq %d is not a multiple of %d' % (s, block))
        kpos = jnp.arange(s)

        def one_block(args):
            qn, qr, start = args
            scores = (jnp.einsum('qhd,khd->hqk', lo(qn), lo(k_nope))
                      + jnp.einsum('qhd,kd->hqk', lo(qr), lo(k_rope))) * scale
            keep = (start + jnp.arange(block))[:, None] >= kpos[None, :]
            scores = jnp.where(keep[None], scores, -jnp.inf)
            return jnp.einsum('hqk,khd->qhd',
                              lo(jax.nn.softmax(scores, axis=-1)), lo(v))

        out = jax.lax.map(jax.checkpoint(one_block), (
            q_nope.reshape(s // block, block, heads, nope),
            q_rope.reshape(s // block, block, heads, rope),
            jnp.arange(0, s, block)))
        return out.reshape(s, heads * vd)

    def mla(x, w):
        qa = lo(x) @ lo(w['w_qa'])
        if q_norm:
            qa = rms_norm(qa, w['ln_q'])
        q = (lo(qa) @ lo(w['w_qb'])).reshape(s, heads, nope + rope)
        c = lo(x) @ lo(w['w_kva'])
        kv = (lo(rms_norm(c[:, :rank], w['ln_kv'])) @ lo(w['w_kvb'])
              ).reshape(s, heads, nope + vd)
        o = attend(q[..., :nope], rotate(q[..., nope:], cos[:, None],
                                         sin[:, None]),
                   kv[..., :nope], rotate(c[:, rank:], cos, sin),
                   kv[..., nope:])
        return lo(o) @ lo(w['w_o'])

    def connected(x, w, name, sublayer):
        return reference_connection(w[name], x, sublayer, config, lo,
                                    **connection)

    def dense_layer(x, w):
        x = connected(x, w, 'hc_attn',
                      lambda u: mla(rms_norm(u, w['ln_attn']), w))
        return connected(x, w, 'hc_mlp', lambda u: kanana2._gated(
            rms_norm(u, w['ln_ffn']), w['w_ffn_gate_up'], w['w_ffn_down'],
            lo))

    def expert_layer(x, w):
        x = connected(x, w, 'hc_attn',
                      lambda u: mla(rms_norm(u, w['ln_attn']), w))
        return connected(
            x, w, 'hc_mlp', lambda u: kanana2.reference_expert_layer(
                w, rms_norm(u, w['ln_mlp']), config,
                matmul_dtype=matmul_dtype))

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        x = jnp.repeat(p['embed'][tokens][:, None, :], n, axis=1)
        x = jax.checkpoint(dense_layer)(x, p['dense'])
        run = jax.checkpoint(expert_layer)
        x, _ = jax.lax.scan(lambda x, w: (run(x, w), None), x, p['layers'])
        h = {'sum': jnp.sum(x, 1), 'mean': jnp.mean(x, 1),
             'first': x[:, 0]}[exit]
        h = rms_norm(h, p['ln_final'])

        def nll(args):
            rows, gold = args
            logits = lo(rows) @ lo(p['head'])
            return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, gold[:, None], axis=-1)[:, 0]
        block = min(LOSS_ROWS, s)
        if s % block:
            raise ValueError('seq %d is not a multiple of %d' % (s, block))
        return jnp.mean(jax.lax.map(
            jax.checkpoint(nll), (h.reshape(s // block, block, -1),
                                  targets.reshape(s // block, block))))


def reference_loss_and_grad_norm(config, ref_params, batch, **switches):
    """(loss, norm of the gradient) of the plain reference on ``batch``:
    the mean over its sequences, one at a time, each sequence's gradient
    taken to the HOST and summed there (on the chip one gradient is 3 GB
    beside the parameters and a sequence's 5 GB of temporaries: a second
    would not be sure of its room); both Python floats. Where the engine
    left the program's own gradient of this batch
    (``engines/trainer_leaves.py``), the norm is RAISED by the worst
    leaf's difference (:func:`held_to_every_leaf`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    one = jax.jit(jax.value_and_grad(
        lambda p, tokens, targets: reference_loss(
            p, tokens, targets, config, **switches)))
    tokens = jnp.asarray(batch['tokens'])
    targets = jnp.asarray(batch['targets'])
    n = tokens.shape[0]
    loss, grads = 0.0, None
    for i in range(n):
        loss_i, grads_i = one(ref_params, tokens[i], targets[i])
        loss += float(loss_i) / n
        grads_i = jax.tree.map(np.asarray, grads_i)
        grads = grads_i if grads is None else jax.tree.map(
            np.add, grads, grads_i)
    norm = math.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                         for g in jax.tree.leaves(grads))) / n
    from benchmark.engines import trainer_leaves
    program = trainer_leaves.PROBE.pop('gradients', None)
    if program is None:
        return loss, norm
    return loss, held_to_every_leaf(norm, to_reference_params(program),
                                    grads, n)


def leaf_limit(name):
    parts = name.split('/')
    if any(p in HC for p in parts):
        return HC_MIX_LEAF_RTOL if any(p.endswith(HC_MIX) for p in parts) \
            else HC_LEAF_RTOL
    if 'w_router' in parts:
        return ROUTER_LEAF_RTOL
    return ROUTED_LEAF_RTOL if any(p in ROUTED for p in parts) \
        else LEAF_RTOL


def _sq(a):
    import numpy as np
    return np.sum(np.square(np.asarray(a, np.float64)).reshape(
        (a.shape[0], -1)), axis=1)


def _over(d, r):
    """``d / r`` as a Python float; nothing over nothing is 0, something
    over nothing infinity."""
    d, r = float(d), float(r)
    return d / r if r else (0.0 if not d else math.inf)


def connection_differences(program, reference, n=1, l2=None,
                           post_apart=None):
    """The readings of the connections' leaves, which a leaf's own ratio
    does not give (``{leaf: reading}``, Python floats, a stack's a layer at
    a time):

    * the write-back's leaves (``phi_post``, its bias ``b_post`` and gate
      ``alpha_post``): ONE reading for the three, given to each: ``|program
      - reference / n|`` over ``|reference / n|`` in L2 over the three
      together. A bias's gradient is ``sum over the tokens of dlogit``, its
      gate's ``sum of dlogit x (v phi)``, ``phi``'s ``sum of v (x) dlogit``:
      the same terms, and in the first two under signs that cancel, so their
      own value is a small remainder that swings with the seed
      (``b_post``'s own ratio read 0.03-0.45 over the four expert layers of
      ONE seed on the chip, where routing near a tie moves a few tokens'
      terms, and over the walk's size 0.02-0.12 from seed to seed:
      ``HC_LEAF_RTOL`` says what that cost; PERF.md section 6). ``phi``'s
      ``n C x n`` numbers carry the same terms un-cancelled and lead the
      pooled reading. What the two read alone goes into ``post_apart``
      where one is given (``{leaf: reading}``), for the record: ``|program
      - reference / n|`` over the larger of ``|reference / n|`` and the
      WALK'S SIZE, ``|phi_post's reference / n| / sqrt(n C)`` (``v`` has ``n
      C`` numbers of mean square 1 for every token, so ``phi``'s norm over
      ``sqrt(n C)`` is the size those sums have where nothing cancels).
    * the read's and the stream mix's leaves (``phi``, ``b`` and the gate of
      ``pre`` and of ``res``): ONE reading for the three of a kind, given
      to each of the three: ``|1 - <program, reference / n> / |reference /
      n|^2|`` over the three together (``phi``'s ``n C x n`` numbers lead
      it), how far the program's gradient ALONG the reference's is from
      the reference's own length (``HC_MIX_LEAF_RTOL`` says why). Their L2
      difference over the reference's norm goes into ``l2`` where one is
      given (``{phi's leaf: reading}``), for the record.
    * the FIRST connection's ``pre`` and ``res``, which nothing reaches:
      the norm of their differences together over the norm of the NEXT
      connection's references together (``ENTRY``)."""
    import numpy as np
    out = {}

    def stacked(tree, name, where):
        a = np.asarray(tree[name], np.float64)
        return a[None] if where == 'dense' else a
    for where in ('dense', 'layers'):
        for conn in HC:
            got, want = program[where][conn], reference[where][conn]
            entry = where + '/' + conn + '/' == ENTRY
            over = reference['dense']['hc_mlp'] if entry else want

            def names(leaf, layers):
                leaf = '%s/%s/%s' % (where, conn, leaf)
                return [leaf] if where == 'dense' else [
                    '%s/%d' % (leaf, i) for i in range(layers)]

            def apart(leaves, over=want):
                """(the leaves' squared difference together, the squared
                norm of ``over``'s same leaves together), a layer at a
                time."""
                return (sum(_sq(stacked(got, leaf, where)
                                - stacked(want, leaf, where) / n)
                            for leaf in leaves),
                        sum(_sq(stacked(over, leaf, where) / n)
                            for leaf in leaves))
            leaves = ['phi_post', 'b_post', 'alpha_post']
            d, r = apart(leaves)
            reading = [math.sqrt(_over(d[i], r[i])) for i in range(len(d))]
            for leaf in leaves:
                out.update(zip(names(leaf, len(d)), reading))
            if post_apart is not None:
                walk = np.sqrt(apart(['phi_post'])[1] / stacked(
                    want, 'phi_post', where)[0].shape[0])
                for leaf in leaves[1:]:
                    d, r = apart([leaf])
                    post_apart.update(zip(names(leaf, len(d)), [
                        _over(math.sqrt(d[i]),
                              max(math.sqrt(r[i]), walk[i]))
                        for i in range(len(d))]))
            for kind in HC_MIX:
                leaves = ['phi' + kind, 'b' + kind, 'alpha' + kind]
                d, r = apart(leaves, over)
                l2_apart = [math.sqrt(_over(d[i], r[i]))
                            for i in range(len(d))]
                if entry:
                    reading = l2_apart
                else:
                    along = sum(np.sum((stacked(got, leaf, where)
                                        * stacked(want, leaf, where) / n
                                        ).reshape(len(d), -1), axis=1)
                                for leaf in leaves)
                    reading = [abs(1.0 - float(along[i]) / float(r[i]))
                               if r[i] else _over(d[i], r[i])
                               for i in range(len(d))]
                    if l2 is not None:
                        l2.update(zip(names('phi' + kind, len(d)),
                                      l2_apart))
                for leaf in leaves:
                    out.update(zip(names(leaf, len(d)), reading))
    return out


def held_to_every_leaf(norm, program, reference, n):
    """``norm x (1 + GRAD_NORM_RTOL x worst)``, as ``kanana2.py``'s: the
    reference's global norm, raised by the largest of the leaves'
    differences (``mellum2.leaf_differences``: a stack's leaves a layer at
    a time; the connections' write-backs, reads and stream mixes by
    :func:`connection_differences`, the reads' and mixes' L2 differences
    beside them as ``hc_mix_l2`` and the write-backs' gates and biases alone
    as ``hc_post_apart``, held to nothing, as the first connection's six
    leaves that nothing reaches are, ``held_to_nothing``), each in units of
    its leaf's limit. ``b_select``'s gradient is nothing on both sides or
    counts as a thousand limits. Prints the leaves' readings as one line and
    returns a Python float, whichever leaf is the worst."""
    import json

    from benchmark import harness
    from benchmark.models.mellum2 import leaf_differences
    leaves = leaf_differences(program, reference, n)
    l2, post_apart = {}, {}
    leaves.update(connection_differences(program, reference, n, l2,
                                         post_apart))
    nothing = tuple(ENTRY + leaf for leaf in NOTHING_AT_ENTRY)
    in_limits = {name: d / leaf_limit(name) if math.isfinite(d) else 1e3
                 for name, d in leaves.items() if name not in nothing}
    worst = max(in_limits, key=in_limits.get)
    print(json.dumps({'gradient_leaves': leaves, 'worst': worst,
                      'worst_difference': leaves[worst],
                      'worst_in_limits': in_limits[worst],
                      'hc_mix_l2': l2, 'hc_post_apart': post_apart,
                      'held_to_nothing': {name: leaves[name]
                                          for name in nothing},
                      'limits': {'leaf': LEAF_RTOL,
                                 'routed_leaf': ROUTED_LEAF_RTOL,
                                 'router_leaf': ROUTER_LEAF_RTOL,
                                 'hc_leaf': HC_LEAF_RTOL,
                                 'hc_mix_leaf': HC_MIX_LEAF_RTOL},
                      'reference_global_grad_norm': norm}), flush=True)
    return float(norm * (1.0 + harness.GRAD_NORM_RTOL * in_limits[worst]))
