"""Model family ``kanana2``: kakaocorp's kanana-2-30b-a3b
(``config.json`` of ``kakaocorp/kanana-2-30b-a3b-instruct-2601``,
``model_type: deepseek_v3``), on ONE CHIP'S SHARE of a deployment that
divides each layer over several chips: ``num_experts_held`` of the
``n_routed_experts`` routed experts (the first ones), attention and the
shared expert whole, a slice of the vocabulary.

The four things a family gives (see ``transformer.py``): :func:`build`,
the plain reference (:func:`reference_loss`,
:func:`reference_loss_and_grad_norm`), :func:`to_reference_params` and
:func:`flops_per_token`.

The architecture as the reference computes it, in float32 under
``jax.default_matmul_precision('highest')``, sharing no code with
``autodist_tpu.models`` (``x [s, hidden]``; no linear layer has a bias;
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``):

* ``h = E[tokens]``. Layer ``i``: ``h = h + MLA(RMSNorm(h))``, ``h = h +
  MLP_i(RMSNorm(h))``. Then ``RMSNorm``, ``logits = h W_head`` (untied,
  over the slice), mean next-token cross-entropy.
* ``MLA(x)`` (no ``q_lora_rank``): ``q = x W_q`` in
  ``num_attention_heads`` heads, each ``q_nope [qk_nope_head_dim] |
  q_rope [qk_rope_head_dim]``; ``x W_kva = c_kv [kv_lora_rank] | k_rope
  [qk_rope_head_dim]``, ONE rotary key for all heads; ``RMSNorm(c_kv)
  W_kvb`` in heads, each ``k_nope [qk_nope_head_dim] | v
  [v_head_dim]``. ``q_rope`` and ``k_rope`` are rotated as the published
  code rotates them under ``rope_interleave``: the adjacent pairs ``(2j,
  2j + 1)`` are moved to ``(j, j + d / 2)``, then ``x cos + rotate_half(
  x) sin`` at ``inv_freq_j = rope_theta^(-2j / d)`` (``rope_scaling``
  null: no factor on the scores). Head n: ``softmax((q_nope_n k_nope_n^T
  + q_rope_n k_rope^T) / sqrt(qk_head_dim))`` over the keys ``j <= i``,
  times ``v_n``; the heads side by side times ``W_o``.
* ``MLP_i``, ``i < first_k_dense_replace``: ``(silu(x W_gate) * (x
  W_up)) W_down`` at ``intermediate_size``.
* ``MLP_i`` after them: ``s = sigmoid(x W_r)`` over all
  ``n_routed_experts``; the ``num_experts_per_tok`` experts with the
  largest ``s_e + b_e`` (``b``: ``e_score_correction_bias``; it SELECTS
  only, and takes no gradient; ``n_group`` 1, so no group limit); ``w_e
  = routed_scaling_factor x s_e / (sum of the chosen s + 1e-20)``;
  ``routed = sum over the chosen e that are HELD (e < num_experts_held)
  of w_e (silu(x W_gate_e) * (x W_up_e)) W_down_e`` at
  ``moe_intermediate_size``; ``shared`` the same gated MLP at
  ``n_shared_experts x moe_intermediate_size`` for every token; ``MLP_i
  = routed + shared``. What the experts held elsewhere would add is
  left out, here as in the program, and the partial sum goes on.

Departures from the published code: none in the equations above; the
update of ``b`` between steps and any balancing loss are outside a
step's loss and are left out (the configuration's ``assumed``).

How it fits beside the training state (as ``mellum2.py``): one sequence
at a time, each layer computed again in the backward pass, attention a
block of queries against every key, the experts one at a time, the four
expert layers scanned over their stack, the logits ``LOSS_ROWS``
positions at a time.
"""
import math

QUERY_BLOCK = 512
LOSS_ROWS = 2048

# The limits on one leaf of the gradient, |program - reference| /
# |reference| in L2, a layer at a time (held_to_every_leaf), each between
# its two readings on the chip (my chip runs, PR 39; PERF.md §6 has every
# reading; embedding rows at N(0, 8^2) and N(0, 32^2), fourteen sound
# runs, the faults at a fifteenth seed and 32). A leaf not behind a
# router: sound 1.2-1.5% at worst (the expert layers' w_q, w_kva,
# ln_attn; embed 1.0, head 0.8); with every product's operands held to
# float8_e4m3's mantissa, the nearest precision below the program's
# bfloat16, 9.9% (ln_attn); scale 128^-0.5 31%; the rotary part left out
# of the scores 66%; the shared expert left out: its leaves' reference
# gradient is nothing.
LEAF_RTOL = 0.04
# ... a leaf whose gradient comes through the routed experts (the
# program routes on bf16 activations, so near a tie its six of 128
# differ from the reference's for a few tokens in a hundred, and an
# expert here sees 1,536 rows where Mellum2's sees 4,096): sound 9.2-12.2%
# (w_gate_up, w_down; ln_mlp 3.3-4.4%); with one held expert's rows left
# out 30.7%, with float8's mantissa 29.4%, with the experts selected
# without a selection bias of N(0, 0.05^2) 80.7%. (Operands at
# bfloat16's mantissa read as the sound reference, 11.5%: the program's
# own precision.)
ROUTED_LEAF_RTOL = 0.20
ROUTED = ('ln_mlp', 'w_gate_up', 'w_down')
# ... and the router's own leaf: the chosen experts' sigmoid scores are
# 0.84-0.95, so what reaches the router through their weights is small
# beside what a token that changes experts moves: sound 13.7-18.3%;
# selected without the bias 114%, float8's mantissa 40.7%, an expert's
# rows left out 32.7% (that fault is the experts' leaves' to catch).
ROUTER_LEAF_RTOL = 0.30


def _dims(config):
    return (config['qk_nope_head_dim'], config['qk_rope_head_dim'],
            config['v_head_dim'])


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if config['hidden_act'] != 'silu' or config['tie_word_embeddings'] \
            or config['attention_bias'] or not config['norm_topk_prob'] \
            or config['q_lora_rank'] is not None \
            or config['rope_scaling'] is not None \
            or not config['rope_interleave']:
        raise ValueError('family kanana2: silu, an untied head, no attention '
                         'bias, norm_topk_prob, no q_lora_rank, no rope '
                         'scaling, rope_interleave only')
    if (config['scoring_func'], config['topk_method'], config['n_group'],
            config['topk_group'], config['moe_layer_freq']) != (
                'sigmoid', 'noaux_tc', 1, 1, 1):
        raise ValueError('family kanana2: sigmoid scores, noaux_tc, one '
                         'group, every layer after the dense ones sparse')
    nope, rope, v = _dims(config)
    if config['qk_head_dim'] != nope + rope:
        raise ValueError('family kanana2: qk_head_dim is not nope + rope')
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=config['hidden_size'],
        n_layers=config['num_hidden_layers'],
        n_heads=config['num_attention_heads'],
        max_len=config['max_position_embeddings'], causal=True,
        tied_embeddings=False, dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'],
        positions='rotary', rope_theta=float(config['rope_theta']),
        latent_rank=config['kv_lora_rank'], qk_nope_dim=nope,
        qk_rope_dim=rope, v_head_dim=v,
        mlp_dim=config['moe_intermediate_size'], gated_mlp=True,
        gelu='silu', norm='rms', norm_eps=config['rms_norm_eps'],
        mlp_bias=False, dense_lead=config['first_k_dense_replace'],
        dense_mlp_dim=config['intermediate_size'],
        moe_experts=config['n_routed_experts'],
        moe_top_k=config['num_experts_per_tok'],
        moe_held=config['num_experts_held'],
        moe_aux_coef=config['moe_aux_coef'], moe_scoring='sigmoid',
        moe_scale=config['routed_scaling_factor'],
        moe_shared_dim=config['n_shared_experts']
        * config['moe_intermediate_size'],
        embed_init_scale=config['embed_init_scale'])
    return TransformerLM(cfg)


def flops_per_token(config, seq):
    """Model FLOPs one training token requires on this chip: 3 x forward,
    where forward = 2 x (a layer's attention matrices; the dense layers'
    MLP whole; in an expert layer the router, the shared expert whole
    and the held experts at the pairs a token is EXPECTED to have among
    them, ``num_experts_per_tok x num_experts_held / n_routed_experts``
    experts of three ``hidden x moe_intermediate`` matrices) + the
    head's matmul over the vocabulary slice + QK^T at ``qk_head_dim``
    and PV at ``v_head_dim`` over half of ``seq``, the keys a query sees
    under the causal mask."""
    d, heads = config['hidden_size'], config['num_attention_heads']
    nope, rope, v = _dims(config)
    rank = config['kv_lora_rank']
    attention = d * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + v) + heads * v * d
    layers = config['num_hidden_layers']
    dense = config['first_k_dense_replace']
    moe = config['moe_intermediate_size']
    pairs = config['num_experts_per_tok'] * config['num_experts_held'] \
        / config['n_routed_experts']
    expert_layer = d * config['n_routed_experts'] \
        + 3 * d * moe * (config['n_shared_experts'] + pairs)
    fwd = 2 * (layers * attention
               + dense * 3 * d * config['intermediate_size']
               + (layers - dense) * expert_layer
               + d * config['vocab_size']) \
        + layers * 2 * (seq / 2) * heads * (nope + rope + v)
    return 3 * fwd


# -- the program's layout under the published one ---------------------------

def published_q_columns(heads, dims):
    """For each column of the published ``W_q`` (head-major, each head
    ``nope | rope``, the rope part's pairs interleaved) the column of
    the program's that holds it. The program keeps, for every lane
    block's heads, their nope parts and then their rope parts
    (``fa.latent_columns``), and a rope part half-split: its column
    ``j`` is the published ``2j``, its ``d / 2 + j`` the published ``2j
    + 1``."""
    from autodist_tpu.kernels import flash_attention as fa
    nope, rope, _ = dims
    at = {published: program for program, published in
          enumerate(fa.latent_columns(heads, dims))}
    cols = []
    for h in range(heads):
        base = h * (nope + rope)
        cols += [at[base + i] for i in range(nope)]
        cols += [at[base + nope + _half_split(i, rope)] for i in range(rope)]
    return cols


def _half_split(i, rope):
    """Where the half-split layout keeps interleaved column ``i``."""
    return i // 2 + (i % 2) * (rope // 2)


def published_kva_columns(rank, rope):
    """... of the published ``W_kva`` (``c_kv | k_rope``, interleaved):
    the program keeps the rotary key first, half-split, then c_kv."""
    return [rope + i for i in range(rank)] \
        + [_half_split(i, rope) for i in range(rope)]


def published_kvb_columns(heads, dims):
    """... of the published ``W_kvb`` (head-major, each head ``k_nope |
    v``): the program keeps every head's k_nope, then every head's v."""
    nope, _, v = dims
    return [c for h in range(heads)
            for c in list(range(h * nope, (h + 1) * nope))
            + list(range(heads * nope + h * v, heads * nope + (h + 1) * v))]


def to_reference_params(params):
    """The program's tree under the reference's names and in the
    published column order (three fixed gathers of an attention's
    columns a layer; the experts, which are most of the bytes, are not
    copied). The head's parts are read from the shapes."""
    import numpy as np
    lead, stack = params['block_000'], params['blocks']['global']
    attn = lead['attn']
    rank = attn['kv_norm']['scale'].shape[-1]
    rope = attn['kv_a']['kernel'].shape[-1] - rank
    q, kv, o = (attn[k]['kernel'].shape[i] for k, i in (
        ('q', -1), ('kv_b', -1), ('out', -2)))
    heads = (q - kv + o) // rope
    dims = ((kv - o) // heads, rope, o // heads)
    q_cols = np.asarray(published_q_columns(heads, dims))
    kva_cols = np.asarray(published_kva_columns(rank, rope))
    kvb_cols = np.asarray(published_kvb_columns(heads, dims))

    def attention(b):
        return {'ln_attn': b['ln1']['scale'],
                'w_q': b['attn']['q']['kernel'][..., q_cols],
                'w_kva': b['attn']['kv_a']['kernel'][..., kva_cols],
                'ln_kv': b['attn']['kv_norm']['scale'],
                'w_kvb': b['attn']['kv_b']['kernel'][..., kvb_cols],
                'w_o': b['attn']['out']['kernel']}
    dense = dict(attention(lead), ln_ffn=lead['ln2']['scale'],
                 w_ffn_gate_up=lead['mlp']['up']['kernel'],
                 w_ffn_down=lead['mlp']['down']['kernel'])
    mlp = stack['mlp']
    layers = dict(attention(stack), ln_mlp=stack['ln2']['scale'],
                  w_router=mlp['router']['kernel'],
                  b_select=mlp['select_bias'],
                  w_gate_up=mlp['up'], w_down=mlp['down'],
                  ws_gate_up=mlp['shared']['up']['kernel'],
                  ws_down=mlp['shared']['down']['kernel'])
    return {'embed': params['embed']['table'], 'dense': dense,
            'layers': layers, 'ln_final': params['ln_f']['scale'],
            'head': params['lm_head']['kernel']}


# -- the plain reference -----------------------------------------------------

def _held_in(dtype):
    """Rounds a product's operand to ``dtype``'s mantissa bits (identity
    for None), by ``reduce_precision``, which the TPU's compiler does
    not drop as excess precision (``mellum2.py``); the gradient passes
    through unrounded."""
    import jax
    import jax.numpy as jnp
    if dtype is None:
        return lambda t: t
    mantissa = jnp.finfo(dtype).nmant
    return lambda t: t + jax.lax.stop_gradient(
        jax.lax.reduce_precision(t, 8, mantissa) - t)


def _gated(x, gate_up, down, lo):
    import jax
    h = jax.nn.silu(lo(x) @ lo(gate_up[:, 0])) * (lo(x) @ lo(gate_up[:, 1]))
    return lo(h) @ lo(down)


def reference_expert_layer(w, x, config, select_bias=True, shared=True,
                           drop_expert=None, matmul_dtype=None):
    """``MLP_i(x)`` of an expert layer for ``x [s, hidden]`` (the
    module's docstring): the held experts' part of the routed sum, every
    held expert computed for every position and weighted by ``w_e``
    (zero where it was not chosen), plus the shared expert. The caller
    sets the matmul precision."""
    import jax
    import jax.numpy as jnp
    lo = _held_in(matmul_dtype)
    held, top_k = config['num_experts_held'], config['num_experts_per_tok']
    scores = jax.nn.sigmoid(lo(x) @ lo(w['w_router']))
    by = scores + jax.lax.stop_gradient(w['b_select']) if select_bias \
        else scores
    _, idx = jax.lax.top_k(by, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = config['routed_scaling_factor'] * chosen / (
        jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    def one_expert(e, gate_up, down):              # [d, 2, f], [f, d]
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        if drop_expert is not None:
            w_e = jnp.where(e == drop_expert, 0.0, w_e)
        return w_e[:, None] * _gated(x, gate_up, down, lo)

    # (the running sum is no input of the checkpointed part, so the scan
    # keeps nothing a position long for each expert)
    out, _ = jax.lax.scan(
        lambda out, args: (out + jax.checkpoint(one_expert)(*args), None),
        jnp.zeros_like(x), (jnp.arange(held), w['w_gate_up'], w['w_down']))
    if shared:
        out = out + _gated(x, w['ws_gate_up'], w['ws_down'], lo)
    return out


def reference_loss(ref_params, tokens, targets, config, rope_scores=True,
                   scale_dim=None, select_bias=True, shared=True,
                   matmul_dtype=None, drop_expert=None):
    """Mean cross-entropy of ONE sequence (``tokens``, ``targets``:
    ``[s]``) in float32.

    The switches exist to show what the comparison tells apart (the
    tests, and once on the chip): the rotary part left out of the
    scores, the scale of another head width (``scale_dim`` 128), the
    experts selected without ``b``, the shared expert left out, all the
    rows of the held expert ``drop_expert`` left out, the operands of
    every product held to a lower precision's mantissa
    (``matmul_dtype``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = config['rms_norm_eps']
    heads = config['num_attention_heads']
    nope, rope, vd = _dims(config)
    rank = config['kv_lora_rank']
    s = tokens.shape[0]

    inv_freq = float(config['rope_theta']) ** (
        -2.0 * np.arange(rope // 2, dtype=np.float64) / rope)
    angle = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    both = np.concatenate([angle, angle], -1)                    # [s, rope]
    cos = jnp.asarray(np.cos(both), jnp.float32)
    sin = jnp.asarray(np.sin(both), jnp.float32)

    def rms_norm(x, g):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * g

    lo = _held_in(matmul_dtype)

    def rotate(x, cos, sin):
        """The published rotation of ``x [..., rope]`` under
        ``rope_interleave``."""
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attend(q_nope, q_rope, k_nope, k_rope, v):
        """``[s, heads * v]`` from q ``[s, heads, .]``, k_nope and v
        ``[s, heads, .]`` and the one ``k_rope [s, rope]``, a block of
        queries at a time."""
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError('seq %d is not a multiple of %d' % (s, block))
        kpos = jnp.arange(s)

        def one_block(args):
            qn, qr, start = args
            scores = jnp.einsum('qhd,khd->hqk', lo(qn), lo(k_nope))
            if rope_scores:
                scores = scores + jnp.einsum('qhd,kd->hqk', lo(qr),
                                             lo(k_rope))
            scores = scores / math.sqrt(scale_dim or nope + rope)
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
        q = (lo(x) @ lo(w['w_q'])).reshape(s, heads, nope + rope)
        c = lo(x) @ lo(w['w_kva'])
        kv = (lo(rms_norm(c[:, :rank], w['ln_kv'])) @ lo(w['w_kvb'])
              ).reshape(s, heads, nope + vd)
        o = attend(q[..., :nope], rotate(q[..., nope:], cos[:, None],
                                         sin[:, None]),
                   kv[..., :nope], rotate(c[:, rank:], cos, sin),
                   kv[..., nope:])
        return lo(o) @ lo(w['w_o'])

    def dense_layer(h, w):
        h = h + mla(rms_norm(h, w['ln_attn']), w)
        return h + _gated(rms_norm(h, w['ln_ffn']), w['w_ffn_gate_up'],
                          w['w_ffn_down'], lo)

    def expert_layer(h, w):
        h = h + mla(rms_norm(h, w['ln_attn']), w)
        return h + reference_expert_layer(
            w, rms_norm(h, w['ln_mlp']), config, select_bias, shared,
            drop_expert, matmul_dtype)

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        h = p['embed'][tokens]
        h = jax.checkpoint(dense_layer)(h, p['dense'])
        run = jax.checkpoint(expert_layer)
        h, _ = jax.lax.scan(lambda h, w: (run(h, w), None), h, p['layers'])
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
    the mean over its sequences, one at a time; both Python floats.
    Where the engine left the program's own gradient of this batch
    (``engines/trainer_leaves.py``), the norm is RAISED by the worst
    leaf's difference (:func:`held_to_every_leaf`)."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(jax.value_and_grad(
        lambda p, tokens, targets: reference_loss(
            p, tokens, targets, config, **switches)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    tokens = jnp.asarray(batch['tokens'])
    targets = jnp.asarray(batch['targets'])
    n = tokens.shape[0]
    loss, grads = 0.0, None
    for i in range(n):
        loss_i, grads_i = one(ref_params, tokens[i], targets[i])
        loss += float(loss_i) / n
        grads = grads_i if grads is None else add(grads, grads_i)
    sq = jax.jit(lambda g: sum(jnp.sum(jnp.square(x))
                               for x in jax.tree.leaves(g)))(grads)
    norm = math.sqrt(float(sq)) / n
    from benchmark.engines import trainer_leaves
    program = trainer_leaves.PROBE.pop('gradients', None)
    if program is None:
        return loss, norm
    return loss, held_to_every_leaf(norm, to_reference_params(program),
                                    grads, n)


def leaf_limit(name):
    parts = name.split('/')
    if 'w_router' in parts:
        return ROUTER_LEAF_RTOL
    return ROUTED_LEAF_RTOL if any(p in ROUTED for p in parts) \
        else LEAF_RTOL


def held_to_every_leaf(norm, program, reference, n):
    """``norm x (1 + GRAD_NORM_RTOL x worst)``, as ``mellum2.py``'s: the
    reference's global norm, raised by the largest of the leaves'
    differences (``mellum2.leaf_differences``: a stack's leaves a layer
    at a time), each in units of its leaf's limit. ``b_select``'s
    gradient is nothing on both sides or counts as a thousand limits.
    Prints the leaves' readings as one line."""
    import json

    from benchmark import harness
    from benchmark.models.mellum2 import leaf_differences
    leaves = leaf_differences(program, reference, n)
    in_limits = {name: d / leaf_limit(name) if math.isfinite(d) else 1e3
                 for name, d in leaves.items()}
    worst = max(in_limits, key=in_limits.get)
    print(json.dumps({'gradient_leaves': leaves, 'worst': worst,
                      'worst_difference': leaves[worst],
                      'worst_in_limits': in_limits[worst],
                      'limits': {'leaf': LEAF_RTOL,
                                 'routed_leaf': ROUTED_LEAF_RTOL,
                                 'router_leaf': ROUTER_LEAF_RTOL},
                      'reference_global_grad_norm': norm}), flush=True)
    return norm * (1.0 + harness.GRAD_NORM_RTOL * in_limits[worst])
