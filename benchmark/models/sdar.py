"""Model family ``sdar``: JetLM's SDAR (``SDAR-30B-A3B-Chat``,
``config.json`` of ``JetLM/SDAR-30B-A3B-Chat``, ``model_type: sdar_moe``:
the Qwen3-MoE block, trained by block diffusion; SDAR, arXiv:2510.06303;
BD3-LM, arXiv:2503.09573), on ONE CHIP'S SHARE of a deployment that
divides each layer over several chips: ``num_experts_held`` of the
``num_experts`` experts (the first ones), attention whole, a slice of
the vocabulary.

The four things a family gives (see ``transformer.py``): :func:`build`,
the plain reference (:func:`reference_sum`,
:func:`reference_loss_and_grad_norm`), :func:`to_reference_params` and
:func:`flops_per_token`.

The step as the reference computes it, in float32 under
``jax.default_matmul_precision('highest')``, sharing no code with
``autodist_tpu.models`` (no linear layer has a bias). A sequence is
``x_0 [L]`` (``targets``), its noised copy ``x_t [L]`` (``tokens``: some
ids replaced by the mask id) and the weights ``w [L]`` (``mask``: ``1 /
t`` of the position's block where ``x_t`` holds the mask id, else 0):

* ``ids = [x_t ; x_0]``, ``2 L`` rows; ``h = E[ids]``; no position
  table; row ``i`` of either copy has position ``i``.
* Layer: ``h = h + Attn(RMSNorm(h))``, ``h = h + MoE(RMSNorm(h))``;
  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
* ``Attn``: ``q = x W_q`` in ``num_attention_heads`` heads of
  ``head_dim``, ``k = x W_k``, ``v = x W_v`` in ``num_key_value_heads``;
  PER HEAD ``q_h = RMSNorm(q_h) * g_q``, ``k_h = RMSNorm(k_h) * g_k``
  over the head's lanes (one ``g_q`` and one ``g_k`` a layer); then q
  and k rotated (half-split pairs, base ``rope_theta``, no scaling) by
  the row's position; query head ``i`` attends kv head ``i // (heads /
  kv heads)``; scores ``q k^T / sqrt(head_dim)`` under THE MASK, a
  boolean ``[2 L, 2 L]`` array built from the four rules (``blk(i) = (i
  mod L) // block_length``): a noised row sees the noised rows of its
  own block and the clean rows of earlier blocks; a clean row sees the
  clean rows of its own and earlier blocks; softmax; ``o W_o``.
* ``MoE``: ``p = softmax(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest; ``w_e = p_e / sum of those``; ``out =
  sum over the chosen e that are HELD of w_e (silu(x W_gate_e) * (x
  W_up_e)) W_down_e``, every one of the ``2 L`` rows routed.
* ``h = RMSNorm(h[:L])`` (the noised rows); ``logits = h W_head``;
  ``nll_i = logsumexp(logits_i) - logits_i[x_0,i]`` AT THE SAME POSITION;
  the batch's loss is ``sum_i w_i nll_i / sum_i w_i`` over all its
  sequences.

How it fits beside the training state (as ``mellum2.py``): one sequence
at a time, each layer computed again in the backward pass, attention in
blocks of ``QUERY_BLOCK`` queries against every key (``[32, 256, 16384]``
f32 scores are 0.5 GB), the experts one at a time, the layers scanned
over their stack, the logits ``LOSS_ROWS`` positions at a time.
"""
import math

QUERY_BLOCK = 256
LOSS_ROWS = 2048

# The limits on one leaf of the gradient, |program - reference| /
# |reference| in L2, a layer at a time (held_to_every_leaf), each between
# its two readings on the chip at the committed draw (my chip runs, PR
# 45, second round; PERF.md section 6). The loss is taken on the mask
# id's rows alone, and what such a row holds is what attention WROTE
# into it (its own embedding is drawn small, the configuration's file
# says why) through scores drawn sharper than a unit norm's, so bf16's
# rounding of q, k and the weights reaches every leaf and the readings
# are several times the other sparse cells'. A leaf not behind a router:
# the sound reference reads 5.8-7.0% at worst on eight seeds (g_q, g_k or
# ln_attn); with every product's operands held to float8_e4m3's mantissa
# 63.7-68.5%; with a noised block that sees its OWN clean block
# 21.5-26.2%.
LEAF_RTOL = 0.12
# ... a leaf whose gradient comes through the routed experts (ln_mlp,
# w_gate_up, w_down): sound 15.5-17.2%, float8 110.5-111.0, own clean
# block 34.6-40.2 ...
ROUTED_LEAF_RTOL = 0.26
# ... and the router's own, which turns on which eight experts a row near
# a tie gets: sound 16.5-19.0%, float8 115.9-121.6, own clean block
# 36.5-43.2. (Each fault fails all three here, through the harness too:
# correct false at 5.3 and 2.2 times a limit; it need fail but one, and
# the first limit tells them with the most room, so these two sit nearer
# their upper readings: the driver draws new seeds for every check, and
# fresh seeds read higher.)
ROUTER_LEAF_RTOL = 0.28
ROUTED = ('ln_mlp', 'w_gate_up', 'w_down')


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if config['hidden_act'] != 'silu' or config['tie_word_embeddings'] \
            or config['attention_bias'] or not config['norm_topk_prob']:
        raise ValueError('family sdar: silu, an untied head, no attention '
                         'bias and norm_topk_prob only')
    if config['decoder_sparse_step'] != 1 or config['mlp_only_layers'] \
            or config['rope_scaling'] is not None \
            or config['use_sliding_window']:
        raise ValueError('family sdar: every layer sparse, no rotary '
                         'scaling, no window')
    if config['mask_token_id'] != config['vocab_size'] - 1:
        raise ValueError('family sdar: the mask id is the last row of the '
                         'vocabulary slice')
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=config['hidden_size'],
        n_layers=config['num_hidden_layers'],
        n_heads=config['num_attention_heads'],
        n_kv_heads=config['num_key_value_heads'],
        head_dim=config['head_dim'],
        max_len=config['max_position_embeddings'], causal=True,
        tied_embeddings=False, dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'],
        positions='rotary', rope_theta=float(config['rope_theta']),
        mlp_dim=config['moe_intermediate_size'], gated_mlp=True,
        gelu='silu', norm='rms', norm_eps=config['rms_norm_eps'],
        moe_experts=config['num_experts'],
        moe_top_k=config['num_experts_per_tok'],
        moe_held=config['num_experts_held'],
        moe_aux_coef=config['moe_aux_coef'],
        embed_init_scale=config['embed_init_scale'],
        qk_norm=True, block_length=config['block_length'])

    class Drawn(TransformerLM):
        """The program's model with its random weights drawn as the
        configuration's file says (``assumed``: the draw, and why): the
        mask id's embedding row and the per-head norms' weights at
        values of their own, the q and k columns of the projection, the
        output projection and the router at a multiple of the program's
        N(0, 1 / fan-in). A draw, applied once to what ``init``
        returns; the step is ``TransformerLM``'s."""

        def init(self, rng):
            params = super().init(rng)
            table = params['embed']['table']
            params['embed']['table'] = table.at[
                config['mask_token_id']].multiply(
                    config['mask_row_init_scale']
                    / config['embed_init_scale'])
            attn = params['blocks']['attn']
            for name in ('q_norm', 'k_norm'):
                attn[name]['scale'] = attn[name]['scale'] \
                    * config['qk_norm_init_scale']
            qk = (config['num_attention_heads']
                  + config['num_key_value_heads']) * config['head_dim']
            kernel = attn['qkv']['kernel']          # [layers, d, q | k | v]
            attn['qkv']['kernel'] = kernel.at[..., :qk].multiply(
                config['qk_proj_init_factor'])
            attn['out']['kernel'] = attn['out']['kernel'] \
                * config['out_proj_init_factor']
            router = params['blocks']['mlp']['router']
            router['kernel'] = router['kernel'] * config['router_init_factor']
            return params

    return Drawn(cfg)


def flops_per_token(config, seq):
    """Model FLOPs one TRAINED token (a position of ``x_0``; the stack
    runs two rows for it) requires on this chip: 3 x forward, where
    forward = 2 rows x 2 x (a layer's attention matrices, the router and
    the held experts at the pairs a row is EXPECTED to have among them)
    a layer + QK^T and PV over the live pairs of the mask, ``4 x (seq +
    block_length) x heads x head_dim`` a layer + the head's matmul over
    the vocabulary slice, once: the noised rows alone are scored."""
    from benchmark.bd_kinds import live_pairs
    d, hd = config['hidden_size'], config['head_dim']
    q_width = config['num_attention_heads'] * hd
    kv_width = config['num_key_value_heads'] * hd
    attention = 2 * d * q_width + 2 * d * kv_width
    pairs = config['num_experts_per_tok'] * config['num_experts_held'] \
        / config['num_experts']
    experts = pairs * 3 * d * config['moe_intermediate_size']
    per_row = attention + d * config['num_experts'] + experts
    keys = live_pairs(seq, config['block_length']) / seq
    layers = config['num_hidden_layers']
    fwd = layers * (2 * 2 * per_row + 4 * keys * q_width) \
        + 2 * d * config['vocab_size']
    return 3 * fwd


def to_reference_params(params):
    """The program's tree under the reference's names; nothing is copied
    (the layers are one stack, which the reference scans)."""
    b = params['blocks']
    return {'embed': params['embed']['table'],
            'layers': {'ln_attn': b['ln1']['scale'],
                       'w_qkv': b['attn']['qkv']['kernel'],
                       'g_q': b['attn']['q_norm']['scale'],
                       'g_k': b['attn']['k_norm']['scale'],
                       'w_o': b['attn']['out']['kernel'],
                       'ln_mlp': b['ln2']['scale'],
                       'w_router': b['mlp']['router']['kernel'],
                       'w_gate_up': b['mlp']['up'],   # [n, held, d, 2, f]
                       'w_down': b['mlp']['down']},
            'ln_final': params['ln_f']['scale'],
            'head': params['lm_head']['kernel']}


def attention_mask(seq, block, kind='block_diffusion'):
    """The boolean ``[2 seq, 2 seq]`` mask over the rows ``[x_t ; x_0]``,
    straight from the four rules. The other ``kind``s are the wrong
    masks the tests hold the comparison against: ``'causal'`` (row r
    sees rows up to r), ``'own_clean'`` (a noised block also sees its
    OWN block's clean copy)."""
    import jax.numpy as jnp
    row = jnp.arange(2 * seq)
    if kind == 'causal':
        return row[:, None] >= row[None, :]
    noised, blk = row < seq, (row % seq) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    earlier = k_blk <= q_blk if kind == 'own_clean' else k_blk < q_blk
    return (q_noised & k_noised & (k_blk == q_blk)) \
        | (q_noised & ~k_noised & earlier) \
        | (~q_noised & ~k_noised & (k_blk <= q_blk))


def reference_sum(ref_params, tokens, targets, weights, config,
                  mask_kind='block_diffusion', qk_norm='before',
                  positions='repeat', shift=0, weighted=True,
                  matmul_dtype=None):
    """``(sum_i w_i nll_i, sum_i w_i)`` of ONE sequence (``tokens`` =
    x_t, ``targets`` = x_0, ``weights`` = w: ``[L]``) in float32.

    The switches exist to show what the comparison tells apart (the
    tests, and once on the chip): another mask (:func:`attention_mask`),
    ``qk_norm`` ``None`` (no per-head norm) or ``'after'`` (the norm
    after the rotation), ``positions='index'`` (rows at ``0 .. 2 L -
    1``), ``shift=1`` (row ``i`` scored against ``x_0[i + 1]``),
    ``weighted=False`` (every masked position at weight one), and the
    operands of every product held to ``matmul_dtype``'s mantissa (the
    value rounded; the products and the gradient in f32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = config['rms_norm_eps']
    heads, kv_heads = (config['num_attention_heads'],
                       config['num_key_value_heads'])
    hd, group = config['head_dim'], heads // kv_heads
    held, top_k = config['num_experts_held'], config['num_experts_per_tok']
    seq = tokens.shape[0]
    rows = 2 * seq

    def held_in(dtype):
        """Rounds a product's operand to ``dtype``'s mantissa
        (``reduce_precision``: a cast there and back is dropped as excess
        precision on the TPU, ``mellum2.py``); the gradient passes
        through unrounded."""
        if dtype is None:
            return lambda t: t
        mantissa = jnp.finfo(dtype).nmant
        return lambda t: t + jax.lax.stop_gradient(
            jax.lax.reduce_precision(t, 8, mantissa) - t)
    lo = held_in(matmul_dtype)

    def rms_norm(x, g):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * g

    pos = np.arange(rows, dtype=np.float64)
    if positions == 'repeat':
        pos = pos % seq
    inv_freq = float(config['rope_theta']) ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    angle = pos[:, None] * inv_freq[None]
    both = np.concatenate([angle, angle], -1)[:, None]        # [rows, 1, hd]
    cos = jnp.asarray(np.cos(both), jnp.float32)
    sin = jnp.asarray(np.sin(both), jnp.float32)
    mask = attention_mask(seq, config['block_length'], mask_kind)

    def rotate(x):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attend(q, k, v):
        """softmax(q k^T / sqrt(hd) under the mask) v for q ``[rows,
        heads, hd]`` and k, v ``[rows, kv_heads, hd]``, a block of
        queries at a time."""
        block = min(QUERY_BLOCK, rows)
        if rows % block:
            raise ValueError('%d rows are no multiple of %d' % (rows, block))

        def one_block(args):
            qb, keep = args               # [block, heads, hd], [block, rows]
            qb = qb.reshape(block, kv_heads, group, hd)
            scores = jnp.einsum('qngd,knd->ngqk', lo(qb), lo(k)) \
                / math.sqrt(hd)
            scores = jnp.where(keep[None, None], scores, -jnp.inf)
            out = jnp.einsum('ngqk,knd->qngd',
                             lo(jax.nn.softmax(scores, axis=-1)), lo(v))
            return out.reshape(block, heads, hd)

        out = jax.lax.map(jax.checkpoint(one_block),
                          (q.reshape(rows // block, block, heads, hd),
                           mask.reshape(rows // block, block, rows)))
        return out.reshape(rows, heads * hd)

    def moe(x, w):
        probs = jax.nn.softmax(lo(x) @ lo(w['w_router']), axis=-1)
        vals, idx = jax.lax.top_k(probs, top_k)
        weights_ = vals / jnp.sum(vals, -1, keepdims=True)

        def one_expert(e, gate_up, down):          # [d, 2, f], [f, d]
            w_e = jnp.sum(jnp.where(idx == e, weights_, 0.0), axis=-1)
            xe, gate_up, down = lo(x), lo(gate_up), lo(down)
            h = jax.nn.silu(xe @ gate_up[:, 0]) * (xe @ gate_up[:, 1])
            return w_e[:, None] * (lo(h) @ down)

        out, _ = jax.lax.scan(
            lambda out, args: (out + jax.checkpoint(one_expert)(*args), None),
            jnp.zeros_like(x),
            (jnp.arange(held), w['w_gate_up'], w['w_down']))
        return out

    def layer(h, w):
        a = rms_norm(h, w['ln_attn'])
        q, k, v = jnp.split(lo(a) @ lo(w['w_qkv']),
                            [heads * hd, (heads + kv_heads) * hd], axis=-1)
        q, k = q.reshape(rows, heads, hd), k.reshape(rows, kv_heads, hd)
        if qk_norm == 'before':
            q, k = rms_norm(q, w['g_q']), rms_norm(k, w['g_k'])
        q, k = rotate(q), rotate(k)
        if qk_norm == 'after':
            q, k = rms_norm(q, w['g_q']), rms_norm(k, w['g_k'])
        o = attend(q, k, v.reshape(rows, kv_heads, hd))
        h = h + lo(o) @ lo(w['w_o'])
        return h + moe(rms_norm(h, w['ln_mlp']), w)

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        h = p['embed'][jnp.concatenate([tokens, targets])]
        run = jax.checkpoint(layer)
        h, _ = jax.lax.scan(lambda h, w: (run(h, w), None), h, p['layers'])
        h = rms_norm(h[:seq], p['ln_final'])
        gold = jnp.roll(targets, -shift) if shift else targets
        w = weights if weighted else (weights > 0).astype(jnp.float32)

        def nll(args):
            part, ids = args
            logits = lo(part) @ lo(p['head'])
            return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, ids[:, None], axis=-1)[:, 0]
        block = min(LOSS_ROWS, seq)
        if seq % block:
            raise ValueError('seq %d is not a multiple of %d' % (seq, block))
        nlls = jax.lax.map(jax.checkpoint(nll),
                           (h.reshape(seq // block, block, -1),
                            gold.reshape(seq // block, block))).reshape(seq)
        return jnp.sum(w * nlls), jnp.sum(w)


def reference_loss_and_grad_norm(config, ref_params, batch, **switches):
    """(loss, norm of the gradient) of the plain reference on ``batch``,
    one sequence at a time: the sequences' weighted sums and their
    gradients added up and divided by the batch's sum of weights; both
    Python floats.

    The norm is the gradient's global L2 norm; where the engine left the
    program's own gradient of this batch (``engines/trainer_leaves.py``),
    it is RAISED by the worst leaf's difference, see
    :func:`held_to_every_leaf`."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(jax.value_and_grad(
        lambda p, tokens, targets, weights: reference_sum(
            p, tokens, targets, weights, config, **switches),
        has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    tokens, targets, weights = (jnp.asarray(batch[k]) for k in (
        'tokens', 'targets', 'mask'))
    total, divisor, grads = 0.0, 0.0, None
    for i in range(tokens.shape[0]):
        (sum_i, w_i), grads_i = one(ref_params, tokens[i], targets[i],
                                    weights[i])
        total += float(sum_i)
        divisor += float(w_i)
        grads = grads_i if grads is None else add(grads, grads_i)
    sq = jax.jit(lambda g: sum(jnp.sum(jnp.square(x))
                               for x in jax.tree.leaves(g)))(grads)
    loss, norm = total / divisor, math.sqrt(float(sq)) / divisor
    from benchmark.engines import trainer_leaves
    # taken, not read: a probe's gradient is compared once, with the
    # reference of the same batch
    program = trainer_leaves.PROBE.pop('gradients', None)
    if program is None:
        return loss, norm
    return loss, held_to_every_leaf(norm, to_reference_params(program),
                                    grads, divisor)


def leaf_limit(name):
    parts = name.split('/')
    if 'w_router' in parts:
        return ROUTER_LEAF_RTOL
    return ROUTED_LEAF_RTOL if any(p in ROUTED for p in parts) \
        else LEAF_RTOL


def held_to_every_leaf(norm, program, reference, n):
    """``norm x (1 + GRAD_NORM_RTOL x worst)``, as ``mellum2.py``'s: the
    reference's global norm, raised by the largest of the leaves'
    differences (``mellum2.leaf_differences``: the stack's leaves a
    layer at a time; ``reference`` is the sum that ``n``, the batch's
    sum of weights, divides), each in units of its leaf's limit. Prints
    the leaves' readings as one line."""
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
