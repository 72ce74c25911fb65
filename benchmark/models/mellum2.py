"""Model family ``mellum2``: JetBrains' Mellum2 (``Mellum2-12B-A2.5B``,
``config.json`` of ``JetBrains/Mellum2-12B-A2.5B-Instruct``), on ONE
CHIP'S SHARE of a deployment that divides each layer over several chips:
``num_experts_held`` of the ``num_experts`` experts (the first ones),
attention whole, a slice of the vocabulary.

The four things a family gives (see ``transformer.py``): :func:`build`,
the plain reference (:func:`reference_loss`,
:func:`reference_loss_and_grad_norm`), :func:`to_reference_params` and
:func:`flops_per_token`.

The architecture as the reference computes it, in float32 under
``jax.default_matmul_precision('highest')``, sharing no code with
``autodist_tpu.models`` (``x [s, hidden]``; no linear layer has a bias):

* ``h = E[tokens]``; no position table.
* Layer ``i``: ``h = h + Attn_kind(RMSNorm(h))``, ``h = h +
  MoE(RMSNorm(h))``; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
* ``Attn``: ``q = x W_q`` in ``num_attention_heads`` heads of
  ``head_dim``, ``k = x W_k``, ``v = x W_v`` in ``num_key_value_heads``;
  q and k rotated by the ``(cos, sin)`` of the layer's kind (half-split
  pairs); query head ``i`` attends kv head ``i // (heads / kv heads)``;
  scores ``q k^T / sqrt(head_dim)``; a ``sliding_attention`` layer keeps
  keys ``j`` with ``0 <= i - j < sliding_window``, a ``full_attention``
  layer ``j <= i``; softmax; ``o W_o``.
* Rotary tables. ``rope_type: default``: ``inv_freq_i = theta^(-2i /
  head_dim)``. ``rope_type: yarn``, as the public ``transformers``
  computes it: ``extra = inv_freq``, ``inter = inv_freq / factor``;
  ``dim(r) = head_dim ln(original / (2 pi r)) / (2 ln theta)``; ``low =
  max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)),
  head_dim - 1)``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq'_i = inter_i ramp_i + extra_i (1 - ramp_i)``; ``cos`` and
  ``sin`` times ``attention_factor``; at every length.
* ``MoE``: ``p = softmax(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest; ``w_e = p_e / sum of those``
  (``norm_topk_prob``); ``out = sum over the chosen e that are HELD (e <
  num_experts_held) of w_e (silu(x W_gate_e) * (x W_up_e)) W_down_e``.
  What the other experts would add is left out, here as in the program,
  and that partial sum goes on to the next layer.
* ``h = RMSNorm(h)``; ``logits = h W_head`` (untied, over the slice);
  mean next-token cross-entropy.

Every held expert is computed for every position and weighted by ``w_e``
(zero where the expert was not chosen): the straightforward form, no
ordering, no groups. So that two sequences of 8192 fit beside the
training state, the reference takes one sequence at a time, computes
each layer again in the backward pass, attends in blocks of queries
against every key (``[32, 512, 8192]`` f32 scores are 0.5 GB), walks
the experts one at a time, scans the consecutive layers of a kind over
their stack (so that a layer's gradient lands in its row of the stack
and no stack-sized temporary is made for it) and takes the logits
``LOSS_ROWS`` positions at a time: with 7.1 GB of weights and AdamW
slots and two gradient trees of 2.4 GB each beside it, the reference
has about 4 GB of the chip for its own temporaries.
"""
import functools
import math

QUERY_BLOCK = 512
LOSS_ROWS = 2048     # rows of logits at a time: [2048, vocabulary] f32

# The limits on one leaf of the gradient, |program - reference| /
# |reference| in L2, a layer at a time (held_to_every_leaf), each between
# its two readings on the chip at the committed draw (my chip runs, PR
# 33; PERF.md §6). A leaf not behind a router: the sound reference reads
# 1.51-1.53% at worst on eight seeds (the full layer's ln_attn / w_qkv;
# embed 0.62%, head 0.53%); with every product's operands held to
# float8_e4m3's mantissa, the nearest precision below the program's
# bfloat16, 8.8-12.1% on every attention leaf; without the window the
# window layers' w_qkv 80%.
LEAF_RTOL = 0.04
# ... and a leaf whose gradient comes through the routed experts: the
# program routes on bf16 activations, so near a tie its top-k differs
# from the reference's for a few tokens in a hundred: 6.3-7.0% at worst
# on eight seeds (w_router); with one held expert's rows left out
# 23.6-27.3%, with every product's operands at float8's mantissa
# 15.7-19.5%. (The experts' operands ALONE at that mantissa read 9.4%:
# not told apart.)
ROUTED_LEAF_RTOL = 0.15
ROUTED = ('ln_mlp', 'w_router', 'w_gate_up', 'w_down')


def _kinds(config):
    """``'window'`` or ``'global'`` for each layer that is run."""
    names = {'sliding_attention': 'window', 'full_attention': 'global'}
    return [names[t] for t in
            config['layer_types'][:config['num_hidden_layers']]]


def _period(config):
    """``(layers a period, the full layer's place in it)``."""
    kinds = _kinds(config)
    at = kinds.index('global')
    every = at + 1
    if any((k == 'global') != (i % every == at) for i, k in enumerate(kinds)):
        raise ValueError('family mellum2: layer_types is no period of '
                         'window layers closed by a full one: %s' % kinds)
    return every, at


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if config['hidden_act'] != 'silu' or config['tie_word_embeddings'] \
            or config['attention_bias'] or not config['norm_topk_prob']:
        raise ValueError('family mellum2: silu, an untied head, no '
                         'attention bias and norm_topk_prob only')
    if set(config['mlp_layer_types']) != {'sparse'}:
        raise ValueError('family mellum2: every MLP is sparse')
    rope = config['rope_parameters']
    window, full = rope['sliding_attention'], rope['full_attention']
    if window['rope_type'] != 'default' or full['rope_type'] != 'yarn':
        raise ValueError('family mellum2: default rotary positions in the '
                         'window layers and yarn in the full ones')
    every, at = _period(config)
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
        positions='rotary', rope_theta=float(full['rope_theta']),
        window_rope_theta=float(window['rope_theta']),
        rope_yarn={k: full[k] for k in (
            'factor', 'original_max_position_embeddings', 'beta_fast',
            'beta_slow', 'attention_factor')},
        window=config['sliding_window'] - 1, global_every=every,
        global_at=at, mlp_dim=config['moe_intermediate_size'],
        gated_mlp=True, gelu='silu', norm='rms',
        norm_eps=config['rms_norm_eps'],
        moe_experts=config['num_experts'],
        moe_top_k=config['num_experts_per_tok'],
        moe_held=config['num_experts_held'],
        moe_aux_coef=config['moe_aux_coef'],
        embed_init_scale=config['embed_init_scale'])
    return TransformerLM(cfg)


def flops_per_token(config, seq):
    """Model FLOPs one training token requires on this chip: 3 x forward,
    where forward = 2 x (a layer's attention matrices, the router, and
    the held experts at the pairs a token is EXPECTED to have among them:
    ``num_experts_per_tok x num_experts_held / num_experts`` experts of
    three ``hidden x moe_intermediate`` matrices) + the head's matmul
    over the vocabulary slice + QK^T and PV: ``4 x keys x heads x
    head_dim`` with ``keys`` what a query sees, ``sliding_window`` (never
    more than ``seq``) in a window layer and half of ``seq`` under the
    causal mask of a full one."""
    d, hd = config['hidden_size'], config['head_dim']
    q_width = config['num_attention_heads'] * hd
    kv_width = config['num_key_value_heads'] * hd
    attention = 2 * d * q_width + 2 * d * kv_width
    pairs = config['num_experts_per_tok'] * config['num_experts_held'] \
        / config['num_experts']
    experts = pairs * 3 * d * config['moe_intermediate_size']
    per_layer = attention + d * config['num_experts'] + experts
    kinds = _kinds(config)
    keys = sum(min(seq, config['sliding_window']) if k == 'window'
               else seq / 2 for k in kinds)
    fwd = 2 * len(kinds) * per_layer + 2 * d * config['vocab_size'] \
        + 4 * keys * q_width
    return 3 * fwd


def to_reference_params(params):
    """The program's tree under the reference's names. The program
    stacks the layers of a kind (``blocks['window']``,
    ``blocks['global']``, each in depth order); the reference takes a
    layer out of its kind's stack where it runs it, so nothing is copied
    here (the training state fills most of the chip)."""
    def stack(b):
        up = b['mlp']['up']                     # [n, held, d, 2, f]
        return {'ln_attn': b['ln1']['scale'],
                'w_qkv': b['attn']['qkv']['kernel'],
                'w_o': b['attn']['out']['kernel'],
                'ln_mlp': b['ln2']['scale'],
                'w_router': b['mlp']['router']['kernel'],
                'w_gate_up': up,
                'w_down': b['mlp']['down']}
    return {'embed': params['embed']['table'],
            'layers': {kind: stack(b)
                       for kind, b in params['blocks'].items()},
            'ln_final': params['ln_f']['scale'],
            'head': params['lm_head']['kernel']}


def rotary_inv_freq(rope, head_dim):
    """``(inv_freq [head_dim / 2], factor on cos and sin)`` of one entry
    of ``rope_parameters``, in float64 numpy."""
    import numpy as np
    theta = float(rope['rope_theta'])
    i = np.arange(head_dim // 2, dtype=np.float64)
    inv_freq = theta ** (-2.0 * i / head_dim)
    if rope['rope_type'] == 'default':
        return inv_freq, 1.0
    original = rope['original_max_position_embeddings']

    def dim(r):
        return head_dim * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim(rope['beta_fast'])), 0)
    high = min(math.ceil(dim(rope['beta_slow'])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (inv_freq / rope['factor'] * ramp + inv_freq * (1.0 - ramp),
            float(rope['attention_factor']))


def reference_loss(ref_params, tokens, targets, config, window=True,
                   yarn=True, matmul_dtype=None, experts_dtype=None,
                   drop_expert_rows=0, drop_expert=None):
    """Mean cross-entropy of ONE sequence (``tokens``, ``targets``:
    ``[s]``) in float32.

    The switches exist to show what the comparison tells apart (the
    tests, and once on the chip): a reference without the window, with
    the window layers' frequencies in the full layers, with the
    operands of every product (``matmul_dtype``) or of the experts'
    products (``experts_dtype``) held to a lower precision's mantissa
    (the value rounded; the products and the gradient in f32 as ever),
    with the first ``drop_expert_rows`` positions left out of every
    expert, or with all the rows of the held expert ``drop_expert``
    left out. At the tiny size of the tests each falls outside the
    limits; on the chip all but the experts' precision alone and one
    dropped position do (the limits' comments above)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = config['rms_norm_eps']
    heads, kv_heads = (config['num_attention_heads'],
                       config['num_key_value_heads'])
    hd, group = config['head_dim'], heads // kv_heads
    held, top_k = config['num_experts_held'], config['num_experts_per_tok']
    kinds = _kinds(config)
    s = tokens.shape[0]

    def tables(kind):
        rope = config['rope_parameters'][
            'full_attention' if kind == 'global' and yarn
            else 'sliding_attention']
        inv_freq, factor = rotary_inv_freq(rope, hd)
        angle = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
        both = np.concatenate([angle, angle], -1)[:, None]     # [s, 1, hd]
        return (jnp.asarray(np.cos(both) * factor, jnp.float32),
                jnp.asarray(np.sin(both) * factor, jnp.float32))

    def rms_norm(x, g):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * g

    def held_in(dtype):
        """Rounds a product's operand to ``dtype``'s mantissa bits
        (identity for None; the exponent keeps float32's range, so
        nothing overflows or is flushed), by ``reduce_precision``: on
        the TPU a cast there and back is dropped as excess precision
        where the compiler pleases (the experts' read as the sound
        reference to the last digit, my chip run, PR 33). The gradient
        passes through unrounded."""
        if dtype is None:
            return lambda t: t
        mantissa = jnp.finfo(dtype).nmant
        return lambda t: t + jax.lax.stop_gradient(
            jax.lax.reduce_precision(t, 8, mantissa) - t)
    lo = held_in(matmul_dtype)
    rounded = held_in(experts_dtype or matmul_dtype)

    def rotate(x, cos, sin):
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attend(q, k, v, kind):
        """softmax(q k^T / sqrt(hd)) v for q ``[s, heads, hd]`` and k, v
        ``[s, kv_heads, hd]``, a block of queries at a time."""
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError('seq %d is not a multiple of %d' % (s, block))
        kpos = jnp.arange(s)

        def one_block(args):
            qb, start = args                       # [block, heads, hd]
            qb = qb.reshape(block, kv_heads, group, hd)
            scores = jnp.einsum('qngd,knd->ngqk', lo(qb), lo(k)) \
                / math.sqrt(hd)
            back = (start + jnp.arange(block))[:, None] - kpos[None, :]
            keep = back >= 0
            if kind == 'window' and window:
                keep = jnp.logical_and(keep, back < config['sliding_window'])
            scores = jnp.where(keep[None, None], scores, -jnp.inf)
            out = jnp.einsum('ngqk,knd->qngd',
                             lo(jax.nn.softmax(scores, axis=-1)), lo(v))
            return out.reshape(block, heads, hd)

        out = jax.lax.map(jax.checkpoint(one_block),
                          (q.reshape(s // block, block, heads, hd),
                           jnp.arange(0, s, block)))
        return out.reshape(s, heads * hd)

    def moe(x, w):
        probs = jax.nn.softmax(lo(x) @ lo(w['w_router']), axis=-1)
        vals, idx = jax.lax.top_k(probs, top_k)
        weights = vals / jnp.sum(vals, -1, keepdims=True)
        live = (jnp.arange(s) >= drop_expert_rows)[:, None]

        def one_expert(e, gate_up, down):          # [d, 2, f], [f, d]
            w_e = jnp.sum(jnp.where(jnp.logical_and(idx == e, live),
                                    weights, 0.0), axis=-1)
            if drop_expert is not None:
                w_e = jnp.where(e == drop_expert, 0.0, w_e)
            xe = rounded(x)
            gate_up, down = rounded(gate_up), rounded(down)
            h = jax.nn.silu(xe @ gate_up[:, 0]) * (xe @ gate_up[:, 1])
            return w_e[:, None] * (rounded(h) @ down)

        # (the running sum is no input of the checkpointed part, so the
        # scan keeps nothing a position long for each expert)
        out, _ = jax.lax.scan(
            lambda out, args: (out + jax.checkpoint(one_expert)(*args), None),
            jnp.zeros_like(x),
            (jnp.arange(held), w['w_gate_up'], w['w_down']))
        return out

    def layer(h, w, kind, cos, sin):
        a = rms_norm(h, w['ln_attn'])
        q, k, v = jnp.split(lo(a) @ lo(w['w_qkv']),
                            [heads * hd, (heads + kv_heads) * hd], axis=-1)
        q = rotate(q.reshape(s, heads, hd), cos, sin)
        k = rotate(k.reshape(s, kv_heads, hd), cos, sin)
        o = attend(q, k, v.reshape(s, kv_heads, hd), kind)
        h = h + lo(o) @ lo(w['w_o'])
        return h + moe(rms_norm(h, w['ln_mlp']), w)

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        h = p['embed'][tokens]
        # the consecutive layers of a kind, scanned over their rows of
        # the kind's stack
        seen, at = dict.fromkeys(set(kinds), 0), 0
        while at < len(kinds):
            kind, n = kinds[at], 1
            while at + n < len(kinds) and kinds[at + n] == kind:
                n += 1
            stack = p['layers'][kind]
            first, rows = seen[kind], len(stack['ln_attn'])
            if (first, n) != (0, rows):
                stack = jax.tree.map(lambda a: a[first:first + n], stack)
            cos, sin = tables(kind)
            run = jax.checkpoint(functools.partial(layer, kind=kind, cos=cos,
                                                   sin=sin))
            h, _ = jax.lax.scan(lambda h, w: (run(h, w), None), h, stack)
            seen[kind] += n
            at += n
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
    the mean over its sequences, one at a time (they are of one length,
    so the mean of their means is the batch's mean); both Python floats.

    The norm is the gradient's global L2 norm; where the engine left the
    program's own gradient of this batch (``engines/trainer_leaves.py``),
    it is RAISED by the worst leaf's difference, see
    :func:`held_to_every_leaf`."""
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
    # taken, not read: a probe's gradient is compared once, with the
    # reference of the same batch
    program = trainer_leaves.PROBE.pop('gradients', None)
    if program is None:
        return loss, norm
    return loss, held_to_every_leaf(norm, to_reference_params(program),
                                    grads, n)


def leaf_differences(program, reference, n=1):
    """``{leaf: |program - reference / n| / |reference / n|}`` (L2) for
    two gradient trees under the reference's names, a layer stack's
    leaves a layer at a time (``layers/window/w_o/2``). A leaf whose
    reference gradient is nothing reads 0 where the program's is
    nothing too, else infinity."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=2)
    def sums(a, b, rows):
        axes = tuple(range(1 if rows else 0, b.ndim))
        b = b / n
        return (jnp.sum(jnp.square(a - b), axes), jnp.sum(jnp.square(b), axes))

    def ratio(d, r):
        d, r = math.sqrt(float(d)), math.sqrt(float(r))
        return d / r if r else (0.0 if not d else math.inf)

    flat_p = jax.tree_util.tree_flatten_with_path(program)[0]
    flat_r = jax.tree_util.tree_flatten_with_path(reference)[0]
    out = {}
    for (path, a), (path_r, b) in zip(flat_p, flat_r):
        if path != path_r or a.shape != b.shape:
            raise ValueError('gradient trees differ at %s' % (path,))
        name = '/'.join(str(k.key) for k in path)
        rows = name.startswith('layers/')
        d, r = sums(jnp.asarray(a), b, rows)
        if rows:
            for i in range(b.shape[0]):
                out['%s/%d' % (name, i)] = ratio(d[i], r[i])
        else:
            out[name] = ratio(d, r)
    return out


def leaf_limit(name):
    return ROUTED_LEAF_RTOL if any(
        part in ROUTED for part in name.split('/')) else LEAF_RTOL


def held_to_every_leaf(norm, program, reference, n):
    """``norm x (1 + GRAD_NORM_RTOL x worst)``: the reference's global
    norm, raised by the largest of :func:`leaf_differences`, each in
    units of its leaf's limit (:func:`leaf_limit`), so that
    ``harness.py``'s comparison of the two sides' norms (within
    ``GRAD_NORM_RTOL``) passes only while the program's global norm
    agrees AND every leaf of its gradient lies within its limit of the
    reference's. A difference that is no number counts as a thousand
    limits (``harness.close`` passes an infinite reference). Prints the
    leaves' readings as one line."""
    import json

    from benchmark import harness
    leaves = leaf_differences(program, reference, n)
    in_limits = {name: d / leaf_limit(name) if math.isfinite(d) else 1e3
                 for name, d in leaves.items()}
    worst = max(in_limits, key=in_limits.get)
    print(json.dumps({'gradient_leaves': leaves, 'worst': worst,
                      'worst_difference': leaves[worst],
                      'worst_in_limits': in_limits[worst],
                      'limits': {'leaf': LEAF_RTOL,
                                 'routed_leaf': ROUTED_LEAF_RTOL},
                      'reference_global_grad_norm': norm}), flush=True)
    return norm * (1.0 + harness.GRAD_NORM_RTOL * in_limits[worst])
