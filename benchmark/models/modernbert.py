"""Model family ``modernbert``: ModernBERT (Warner et al. 2024,
arXiv:2412.13663; ``answerdotai/ModernBERT-large`` ``config.json``).

The four things a family gives (see ``transformer.py``): :func:`build`
makes the program's model from a configuration file through the
program's public constructors; :func:`reference_loss` is the plain
reference; :func:`to_reference_params` the name map; and
:func:`flops_per_token` the analytic model FLOPs.

The architecture, as published and as the reference computes it in
float32 under ``jax.default_matmul_precision('highest')``, sharing no
code with ``autodist_tpu.models``. ``LN`` is LayerNorm with a scale and
no bias; no linear layer has a bias but the decoder:

* ``h = LN_emb(E[tokens])``; no position table.
* Layer ``i``: ``a = h`` for ``i == 0`` else ``LN_attn_i(h)``;
  ``q, k, v = split(a @ Wqkv_i)`` in heads; rotary positions on q and k
  over the whole head dim, rotate-half convention, base
  ``global_rope_theta`` where ``i % global_attn_every_n_layers == 0``
  and ``local_rope_theta`` otherwise; scores ``q k^T / sqrt(head)``, not
  causal; in the other ("local") layers a pair (i, j) is masked unless
  ``|i - j| <= local_attention / 2``; softmax; ``h += (P v) @ Wo_i``.
  Then ``input, gate = split(LN_mlp_i(h) @ Wi_i)`` (input first) and
  ``h += (gelu(input) * gate) @ Wo2_i`` with the exact (erf) GELU.
* ``h = LN_final(h)``; ``p = LN_head(gelu(h @ W_dense))``;
  ``logits = p @ E^T + b_dec``; mean cross-entropy.

Departures from the published model, each also under ``assumed`` in the
configuration file: the loss is scored at every position (the recipe
scores the masked 30% only), sequences are full-length (the recipe
unpads and packs documents), no dropout, AdamW where the recipe has
StableAdamW.

So that two sequences of 8192 fit beside the training state, the
reference takes one sequence at a time, computes each layer again in
the backward pass (``jax.checkpoint``), and attends in blocks of
queries against the whole key range, each block computed again too: a
``[16, 1024, 8192]`` f32 score block is 0.5 GB. The band is an explicit
boolean mask on those scores. The arithmetic is that of the equations.
"""
import math

QUERY_BLOCK = 1024


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if config['hidden_activation'] != 'gelu' or not config['tied_embeddings']:
        raise ValueError('family modernbert: hidden_activation gelu and '
                         'tied embeddings only')
    if config['attention_bias'] or config['mlp_bias'] or config['norm_bias']:
        raise ValueError('family modernbert: the reference has no bias '
                         'but the decoder\'s')
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=config['hidden_size'],
        n_layers=config['num_hidden_layers'],
        n_heads=config['num_attention_heads'],
        max_len=config['max_position_embeddings'], causal=False,
        tied_embeddings=True, dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'],
        positions='rotary', rope_theta=config['global_rope_theta'],
        window_rope_theta=config['local_rope_theta'],
        window=config['local_attention'] // 2,
        global_every=config['global_attn_every_n_layers'],
        mlp_dim=config['intermediate_size'], gated_mlp=True, gelu='erf',
        norm_eps=config['norm_eps'], norm_bias=False, mlp_bias=False,
        embed_norm=True, head_transform=True,
        decoder_bias=config['decoder_bias'])
    return TransformerLM(cfg)


def layer_kinds(config):
    """True for each layer that attends globally."""
    every = config['global_attn_every_n_layers']
    return [i % every == 0 for i in range(config['num_hidden_layers'])]


def flops_per_token(config, seq):
    """Model FLOPs one training token requires: 3 x forward, where
    forward = 2 x (the layers' matrices: 4 d^2 of attention and 3 d x
    intermediate of the gated MLP, plus the head's d^2 dense) + the tied
    decoder's matmul + QK^T and PV: 4 x seq x d in a global layer, and
    4 x (keys a query sees: ``local_attention + 1``, never more than
    ``seq``) x d in a local one."""
    d, layers = config['hidden_size'], config['num_hidden_layers']
    n_global = sum(layer_kinds(config))
    per_layer = 4 * d * d + 3 * d * config['intermediate_size']
    band = min(seq, config['local_attention'] + 1)
    attn = 4 * d * (n_global * seq + (layers - n_global) * band)
    fwd = 2 * (layers * per_layer + d * d) + 2 * d * config['vocab_size'] \
        + attn
    return 3 * fwd


def to_reference_params(params):
    """The program's tree under the reference's names: layer 0 (which
    the program keeps apart: it has no attention norm) and layers 1..n-1
    stacked in depth order. The program stacks the layers of a kind
    (``blocks['global']``, ``blocks['window']``), and its scan runs
    periods of layers 1..n-1; here they are interleaved again. The
    gated MLP's ``[d, 2, I]`` kernel is the published ``[d, 2 I]``
    matrix, input half first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def layer(b):
        up = b['mlp']['up']['kernel']
        out = {'w_qkv': b['attn']['qkv']['kernel'],
               'w_o': b['attn']['out']['kernel'],
               'ln_mlp': b['ln2']['scale'],
               'w_i': up.reshape(up.shape[:-2] + (-1,)),
               'w_o2': b['mlp']['down']['kernel']}
        if 'ln1' in b:
            out['ln_attn'] = b['ln1']['scale']
        return out

    stacks = {kind: layer(b) for kind, b in params['blocks'].items()}
    n_global = len(stacks['global']['ln_mlp'])
    n_window = len(stacks['window']['ln_mlp'])
    every = n_window // n_global + 1
    if n_window != (every - 1) * n_global:
        raise ValueError('%d global and %d window layers after layer 0 are '
                         'not whole periods' % (n_global, n_window))
    # depth order of layers 1..n-1: global where the depth is a multiple
    # of `every`; row g of the global stack is layer (g + 1) * every
    order, seen = [], {'global': 0, 'window': 0}
    for depth in range(1, 1 + n_global + n_window):
        kind = 'global' if depth % every == 0 else 'window'
        order.append(seen[kind] + (n_window if kind == 'global' else 0))
        seen[kind] += 1
    order = np.asarray(order)
    rest = jax.tree.map(
        lambda w, g: jnp.concatenate([w, g])[order],
        stacks['window'], stacks['global'])
    head = params['head']
    return {
        'embed': params['embed']['table'],
        'ln_emb': params['ln_embed']['scale'],
        'first': layer(params['block_000']),
        'rest': rest,
        'ln_final': params['ln_f']['scale'],
        'head_dense': head['dense']['kernel'],
        'ln_head': head['norm']['scale'],
        'decoder_bias': head['decoder_bias'],
    }


def reference_loss(ref_params, tokens, targets, config, window=True,
                   local_theta=True, exact_gelu=True):
    """Mean cross-entropy of ONE sequence (``tokens``, ``targets``:
    ``[s]``) in float32.

    The three switches exist for the tests only: a reference without
    the window, with the global rotary base in every layer, or with the
    tanh GELU has to be told apart from the right one by the tolerance
    the benchmark uses."""
    import jax
    import jax.numpy as jnp

    eps = config['norm_eps']
    n_heads = config['num_attention_heads']
    half = config['local_attention'] // 2
    kinds = layer_kinds(config)

    def layer_norm(x, g):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * g

    def gelu(x):
        if exact_gelu:
            return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    def rotate(x, theta):
        # x [s, heads, hd]; rotate-half over the whole head dim
        s, _, hd = x.shape
        inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
        cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
        sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attend(q, k, v, is_global):
        """softmax(q k^T / sqrt(hd)) v for [s, heads, hd] operands, a
        block of queries at a time against every key."""
        s, _, hd = q.shape
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError('seq %d is not a multiple of %d' % (s, block))
        kpos = jnp.arange(s)

        def one_block(args):
            qb, start = args                       # [block, heads, hd]
            scores = jnp.einsum('qhd,khd->hqk', qb, k) / math.sqrt(hd)
            qpos = start + jnp.arange(block)
            near = jnp.abs(qpos[:, None] - kpos[None, :]) <= half
            keep = jnp.logical_or(near, is_global) if window else \
                jnp.ones_like(near)
            scores = jnp.where(keep[None], scores, -jnp.inf)
            return jnp.einsum('hqk,khd->qhd',
                              jax.nn.softmax(scores, axis=-1), v)

        out = jax.lax.map(jax.checkpoint(one_block),
                          (q.reshape(s // block, block, n_heads, hd),
                           jnp.arange(0, s, block)))
        return out.reshape(s, n_heads, hd)

    def layer(h, w, is_global, theta):
        s, d = h.shape
        a = layer_norm(h, w['ln_attn']) if 'ln_attn' in w else h
        q, k, v = (t.reshape(s, n_heads, d // n_heads)
                   for t in jnp.split(a @ w['w_qkv'], 3, axis=-1))
        o = attend(rotate(q, theta), rotate(k, theta), v, is_global)
        h = h + o.reshape(s, d) @ w['w_o']
        inp, gate = jnp.split(layer_norm(h, w['ln_mlp']) @ w['w_i'], 2,
                              axis=-1)
        return h + (gelu(inp) * gate) @ w['w_o2']

    def theta_of(is_global):
        if not local_theta:
            return float(config['global_rope_theta'])
        return jnp.where(is_global, float(config['global_rope_theta']),
                         float(config['local_rope_theta']))

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        h = layer_norm(p['embed'][tokens], p['ln_emb'])
        # layer 0 is global (0 % n == 0) and has no attention norm
        h = jax.checkpoint(lambda h, w: layer(
            h, w, True, float(config['global_rope_theta'])))(h, p['first'])

        def step(h, xs):
            w, is_global = xs
            return layer(h, w, is_global, theta_of(is_global)), None

        h, _ = jax.lax.scan(jax.checkpoint(step), h,
                            (p['rest'], jnp.asarray(kinds[1:])))
        h = layer_norm(h, p['ln_final'])
        h = layer_norm(gelu(h @ p['head_dense']), p['ln_head'])
        logits = h @ p['embed'].T + p['decoder_bias']
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)


def reference_loss_and_grad_norm(config, ref_params, batch, **switches):
    """(loss, global L2 norm of the gradient) of the plain reference on
    ``batch``: the mean over its sequences, one at a time (they are of
    one length, so the mean of their means is the batch's mean); both
    Python floats."""
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
    return loss, math.sqrt(float(sq)) / n
