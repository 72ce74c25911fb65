"""Model family ``transformer``: GPT-2's block, causal or not.

Four things a configuration of this family needs, and nothing of the
program's beyond its public constructors:

* :func:`build` makes the program's model from a configuration file;
* :func:`reference_loss` is the plain reference: float32 ``jax.numpy``
  forward and loss of the architecture as published (Radford et al. 2019
  for the block: pre-LN, learned positions, tanh GELU, tied head, final
  LN; Devlin et al. 2018 for the non-causal masked-LM use), with the
  departures each configuration lists under ``assumed``. No kernel,
  layers under ``lax.scan`` so it compiles in seconds. It shares no code
  with ``autodist_tpu.models``;
* :func:`to_reference_params` is the name map from the program's
  parameter tree to the reference's;
* :func:`flops_per_token` is the analytic model FLOPs (PaLM appendix B
  style): what forward and backward require, recomputation not counted.
"""
import math

LN_EPS = 1e-6   # the program's LayerNorm eps; listed under `assumed`


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    d = config['hidden_size']
    if config['intermediate_size'] % d:
        raise ValueError('intermediate_size %d is not a multiple of '
                         'hidden_size %d' % (config['intermediate_size'], d))
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=d,
        n_layers=config['num_hidden_layers'],
        n_heads=config['num_attention_heads'],
        mlp_ratio=config['intermediate_size'] // d,
        max_len=config['max_position_embeddings'],
        causal=config['causal'],
        tied_embeddings=config['tied_embeddings'],
        dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'])
    return TransformerLM(cfg)


def flops_per_token(config, seq):
    """Model FLOPs one training token requires: 3 x forward, where
    forward = 2 x (non-embedding parameters) + the tied head's matmul +
    QK^T and AV (4 x layers x seq x width; half of it under a causal
    mask, where the upper triangle is never needed)."""
    d, layers = config['hidden_size'], config['num_hidden_layers']
    per_layer = 4 * d * d + 2 * d * config['intermediate_size']
    attn = 4 * layers * seq * d
    if config['causal']:
        attn //= 2
    fwd = 2 * layers * per_layer + 2 * d * config['vocab_size'] + attn
    return 3 * fwd


def to_reference_params(params):
    """The program's tree (scanned layers stacked on a leading axis)
    under the reference's names."""
    blocks = params['blocks']
    return {
        'wte': params['embed']['table'],
        'wpe': params['pos_embed']['table'],
        'lnf_g': params['ln_f']['scale'], 'lnf_b': params['ln_f']['bias'],
        'layers': {
            'ln1_g': blocks['ln1']['scale'], 'ln1_b': blocks['ln1']['bias'],
            'w_qkv': blocks['attn']['qkv']['kernel'],
            'w_o': blocks['attn']['out']['kernel'],
            'ln2_g': blocks['ln2']['scale'], 'ln2_b': blocks['ln2']['bias'],
            'w_fc': blocks['mlp']['up']['kernel'],
            'b_fc': blocks['mlp']['up']['bias'],
            'w_proj': blocks['mlp']['down']['kernel'],
            'b_proj': blocks['mlp']['down']['bias'],
        },
    }


def reference_loss(ref_params, tokens, targets, n_heads, causal,
                   attention_scale=True, mask=True, final_ln=True):
    """Mean next-token / masked-token cross-entropy in float32.

    The three switches exist for the tests only: a reference with the
    attention scale, the causal mask or the final LN removed has to be
    told apart from the right one by the tolerance the benchmark uses.
    """
    import jax
    import jax.numpy as jnp

    def layer_norm(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b

    def gelu_new(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        b, s = tokens.shape
        d = p['wte'].shape[1]
        hd = d // n_heads
        x = p['wte'][tokens] + p['wpe'][jnp.arange(s)][None]

        def block(x, w):
            h = layer_norm(x, w['ln1_g'], w['ln1_b'])
            q, k, v = jnp.split(h @ w['w_qkv'], 3, axis=-1)
            q, k, v = (t.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
                       for t in (q, k, v))
            scores = jnp.einsum('bhqd,bhkd->bhqk', q, k)
            if attention_scale:
                scores = scores / math.sqrt(hd)
            if causal and mask:
                keep = jnp.tril(jnp.ones((s, s), bool))
                scores = jnp.where(keep, scores, -jnp.inf)
            a = jnp.einsum('bhqk,bhkd->bhqd',
                           jax.nn.softmax(scores, axis=-1), v)
            x = x + a.transpose(0, 2, 1, 3).reshape(b, s, d) @ w['w_o']
            h = layer_norm(x, w['ln2_g'], w['ln2_b'])
            h = gelu_new(h @ w['w_fc'] + w['b_fc'])
            return x + h @ w['w_proj'] + w['b_proj'], None

        # The backward pass computes each layer again instead of keeping
        # 24 layers of f32 score matrices (12 GB at seq 1024, which the
        # chip does not have beside the training state); the arithmetic
        # is the same.
        x, _ = jax.lax.scan(jax.checkpoint(block), x, p['layers'])
        if final_ln:
            x = layer_norm(x, p['lnf_g'], p['lnf_b'])
        logits = x @ p['wte'].T
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def reference_loss_and_grad_norm(config, ref_params, batch, **switches):
    """(loss, global L2 norm of the gradient) of the plain reference on
    ``batch``; both Python floats."""
    import jax
    import jax.numpy as jnp

    def run(p, tokens, targets):
        loss, grads = jax.value_and_grad(reference_loss)(
            p, tokens, targets, config['num_attention_heads'],
            config['causal'], **switches)
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        return loss, jnp.sqrt(sq)

    loss, norm = jax.jit(run)(ref_params, jnp.asarray(batch['tokens']),
                              jnp.asarray(batch['targets']))
    return float(loss), float(norm)
