"""Model family ``nemotron_h``: NVIDIA's Nemotron-3-Nano-30B-A3B
(``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``,
``model_type: nemotron_h``), on ONE CHIP'S SHARE of a deployment that
divides each layer over several chips: ``num_experts_held`` of the
``n_routed_experts`` routed experts (the first ones), the Mamba-2 and
attention mixers and the shared expert whole, a slice of the vocabulary,
the first ``num_hidden_layers`` layers of ``hybrid_override_pattern``.

The four things a family gives (see ``transformer.py``): :func:`build`,
the plain reference (:func:`reference_loss`,
:func:`reference_loss_and_grad_norm`), :func:`to_reference_params` and
:func:`flops_per_token`.

The architecture as the reference computes it, in float32 under
``jax.default_matmul_precision('highest')``, sharing no code with
``autodist_tpu`` (``x [s, hidden]``; no projection has a bias;
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``):

* ``h = E[tokens]`` (unscaled). Layer ``i``: ``h = h + mixer_i(
  RMSNorm_i(h))``, the mixer by the i-th letter of the pattern. Then
  ``RMSNorm``, ``logits = h W_head`` (untied, over the slice), mean
  next-token cross-entropy.
* ``M``, Mamba-2 (``H = mamba_num_heads`` heads of ``P =
  mamba_head_dim``, ``G = n_groups`` groups of ``N = ssm_state_size``):
  ``[z | xBC | dt] = x W_in`` (widths ``HP | HP + 2GN | H``); ``xBC <-
  silu(conv(xBC) + b_conv)``, the conv depthwise and causal over
  ``conv_kernel`` taps (``xBC_t`` from ``t - 3 .. t``, zeros before the
  sequence), as four shifted products; ``x | B | C = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``a = exp(dt x -exp(A_log))``; per head
  (group ``h // (H / G)``), THE RECURRENCE A POSITION AT A TIME (a
  ``lax.scan`` over positions, never the chunked form): ``S_t = a_t
  S_{t-1} + dt_t x_t B_t^T`` from ``S = 0``, ``y_t = S_t C_t + D x_t``;
  ``y <- RMSNorm over each of G groups of HP / G lanes of (y *
  silu(z))``, one weight of ``HP``; ``out = y W_out``.
* ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` kv heads of ``head_dim`` (query head ``n``
  uses kv head ``n // (heads / kv)``), causal softmax at
  ``head_dim^-0.5``, NO rotation and no position table.
* ``E``, experts: ``s = sigmoid(x W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` experts with the largest ``s_e + b_e``
  (``b`` selects only, and takes no gradient; one group); ``w_e =
  routed_scaling_factor x s_e / (sum of the chosen s + 1e-20)``;
  ``routed = sum over the chosen e that are HELD of w_e relu(x
  W_up,e)^2 W_down,e`` at ``moe_intermediate_size``, no gate; plus one
  shared expert of the same form at
  ``moe_shared_expert_intermediate_size`` for every token. What the
  experts held elsewhere would add is left out, here as in the program.

How it fits beside the training state: one sequence at a time, each
layer computed again in the backward pass, the recurrence in blocks of
``SCAN_BLOCK`` positions whose inner steps are computed again (what is
kept is a state a block, 2 MB each), attention a block of queries
against every key, the experts one at a time, the logits ``LOSS_ROWS``
positions at a time.
"""
import math

QUERY_BLOCK = 512
LOSS_ROWS = 2048
SCAN_BLOCK = 128

# The limits on one leaf of the gradient, |program - reference| /
# |reference| in L2 (held_to_every_leaf), each between its two readings
# on the chip (my chip runs, PR 41; PERF.md §6 has every reading:
# fifteen sound runs on fourteen seeds, embedding rows at N(0, 16^2),
# N(0, 128^2) and N(0, 256^2); the faults at seed 4300000031 and 256). A
# leaf not behind a router (the Mamba-2 and attention layers', embed,
# head, the shared experts'): sound 1.14-1.68% at worst (dt_bias or a_log of a
# Mamba-2 layer; w_q and w_k 1.3, embed 0.8, head 0.7); with every
# product's operands, the scan's among them, held to float8_e4m3's
# mantissa, the nearest precision below the program's bfloat16, 11.5%
# (layer_7/dt_bias); at bfloat16's mantissa 1.42%, the program's own
# precision.
LEAF_RTOL = 0.04
# ... a leaf whose gradient comes through the routed experts (the
# program routes on bf16 activations, so near a tie its six of 128
# differ from the reference's for a few tokens in a hundred, and a held
# expert here sees 768 rows a step): sound 9.4-13.3% (w_up, w_down;
# ln_mlp 2.5-3.6%); with float8's mantissa 28.4%, with one held
# expert's rows left out 40.1%.
ROUTED_LEAF_RTOL = 0.20
ROUTED = ('ln_mlp', 'w_up', 'w_down')
# ... and the router's own leaf: sound 13.9-18.8%; float8's mantissa
# 39.1%, an expert's rows left out 40.6%.
ROUTER_LEAF_RTOL = 0.30

LETTERS = 'ME*'       # Mamba-2, the expert layer, attention


def pattern(config):
    """The layers run: the first ``num_hidden_layers`` letters of the
    published string."""
    letters = config['hybrid_override_pattern'][:config['num_hidden_layers']]
    if len(letters) != config['num_hidden_layers'] \
            or set(letters) - set(LETTERS):
        raise ValueError('hybrid_override_pattern %r does not give %d '
                         'layers of M, E or *'
                         % (config['hybrid_override_pattern'],
                            config['num_hidden_layers']))
    return letters


def _ssm_dims(config):
    """(heads, head width, groups, state, conv taps)."""
    return (config['mamba_num_heads'], config['mamba_head_dim'],
            config['n_groups'], config['ssm_state_size'],
            config['conv_kernel'])


def build(config):
    """The program's model for ``config`` (a configuration file's dict)."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    if (config['mlp_hidden_act'], config['mamba_hidden_act']) != (
            'relu2', 'silu') or config['tie_word_embeddings'] \
            or config['attention_bias'] or config['mlp_bias'] \
            or config['use_bias'] or config['mamba_proj_bias'] \
            or not config['use_conv_bias'] or not config['norm_topk_prob']:
        raise ValueError('family nemotron_h: relu2 experts, silu in the '
                         'Mamba layers, an untied head, a conv bias and no '
                         'other, norm_topk_prob only')
    if (config['n_group'], config['topk_group'],
            config['n_shared_experts']) != (1, 1, 1):
        raise ValueError('family nemotron_h: one group of experts and one '
                         'shared expert')
    heads, head_dim, groups, state, conv = _ssm_dims(config)
    cfg = TransformerConfig(
        vocab=config['vocab_size'], dim=config['hidden_size'],
        n_layers=config['num_hidden_layers'], mixers=pattern(config),
        n_heads=config['num_attention_heads'],
        n_kv_heads=config['num_key_value_heads'],
        head_dim=config['head_dim'],
        max_len=config['max_position_embeddings'], causal=True,
        tied_embeddings=False, dtype=jnp.dtype(config['dtype']),
        remat=config['remat'], scan_layers=config['scan_layers'],
        loss_chunk=config['loss_chunk'], positions='none',
        ssm=dict(heads=heads, head_dim=head_dim, groups=groups, state=state,
                 conv=conv, dt_min=config['time_step_min'],
                 dt_max=config['time_step_max'],
                 dt_floor=config['time_step_floor'],
                 a_range=tuple(config['a_init_range'])),
        mlp_dim=config['moe_intermediate_size'], gated_mlp=False,
        gelu='relu2', norm='rms', norm_eps=config['layer_norm_epsilon'],
        mlp_bias=False, moe_experts=config['n_routed_experts'],
        moe_top_k=config['num_experts_per_tok'],
        moe_held=config['num_experts_held'],
        moe_aux_coef=config['moe_aux_coef'], moe_scoring='sigmoid',
        moe_scale=config['routed_scaling_factor'],
        moe_shared_dim=config['moe_shared_expert_intermediate_size'],
        embed_init_scale=config['embed_init_scale'])
    return TransformerLM(cfg)


def scan_flops_per_token(config):
    """FLOPs a token of the chunked scan of ONE Mamba-2 layer, forward,
    at ``chunk_size``, a multiply-add as two: ``C B^T`` (a group's, over
    the ``Q / 2`` positions a position sees in its chunk on average),
    the masked product with ``dt x`` (a head's, the same half), the
    chunk's state (``P x N`` a head and position), what the entering
    state adds (the same) and the state carried on (``P x N`` a head and
    chunk)."""
    heads, p, groups, n, _ = _ssm_dims(config)
    q = config['chunk_size']
    return 2 * (groups * (q / 2) * n + heads * (q / 2) * p
                + 2 * heads * p * n + heads * p * n / q)


def layer_flops_per_token(config, seq):
    """Forward FLOPs a token of one layer of each kind on this chip,
    ``{'M', 'E', '*'}``."""
    d = config['hidden_size']
    heads, p, groups, n, conv = _ssm_dims(config)
    inner = heads * p
    conv_dim = inner + 2 * groups * n
    q_heads, kv, hd = (config['num_attention_heads'],
                       config['num_key_value_heads'], config['head_dim'])
    pairs = config['num_experts_per_tok'] * config['num_experts_held'] \
        / config['n_routed_experts']
    return {
        'M': 2 * (d * (inner + conv_dim + heads) + inner * d)
        + 2 * conv * conv_dim + scan_flops_per_token(config),
        '*': 2 * (d * (q_heads + 2 * kv) * hd + q_heads * hd * d)
        + 2 * (seq / 2) * q_heads * 2 * hd,
        'E': 2 * (d * config['n_routed_experts']
                  + 2 * d * config['moe_shared_expert_intermediate_size']
                  + pairs * 2 * d * config['moe_intermediate_size']),
    }


def flops_per_token(config, seq):
    """Model FLOPs one training token requires on this chip: 3 x forward,
    where forward = the layers run, each by its kind
    (:func:`layer_flops_per_token`: a Mamba-2 layer its two projections,
    the conv and the chunked scan at ``chunk_size``; attention its
    matrices and QK^T and PV over half of ``seq``, the keys a query sees
    under the causal mask; an expert layer the router, the shared expert
    whole and the held experts at the pairs a token is EXPECTED to have
    among them) + the head's matmul over the vocabulary slice."""
    by_kind = layer_flops_per_token(config, seq)
    fwd = sum(by_kind[letter] for letter in pattern(config)) \
        + 2 * config['hidden_size'] * config['vocab_size']
    return 3 * fwd


# -- the program's tree under the reference's names ---------------------------

def to_reference_params(params):
    """The program's tree under the reference's names: ``layer_<i>`` a
    layer, by its kind; the attention's fused projection in its runs q,
    k, v (three slices of a small matrix; the experts, which are most of
    the bytes, are not copied)."""
    out = {'embed': params['embed']['table'],
           'ln_final': params['ln_f']['scale'],
           'head': params['lm_head']['kernel']}
    i = 0
    while 'block_%03d' % i in params:
        layer = params['block_%03d' % i]
        m, ln = layer['mixer'], layer['norm']['scale']
        if 'a_log' in m:
            ref = {'ln_ssm': ln, 'w_in': m['in']['kernel'],
                   'w_conv': m['conv'], 'b_conv': m['conv_bias'],
                   'dt_bias': m['dt_bias'], 'a_log': m['a_log'], 'd': m['d'],
                   'ln_gate': m['norm']['scale'],
                   'w_out': m['out']['kernel']}
        elif 'router' in m:
            ref = {'ln_mlp': ln, 'w_router': m['router']['kernel'],
                   'b_select': m['select_bias'], 'w_up': m['up'],
                   'w_down': m['down'],
                   'ws_up': m['shared']['up']['kernel'],
                   'ws_down': m['shared']['down']['kernel']}
        else:
            qkv, o = m['qkv']['kernel'], m['out']['kernel']
            q = o.shape[-2]
            kv = (qkv.shape[-1] - q) // 2
            ref = {'ln_attn': ln, 'w_q': qkv[..., :q],
                   'w_k': qkv[..., q:q + kv], 'w_v': qkv[..., q + kv:],
                   'w_o': o}
        out['layer_%d' % i] = ref
        i += 1
    return out


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


def _relu2_mlp(x, up, down, lo, gated=False):
    import jax
    import jax.numpy as jnp
    u = lo(x) @ lo(up)
    if gated:       # a FAULT: the first half of the units gating the second
        f = u.shape[-1] // 2
        h = jnp.square(jax.nn.relu(u[:, :f])) * u[:, f:]
        return lo(h) @ lo(down[:f])
    return lo(jnp.square(jax.nn.relu(u))) @ lo(down)


def reference_expert_layer(w, x, config, select_bias=True, shared=True,
                           drop_expert=None, matmul_dtype=None, gated=False):
    """The ``E`` mixer for ``x [s, hidden]`` (the module's docstring):
    the held experts' part of the routed sum, every held expert computed
    for every position and weighted by ``w_e`` (zero where it was not
    chosen), plus the shared expert. The caller sets the matmul
    precision."""
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

    def one_expert(e, up, down):                   # [d, f], [f, d]
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        if drop_expert is not None:
            w_e = jnp.where(e == drop_expert, 0.0, w_e)
        return w_e[:, None] * _relu2_mlp(x, up, down, lo, gated)

    out, _ = jax.lax.scan(
        lambda out, args: (out + jax.checkpoint(one_expert)(*args), None),
        jnp.zeros_like(x), (jnp.arange(held), w['w_up'], w['w_down']))
    if shared:
        out = out + _relu2_mlp(x, w['ws_up'], w['ws_down'], lo, gated)
    return out


def reference_mamba_layer(w, x, config, conv_bias=True, skip=True,
                          gate_inside=True, matmul_dtype=None):
    """The ``M`` mixer for ``x [s, hidden]`` (the module's docstring),
    the recurrence a position at a time. The switches are FAULTS: the
    conv's bias left out, ``D x`` left out, the gate applied after the
    norm instead of inside it."""
    import jax
    import jax.numpy as jnp
    lo = _held_in(matmul_dtype)
    heads, p, groups, n, conv = _ssm_dims(config)
    inner, s = heads * p, x.shape[0]
    conv_dim = inner + 2 * groups * n
    eps = config['layer_norm_epsilon']

    zxbcdt = lo(x) @ lo(w['w_in'])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                  zxbcdt[:, inner + conv_dim:])
    padded = jnp.pad(xbc, ((conv - 1, 0), (0, 0)))
    xbc = sum(padded[k:k + s] * w['w_conv'][k] for k in range(conv))
    if conv_bias:
        xbc = xbc + w['b_conv']
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :inner].reshape(s, heads, p)
    b = xbc[:, inner:inner + groups * n].reshape(s, groups, n)
    c = xbc[:, inner + groups * n:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + w['dt_bias'])                     # [s, heads]
    decay = jnp.exp(dt * -jnp.exp(w['a_log']))
    rep = heads // groups

    def step(state, args):
        x_t, dt_t, a_t, b_t, c_t = args
        b_t, c_t = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)
        state = a_t[:, None, None] * state \
            + lo(dt_t[:, None] * x_t)[:, :, None] * lo(b_t)[:, None, :]
        return state, jnp.sum(state * lo(c_t)[:, None, :], axis=-1)

    def block(state, args):
        return jax.lax.scan(step, state, args)
    size = min(SCAN_BLOCK, s)
    if s % size:
        raise ValueError('seq %d is not a multiple of %d' % (s, size))
    _, y = jax.lax.scan(
        jax.checkpoint(block), jnp.zeros((heads, p, n), jnp.float32),
        tuple(t.reshape((s // size, size) + t.shape[1:])
              for t in (xs, dt, decay, b, c)))
    y = y.reshape(s, heads, p)
    if skip:
        y = y + w['d'][:, None] * xs
    y = y.reshape(s, inner)
    gate = jax.nn.silu(z)

    def group_norm(t):
        t = t.reshape(s, groups, inner // groups)
        t = t / jnp.sqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + eps)
        return t.reshape(s, inner) * w['ln_gate']
    y = group_norm(y * gate) if gate_inside else group_norm(y) * gate
    return lo(y) @ lo(w['w_out'])


def reference_loss(ref_params, tokens, targets, config, rotary=False,
                   matmul_dtype=None, **switches):
    """Mean cross-entropy of ONE sequence (``tokens``, ``targets``:
    ``[s]``) in float32.

    The switches exist to show what the comparison tells apart (the
    tests, and once on the chip): ``rotary`` LEFT ON in attention
    (rotate-half at ``rope_theta``, which the published code does not
    apply), the operands of every product held to a lower precision's
    mantissa (``matmul_dtype``), and by layer kind those of
    :func:`reference_mamba_layer` (``conv_bias``, ``skip``,
    ``gate_inside``) and :func:`reference_expert_layer`
    (``select_bias``, ``shared``, ``drop_expert``, ``gated``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = config['layer_norm_epsilon']
    heads, kv, hd = (config['num_attention_heads'],
                     config['num_key_value_heads'], config['head_dim'])
    s = tokens.shape[0]
    lo = _held_in(matmul_dtype)
    mamba_faults = {k: switches.pop(k) for k in ('conv_bias', 'skip',
                                                 'gate_inside')
                    if k in switches}

    def rms_norm(x, g):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + eps) * g

    def rotate(x):                     # [s, n, hd], the FAULT `rotary`
        inv_freq = float(config['rope_theta']) ** (
            -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
        angle = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
        both = np.concatenate([angle, angle], -1)[:, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return x * jnp.asarray(np.cos(both), jnp.float32) \
            + jnp.concatenate([-x2, x1], -1) * jnp.asarray(np.sin(both),
                                                           jnp.float32)

    def attention(x, w):
        q = (lo(x) @ lo(w['w_q'])).reshape(s, heads, hd)
        k = (lo(x) @ lo(w['w_k'])).reshape(s, kv, hd)
        v = (lo(x) @ lo(w['w_v'])).reshape(s, kv, hd)
        if rotary:
            q, k = rotate(q), rotate(k)
        k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
        size = min(QUERY_BLOCK, s)
        if s % size:
            raise ValueError('seq %d is not a multiple of %d' % (s, size))
        kpos = jnp.arange(s)

        def one_block(args):
            qb, start = args
            scores = jnp.einsum('qhd,khd->hqk', lo(qb), lo(k)) / math.sqrt(hd)
            keep = (start + jnp.arange(size))[:, None] >= kpos[None, :]
            scores = jnp.where(keep[None], scores, -jnp.inf)
            return jnp.einsum('hqk,khd->qhd',
                              lo(jax.nn.softmax(scores, axis=-1)), lo(v))
        o = jax.lax.map(jax.checkpoint(one_block), (
            q.reshape(s // size, size, heads, hd), jnp.arange(0, s, size)))
        return lo(o.reshape(s, heads * hd)) @ lo(w['w_o'])

    def layer(kind):
        def run(h, w):
            if kind == 'M':
                return h + reference_mamba_layer(
                    w, rms_norm(h, w['ln_ssm']), config,
                    matmul_dtype=matmul_dtype, **mamba_faults)
            if kind == 'E':
                return h + reference_expert_layer(
                    w, rms_norm(h, w['ln_mlp']), config,
                    matmul_dtype=matmul_dtype, **switches)
            return h + attention(rms_norm(h, w['ln_attn']), w)
        return jax.checkpoint(run)

    with jax.default_matmul_precision('highest'):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
        h = p['embed'][tokens]
        for i, kind in enumerate(pattern(config)):
            h = layer(kind)(h, p['layer_%d' % i])
        h = rms_norm(h, p['ln_final'])

        def nll(args):
            rows, gold = args
            logits = lo(rows) @ lo(p['head'])
            return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, gold[:, None], axis=-1)[:, 0]
        size = min(LOSS_ROWS, s)
        if s % size:
            raise ValueError('seq %d is not a multiple of %d' % (s, size))
        return jnp.mean(jax.lax.map(
            jax.checkpoint(nll), (h.reshape(s // size, size, -1),
                                  targets.reshape(s // size, size))))


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
    """``norm x (1 + GRAD_NORM_RTOL x worst)``, as ``kanana2.py``'s: the
    reference's global norm, raised by the largest of the leaves'
    differences (``mellum2.leaf_differences``; a layer's leaves are
    ``layer_<i>/<name>``, each whole), each in units of its leaf's limit.
    ``b_select``'s gradient is nothing on both sides or counts as a
    thousand limits. Prints the leaves' readings as one line."""
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
