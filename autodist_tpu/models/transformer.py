"""Transformer language model — the framework's flagship model family.

Covers the reference's BERT-large benchmark role (BASELINE.md: BERT-large
tokens/s) and the lm1b LSTM example's role as the language-model case,
built TPU-first: bfloat16 matmuls on the MXU, logical-axis sharding for
DP/TP/SP/EP, ring attention for long context, remat-friendly block
structure (scan-over-layers so XLA compiles one block).
"""
import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu import telemetry
from autodist_tpu.const import AXIS_PIPELINE, AXIS_SEQUENCE
from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.models.attention import (LatentAttention,
                                           MultiHeadAttention)
from autodist_tpu.models.core import (Dense, Embedding, GatedMlp, LayerNorm,
                                      Mlp, Module, ParamDef, RMSNorm,
                                      activation, constrain, record_counter)
from autodist_tpu.models.hyper_connections import (HyperConnection,
                                                    split_streams)
from autodist_tpu.parallel.axes import (active_manual_axes, ctx_option,
                                        manual_axis)


@dataclass
class TransformerConfig:
    vocab: int = 32000
    dim: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    mlp_ratio: int = 4
    max_len: int = 2048
    causal: bool = True
    tied_embeddings: bool = True
    dtype: object = jnp.bfloat16
    # remat: False = none; True = checkpoint each block: the backward
    # recomputes the block, all but the flash kernel's forward call
    # where the block makes one, whose output ([b, s, dim], the input of
    # the output projection) and row statistics are kept: b x s x (dim
    # x itemsize + 4 x heads) bytes a layer (docs/design/kernels.md),
    # and an expert layer's order of its rows (models/moe.py);
    # 'save_attn' = checkpoint each block but
    # SAVE the post-attention residual, so backward recomputes only the
    # LN2+MLP half at one extra [b,s,d] save per layer (it matters when
    # attention is the expensive recompute); 'dots' = save every matmul
    # output and recompute only elementwise work (the highest-memory
    # tier); 'dots_no_batch' = save only batch-free dots, which in a
    # transformer block is full remat. See _block_fn.
    remat: object = False
    scan_layers: bool = True     # stack blocks + lax.scan (1 compile/block)
    # Chunked cross-entropy: target rows (batch*seq positions) per chunk
    # of the lm-head + softmax computation. 0 = off (materialize full
    # [b, s, vocab] fp32 logits). On, the loss scans over sequence
    # chunks with jax.checkpoint, so peak memory holds one
    # [b, s/n, vocab] slab instead of the whole thing. A memory
    # feature, not a speed feature: at BERT-large bench shapes it frees
    # ~8 GB (batch 768 compiles where 640 OOMed before) at unchanged
    # tokens/s; it is what makes big-vocab / long-seq losses fit.
    loss_chunk: int = 0
    moe_experts: int = 0         # >0: MoE MLP; the router's width
    moe_top_k: int = 2
    moe_aux_coef: float = 0.01   # load-balance loss weight
    moe_held: object = None      # the experts this program holds of each
    #                              layer's moe_experts: a count (from
    #                              expert 0) or (first, count); None: all.
    #                              The router keeps its width and its
    #                              top_k; the layer computes what its own
    #                              experts add (models/moe.py)
    # -- the block's variants (docs/usage/layer-patterns.md). Each field
    # describes the architecture; the defaults are GPT-2's block.
    positions: str = 'learned'   # 'learned': a table of max_len rows
    #                              added to the embedding; 'rotary': none,
    #                              q and k are rotated in every layer;
    #                              'none': no table and no rotation (a
    #                              stack whose state-space layers carry
    #                              the order)
    rope_theta: float = 10000.0  # rotary base of the global layers
    window: object = None        # keys EACH SIDE a window layer attends
    #                              to (ModernBERT's local_attention 128
    #                              is 64); None: every layer is global
    #                              (under `causal`: keys before it, so
    #                              1023 is a causal window of 1024)
    global_every: int = 1        # with a window: layer i is global iff
    #                              i % global_every == global_at, else a
    #                              window layer
    global_at: int = 0           # the global layer's place in a period:
    #                              ModernBERT's first (0), Mellum2's last
    #                              (global_every - 1)
    window_rope_theta: object = None   # rotary base of the window
    #                              layers; None: rope_theta
    rope_yarn: object = None     # YaRN on the GLOBAL layers' rotary
    #                              frequencies: a mapping with factor,
    #                              original_max_position_embeddings,
    #                              beta_fast, beta_slow, attention_factor
    #                              (models/attention.rope_frequencies)
    n_kv_heads: object = None    # grouped kv heads; None: n_heads
    head_dim: object = None      # None: dim // n_heads
    mlp_dim: object = None       # MLP (or expert) width; None: dim *
    #                              mlp_ratio
    gated_mlp: bool = False      # down(act(input) * gate): GeGLU, SwiGLU
    gelu: str = 'tanh'           # the MLP's activation by name: 'tanh'
    #                              (GPT-2's gelu_new) | 'erf' | 'silu'
    norm: str = 'layer'          # 'layer' (LayerNorm) | 'rms' (RMSNorm:
    #                              no mean, no bias)
    norm_eps: float = 1e-6
    norm_bias: bool = True       # LayerNorms carry a bias
    mlp_bias: bool = True
    embed_norm: bool = False     # LayerNorm after the embedding; the
    #                              first layer's attention norm is then
    #                              the identity (ModernBERT)
    head_transform: bool = False  # prediction head: dense, act, norm
    #                              before the decoder (BERT's MLM head)
    decoder_bias: bool = False
    embed_init_scale: float = 0.02   # std of an embedding row's elements
    #                              as drawn at init
    # -- latent attention (DeepSeek-V2's MLA): with `latent_rank` every
    # layer's attention is models/attention.LatentAttention, a q/k head
    # of qk_nope_dim lanes of its own + qk_rope_dim rotary lanes whose
    # key all heads share, a v head of v_head_dim; `head_dim`,
    # `n_kv_heads` and `window` are then not used. `rope_yarn` applies to
    # the rotary lanes and may carry `score_factor`, what the family
    # multiplies the softmax scale by (DeepSeek's mscale^2)
    latent_rank: object = None   # the kv latent's width
    latent_q_rank: object = None   # q through a down-projection of this
    #                              width, an RMSNorm and an up-projection;
    #                              None: straight from the hidden state
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # -- MLPs of two kinds in one stack, and the router's variants
    dense_lead: int = 0          # with moe_experts: the first layers
    #                              whose MLP is dense, of dense_mlp_dim
    dense_mlp_dim: object = None   # None: mlp_dim
    moe_scoring: str = 'softmax'   # | 'sigmoid': scores of their own,
    #                              the experts chosen by score + a
    #                              selection bias (a parameter without a
    #                              gradient), weighed by the score alone
    moe_scale: float = 1.0       # factor on the routed experts' weights
    moe_shared_dim: int = 0      # >0: an always-on expert of this width
    #                              beside the routed ones
    # -- layers that are ONE mixer each (nemotron_h): with `mixers` layer
    # i is `x + mixer_i(norm(x))`, its mixer by the i-th of the pattern's
    # letters (MIXERS): 'M' a Mamba-2 layer (models/ssm.py, the sizes in
    # `ssm`), 'E' the MLP as the fields above describe it (the expert
    # layer with moe_experts), '*' attention. None: every layer is the
    # attention-then-MLP Block.
    mixers: object = None
    ssm: object = None           # the Mamba-2 layers' sizes: a mapping
    #                              with heads, head_dim, groups, state,
    #                              conv and, optionally, the draw's
    #                              dt_min, dt_max, dt_floor, a_range
    qk_norm: bool = False        # an RMSNorm (norm_eps) over every q head
    #                              and every k head before the rotation, one
    #                              weight of head_dim each for q and for k
    #                              (Qwen3's q_norm / k_norm)
    # -- the objective. None: one row of logits a token of `tokens`,
    # scored against `targets` (next-token or masked-LM, by the batch).
    # A block length B: a block-diffusion step (BD3-LM, SDAR): the stack
    # runs on the 2 L rows [tokens ; targets] of every sequence, the
    # noised copy and then the clean one, under the block-diffusion mask
    # in blocks of B (kernels/flash_attention.py), row i of either copy at
    # position i; the final norm, the head and the loss on the L noised
    # rows alone, at the same position (no shift), weighed by the batch's
    # `mask` (TransformerLM.loss)
    block_length: object = None
    # -- the residual path as `hc_streams` streams, mixed a sublayer by
    # manifold-constrained hyper-connections
    # (models/hyper_connections.py): the stack carries [b, s, hc_streams
    # * dim], the embedding copied to every stream at the entry, the
    # streams summed before the final norm. None: x + f(norm(x)) on one
    hc_streams: object = None
    hc_iters: int = 20           # Sinkhorn-Knopp rounds of the stream mix
    hc_clamp: tuple = (-30.0, 30.0)   # of that mix's logits, before exp
    hc_eps: float = 1e-6         # in the coefficients' norm and in every
    #                              round's sums

    def __post_init__(self):
        if self.positions not in POSITIONS:
            raise ValueError('positions must be %s, not %r'
                             % (' or '.join(map(repr, POSITIONS)),
                                self.positions))
        if self.mixers is not None:
            self.mixers = ''.join(self.mixers)
            if len(self.mixers) != self.n_layers \
                    or set(self.mixers) - set(MIXERS):
                raise ValueError(
                    'mixers=%r: one of %s for each of the %d layers'
                    % (self.mixers, ', '.join(map(repr, MIXERS)),
                       self.n_layers))
            if 'M' in self.mixers and not self.ssm:
                raise ValueError("mixers=%r has Mamba-2 layers: give their "
                                 "sizes in `ssm`" % (self.mixers,))
            if self.window is not None or self.latent_rank \
                    or self.dense_lead or self.embed_norm:
                raise ValueError('single-mixer layers take no window, no '
                                 'latent attention, no dense_lead and no '
                                 'embed_norm')
        if self.hc_streams is not None and (self.hc_streams < 2
                                            or self.mixers):
            raise ValueError(
                'hc_streams=%r: two or more residual streams, around the '
                'two sublayers of attention-then-MLP blocks; single-mixer '
                'layers (mixers=%r) take one stream'
                % (self.hc_streams, self.mixers))
        if self.latent_q_rank and not self.latent_rank:
            raise ValueError('latent_q_rank=%r is latent attention\'s q '
                             'down-projection: give latent_rank too'
                             % (self.latent_q_rank,))
        activation(self.gelu)
        if self.norm not in ('layer', 'rms'):
            raise ValueError("norm must be 'layer' or 'rms', not %r"
                             % (self.norm,))
        if self.window is not None and self.global_every < 2:
            raise ValueError(
                'window=%r needs global_every >= 2 (layer i is global iff '
                'i %% global_every == global_at); with global_every=%d no '
                'layer would use the window'
                % (self.window, self.global_every))
        if not 0 <= self.global_at < self.global_every:
            raise ValueError('global_at=%d is no place in a period of %d '
                             'layers' % (self.global_at, self.global_every))
        if self.latent_rank and (self.positions != 'rotary'
                                 or self.window is not None):
            raise ValueError('latent attention takes rotary positions and '
                             'no window')
        if self.block_length is not None and (
                self.window is not None or self.latent_rank or self.mixers
                or self.positions == 'learned' or self.loss_chunk):
            raise ValueError(
                'block_length=%r (the block-diffusion objective) runs '
                'attention-then-MLP blocks of one kind under its own mask: '
                'no window (the band and this mask are two descriptions of '
                'live pairs), no latent attention, no single-mixer layers, '
                'no learned position table (a row\'s position is not its '
                'index) and no chunked loss' % (self.block_length,))
        if self.dense_lead and not (self.moe_experts and 0 < self.dense_lead
                                    < self.n_layers):
            raise ValueError('dense_lead=%d: the dense layers lead a stack '
                             'of expert layers (moe_experts > 0, fewer than '
                             'n_layers=%d)' % (self.dense_lead,
                                               self.n_layers))

    def layer_kinds(self):
        """'global' or 'window' for each layer."""
        return ['global' if self.window is None
                or i % self.global_every == self.global_at
                else 'window' for i in range(self.n_layers)]

    def held_experts(self):
        """``(first, count)`` of the experts this program holds."""
        held = self.moe_held
        if held is None:
            return 0, self.moe_experts
        return (0, held) if isinstance(held, int) else tuple(held)

    @classmethod
    def modernbert_large(cls, **kw):
        """ModernBERT-large (answerdotai/ModernBERT-large, arXiv:
        2412.13663): 28 x 1024 x 16 heads, GeGLU 2624, rotary positions,
        every third layer global, the others in a window of 64 each
        side."""
        d = dict(vocab=50368, dim=1024, n_layers=28, n_heads=16,
                 max_len=8192, causal=False, positions='rotary',
                 rope_theta=160000.0, window_rope_theta=10000.0, window=64,
                 global_every=3, mlp_dim=2624, gated_mlp=True, gelu='erf',
                 norm_eps=1e-5, norm_bias=False, mlp_bias=False,
                 embed_norm=True, head_transform=True, decoder_bias=True)
        d.update(kw)
        return cls(**d)

    @classmethod
    def bert_large(cls, **kw):
        """BERT-large class config (24L/1024d/16h) — reference headline
        pre-training model (docs/usage/performance.md:7)."""
        d = dict(vocab=30522, dim=1024, n_layers=24, n_heads=16,
                 causal=False, max_len=512)
        d.update(kw)
        return cls(**d)

    @classmethod
    def gpt_small(cls, **kw):
        d = dict(vocab=32000, dim=768, n_layers=12, n_heads=12,
                 causal=True, max_len=1024)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab=256, dim=64, n_layers=2, n_heads=4, max_len=128)
        d.update(kw)
        return cls(**d)


POSITIONS = ('learned', 'rotary', 'none')
# A single-mixer layer's kinds, by the letter nemotron_h's
# hybrid_override_pattern gives them, and the scope each runs under.
MIXERS = {'M': 'ssm', 'E': 'mlp', '*': 'attention'}


def _add(a, b):
    """Sum of two ``aux`` values: scalars, or ``(aux, stats)`` pairs."""
    return jax.tree.map(jnp.add, a, b)


def _norm(cfg):
    if cfg.norm == 'rms':
        return RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
    return LayerNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype,
                     use_bias=cfg.norm_bias)


def _act(cfg):
    return activation(cfg.gelu)


def _mlp(cfg, dense=False):
    """The MLP of a layer as ``cfg`` describes it: the expert layer with
    ``moe_experts`` (unless ``dense``: a leading dense layer, of
    ``dense_mlp_dim``), else a gated or a plain MLP."""
    hidden = cfg.mlp_dim or cfg.dim * cfg.mlp_ratio
    if dense:
        hidden = cfg.dense_mlp_dim or hidden
    if cfg.moe_experts and not dense:
        from autodist_tpu.models.moe import MoeMlp
        return MoeMlp(cfg.dim, hidden,
                      cfg.moe_experts, top_k=cfg.moe_top_k,
                      held=cfg.held_experts(), dtype=cfg.dtype,
                      act=_act(cfg), gated=cfg.gated_mlp,
                      scoring=cfg.moe_scoring,
                      select_bias=cfg.moe_scoring == 'sigmoid',
                      scale=cfg.moe_scale,
                      shared=cfg.moe_shared_dim)
    if cfg.gated_mlp:
        return GatedMlp(cfg.dim, hidden, dtype=cfg.dtype,
                        act=_act(cfg), use_bias=cfg.mlp_bias)
    return Mlp(cfg.dim, hidden, dtype=cfg.dtype, act=_act(cfg),
               use_bias=cfg.mlp_bias)


class MixerLayer(Module):
    """A layer that is ONE mixer: ``x + mixer(norm(x))``, the mixer by
    ``kind`` (:data:`MIXERS`): a Mamba-2 layer, the MLP (the expert
    layer with ``cfg.moe_experts``) or attention, each under the scope
    :data:`MIXERS` names. ``apply`` returns what :class:`Block`'s does."""

    def __init__(self, cfg, kind):
        self.cfg, self.kind = cfg, kind
        self.norm = _norm(cfg)
        self.attn = None
        self.sparse = kind == 'E' and bool(cfg.moe_experts)
        if kind == 'M':
            from autodist_tpu.models.ssm import Mamba2Mixer
            self.mixer = Mamba2Mixer(cfg.dim, dtype=cfg.dtype,
                                     norm_eps=cfg.norm_eps, **cfg.ssm)
        elif kind == 'E':
            self.mixer = _mlp(cfg)
        else:
            self.mixer = self.attn = MultiHeadAttention(
                cfg.dim, cfg.n_heads, head_dim=cfg.head_dim,
                causal=cfg.causal, dtype=cfg.dtype,
                rope_theta=cfg.rope_theta if cfg.positions == 'rotary'
                else None, num_kv_heads=cfg.n_kv_heads,
                rope_yarn=cfg.rope_yarn)

    def param_defs(self):
        return {'norm': self.norm, 'mixer': self.mixer}

    @jax.named_scope('block')
    def apply(self, params, x, tables=None, stats=False):
        aux = jnp.zeros((), jnp.float32)
        load = jnp.zeros((2,), jnp.float32)
        with jax.named_scope(MIXERS[self.kind]):
            h = self.norm.apply(params['norm'], x)
            if self.attn is not None:
                h = self.mixer.apply(params['mixer'], h, tables)
            else:
                h = self.mixer.apply(params['mixer'], h)
            if self.sparse:
                h, aux, load = h
            x = x + h
        return constrain(x, ('batch', 'seq', 'embed')), \
            (aux, load) if stats else aux


class Block(Module):
    """Pre-LN transformer block; MoE MLP when cfg.moe_experts > 0.

    ``kind`` is 'global' or 'window' (``cfg.layer_kinds()``): a window
    layer attends inside ``cfg.window`` keys each side, with its own
    rotary base. ``attn_norm=False`` makes the attention norm the
    identity (the first layer after an embedding norm).

    ``apply`` returns ``(x, aux)`` where aux is the router load-balance
    loss contribution (0.0 for dense blocks); with ``stats`` ``(x, (aux,
    stats))``, the expert layer's load beside it (``MoeMlp.apply``)."""

    def __init__(self, cfg, kind='global', attn_norm=True, dense=False):
        self.cfg = cfg
        windowed = kind == 'window'
        theta = None
        if cfg.positions == 'rotary':
            theta = cfg.window_rope_theta if windowed and \
                cfg.window_rope_theta is not None else cfg.rope_theta
        self.ln1 = _norm(cfg) if attn_norm else None
        if cfg.latent_rank:
            self.attn = LatentAttention(
                cfg.dim, cfg.n_heads, cfg.latent_rank, cfg.qk_nope_dim,
                cfg.qk_rope_dim, cfg.v_head_dim, causal=cfg.causal,
                dtype=cfg.dtype, rope_theta=theta, norm_eps=cfg.norm_eps,
                q_rank=cfg.latent_q_rank, rope_yarn=cfg.rope_yarn)
        else:
            self.attn = MultiHeadAttention(
                cfg.dim, cfg.n_heads, head_dim=cfg.head_dim,
                # (the block-diffusion mask holds the clean copy's
                # block-causal one)
                causal=cfg.causal and cfg.block_length is None,
                dtype=cfg.dtype, rope_theta=theta,
                window=cfg.window if windowed else None,
                num_kv_heads=cfg.n_kv_heads,
                rope_yarn=None if windowed else cfg.rope_yarn,
                qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                block_diffusion=cfg.block_length)
        self.ln2 = _norm(cfg)
        # (a leading dense layer of a stack of expert layers: `dense`)
        self.sparse = bool(cfg.moe_experts) and not dense
        self.mlp = _mlp(cfg, dense)
        # with residual streams: each sublayer's connection
        self.hc_attn = self.hc_mlp = None
        if cfg.hc_streams:
            self.hc_attn, self.hc_mlp = (
                HyperConnection(cfg.dim, cfg.hc_streams, cfg.hc_iters,
                                cfg.hc_clamp, cfg.hc_eps, cfg.dtype)
                for _ in range(2))

    def param_defs(self):
        d = {'attn': self.attn, 'ln2': self.ln2, 'mlp': self.mlp}
        if self.ln1 is not None:
            d['ln1'] = self.ln1
        if self.hc_attn is not None:
            d.update(hc_attn=self.hc_attn, hc_mlp=self.hc_mlp)
        return d

    @jax.named_scope('block')
    def apply(self, params, x, tables=None, stats=False):
        """``tables``: the attention's ``position_tables`` for ``x``,
        where the model made them once for its layers. With residual
        streams (``cfg.hc_streams``) ``x`` is ``[b, s, streams * dim]``
        and each sublayer reads and writes it through its connection
        (``hc_attn``, ``hc_mlp``: scope ``hc``, beside ``attention`` and
        ``mlp``); the stats then have a third number, the two connections'
        ``err`` summed."""
        streams = self.hc_attn is not None
        if not streams:
            x = self._attention(params, x, tables, residual=x)
        else:
            a, held = self.hc_attn.enter(params['hc_attn'], x)
            x, err = self.hc_attn.leave(
                x, self._attention(params, a, tables), held)
        # named so remat='save_attn' can keep it while recomputing the rest
        x = checkpoint_name(x, 'attn_out')
        if not streams:
            x, aux = self._mlp(params, x, stats, residual=x)
        else:
            h, held = self.hc_mlp.enter(params['hc_mlp'], x)
            h, aux = self._mlp(params, h, stats)
            x, err_mlp = self.hc_mlp.leave(x, h, held)
            if stats:
                aux = (aux[0], jnp.concatenate([aux[1],
                                                (err + err_mlp)[None]]))
        return constrain(x, ('batch', 'seq', 'embed')), aux

    @jax.named_scope('attention')
    def _attention(self, params, a, tables, residual=None):
        """The attention sublayer with its norm, added to ``residual``
        where that is given."""
        if self.ln1 is not None:
            a = self.ln1.apply(params['ln1'], a)
        a = self.attn.apply(params['attn'], a, tables)
        return a if residual is None else residual + a

    @jax.named_scope('mlp')
    def _mlp(self, params, h, stats, residual=None):
        """``(h, aux)`` of the MLP sublayer with its norm, as
        :meth:`apply` returns them."""
        h = self.mlp.apply(params['mlp'], self.ln2.apply(params['ln2'], h))
        aux = jnp.zeros((), jnp.float32)
        if self.sparse:
            h, aux, load = h
        elif stats:
            load = jnp.zeros((2,), jnp.float32)
        if stats:
            aux = (aux, load)
        return h if residual is None else residual + h, aux


class TransformerLM(Module):
    """Embedding -> N blocks -> final LN -> logits.

    With ``scan_layers`` the block params are stacked along a leading
    ``stage`` logical axis and the forward is a ``lax.scan`` — one
    compiled block regardless of depth, and the natural substrate for
    pipeline parallelism (the ``stage`` axis shards over ``pipe``).

    Layers of two kinds (``cfg.window``, ``cfg.global_every``) scan over
    PERIODS of the pattern: the parameters of a kind are stacked under
    ``blocks[kind]`` (``stage`` leading, in depth order) and one scan
    step runs a period's layers, each under the remat policy. The
    layers that fill no period come first, unrolled, under their own
    names ``block_000``...: ModernBERT's 28 are layer 0 (global, and
    the one whose attention norm is the identity) and 9 periods of
    (window, window, global). ``_layers`` has the arithmetic.

    ``remat=True`` runs a block again in the backward, all but what
    its checkpoint keeps by name (``_block_fn``, ``_saved_names``): the
    flash kernel's output and ``lse`` (``fa.CHECKPOINT_NAMES``) and, in
    a model with expert layers, the order of their rows
    (``models/moe.CHECKPOINT_NAMES``: integers, a few MB a layer), so
    that neither the kernel's forward call nor the sort that lays the
    rows out runs twice a step.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab, cfg.dim, dtype=cfg.dtype,
                               init_scale=cfg.embed_init_scale)
        # 'pos' is deliberately unmapped (replicated): in sequence-parallel
        # mode every shard looks up its own global positions locally.
        self.pos_embed = Embedding(cfg.max_len, cfg.dim,
                                   vocab_axis='pos', dtype=cfg.dtype)
        self.block = Block(cfg)
        self.ln_f = _norm(cfg)
        if not cfg.tied_embeddings:
            self.lm_head = Dense(cfg.dim, cfg.vocab, 'embed', 'vocab',
                                 use_bias=False, dtype=cfg.dtype)
        self.ln_embed = _norm(cfg)
        self.head = _HeadTransform(cfg)
        self._lead, self._period, self._periods = self._layers()
        kinds = cfg.layer_kinds()
        # the unrolled layers' blocks, by depth; the scanned ones by kind
        self._lead_blocks = [
            MixerLayer(cfg, cfg.mixers[i]) if cfg.mixers else
            Block(cfg, kinds[i], attn_norm=not (cfg.embed_norm and i == 0),
                  dense=i < cfg.dense_lead)
            for i in range(cfg.n_layers if not cfg.scan_layers
                           else self._lead)]
        self._kind_blocks = {kind: Block(cfg, kind)
                             for kind in sorted(set(self._period))} \
            if self.patterned else {}

    def _layers(self):
        """``(lead, period, periods)`` of the layer stack: the first
        ``lead`` layers run unrolled, then ``periods`` scan steps run
        the kinds ``period`` each. A stack of one kind whose first layer
        is like the others is one period a layer and no lead (the plain
        model); otherwise the lead is what fills no period, and a whole
        period where the first layer (no attention norm after an
        embedding norm) would else fall inside the scan."""
        cfg = self.cfg
        if cfg.mixers:
            # single-mixer layers follow a published string that has no
            # period: every layer unrolled, under its own name
            return cfg.n_layers, (), 0
        kinds = cfg.layer_kinds()
        size = cfg.global_every if cfg.window is not None else 1
        lead = cfg.n_layers % size
        if cfg.embed_norm and lead == 0:
            lead = min(size, cfg.n_layers)
        # leading dense layers of a stack of expert layers run unrolled
        # too, and whole periods with them
        while lead < cfg.dense_lead:
            lead += size
        return lead, tuple(kinds[lead:lead + size]), \
            (cfg.n_layers - lead) // size

    @property
    def patterned(self):
        """Whether the scanned stack is by kind (``blocks[kind]``) with
        unrolled lead layers, and not the plain ``blocks``."""
        return bool(self._lead) or len(self._period) > 1

    def param_defs(self):
        cfg = self.cfg
        d = {'embed': self.embed, 'ln_f': self.ln_f}
        if cfg.positions == 'learned':
            d['pos_embed'] = self.pos_embed
        if cfg.embed_norm:
            d['ln_embed'] = self.ln_embed
        if cfg.head_transform or cfg.decoder_bias:
            d['head'] = self.head
        if not cfg.tied_embeddings:
            d['lm_head'] = self.lm_head
        if not cfg.scan_layers:
            for i in range(cfg.n_layers):
                d['block_%03d' % i] = self._lead_blocks[i]
        elif not self.patterned:
            d['blocks'] = _Stacked(self.block, cfg.n_layers)
        else:
            for i in range(self._lead):
                d['block_%03d' % i] = self._lead_blocks[i]
            if not cfg.mixers:
                d['blocks'] = _Kinds({
                    kind: _Stacked(block,
                                   self._periods * self._period.count(kind))
                    for kind, block in self._kind_blocks.items()})
        return d

    def apply(self, params, tokens):
        return self.apply_with_aux(params, tokens)[0]

    def apply_with_aux(self, params, tokens):
        """Returns (logits, aux) where aux is the summed MoE router
        load-balance loss (0.0 for dense configs)."""
        x, aux_total = self.hidden_with_aux(params, tokens)
        with jax.named_scope('head_loss'):
            logits = self._head_logits(params, x)
            return constrain(logits.astype(jnp.float32),
                             ('batch', 'seq', 'vocab')), aux_total

    def _head_logits(self, params, x):
        """LM-head logits (model dtype) for hidden states of any
        leading shape (..., dim): the prediction head's transform where
        the configuration has one, the decoder, its bias."""
        cfg = self.cfg
        if cfg.head_transform:
            x = self.head.apply(params['head'], x)
        if cfg.tied_embeddings:
            logits = self.embed.attend(params['embed'], x)
        else:
            logits = self.lm_head.apply(params['lm_head'], x)
        if cfg.decoder_bias:
            logits = logits + params['head']['decoder_bias'].astype(
                logits.dtype)
        return logits

    @jax.named_scope('embed')
    def _embedded(self, params, tokens):
        """Embedding + positions (the pipeline prologue)."""
        _, s = tokens.shape
        x = self.embed.apply(params['embed'], tokens)
        if self.cfg.positions == 'learned':
            # global positions: offset by the manual seq-shard index when
            # the sequence axis runs inside shard_map (ring attention mode)
            seq_axis = manual_axis(AXIS_SEQUENCE)
            pos = jnp.arange(s)
            if seq_axis is not None:
                pos = pos + jax.lax.axis_index(seq_axis) * s
            x = x + self.pos_embed.apply(params['pos_embed'], pos)[None]
        if self.cfg.embed_norm:
            x = self.ln_embed.apply(params['ln_embed'], x)
        if self.cfg.hc_streams:
            x = self._entered(x)
        return constrain(x, ('batch', 'seq', 'embed'))

    @jax.named_scope('embed')
    def _entered(self, row):
        """The residual streams' entry: every stream the embedding
        ``row [b, s, dim]``."""
        return constrain(
            jnp.concatenate([row] * self.cfg.hc_streams, axis=-1),
            ('batch', 'seq', 'embed'))

    def _position_tables(self, x):
        """``tables(block)``: the rotary positions' ``(cos, sin)`` that
        ``block``'s attention takes for block input ``x [b, s, dim]``
        (``MultiHeadAttention.position_tables``; None where it takes
        none). Called once a trace, outside the layer scan and the
        blocks' checkpoints, it makes one pair for each rotary base, 4 MB
        a table at seq 8192, and the layers' functions close over
        theirs: inside a block the tables would be made, and under remat
        made again, in every layer."""
        b, s, _ = x.shape
        made = {}

        def tables(block):
            attn = block.attn
            if attn is None:             # a layer of another mixer
                return None
            shape = (b, attn.num_heads, s, attn.head_dim)
            kind = (attn.rope, attn.kernel_shape(shape))
            if kind not in made:
                made[kind] = attn.position_tables(shape)
            return made[kind]
        return tables

    def _block_fn(self, block=None, tables=None, stats=False, enters=False):
        """Single-block apply (``block``: the plain model's by default;
        ``tables``: its ``_position_tables``, which it closes over;
        ``stats``: the expert layers' load beside ``aux``) with the
        remat policy applied. ``enters``: the block takes the first of
        the residual streams and makes the streams from it
        (:meth:`_entered`) INSIDE its checkpoint: what the step keeps of
        this layer's input is then the row and not ``hc_streams`` copies
        of it (the connections' kernels take whole arrays, so XLA no
        longer makes the copies again by cloning a fusion: 0.16 GB in
        Xing4.0's cell, ``PERF.md`` section 6, PR 51).

        ``cfg.remat``: False (no remat), True (recompute the block in
        the backward, all but the flash kernel's forward call: the
        policy keeps what ``fa.flash_attention_merged`` names, its
        output and ``lse``, and what an expert layer names of the order
        of its rows, ``_saved_names``; a block on another attention
        path and without experts names nothing and is recomputed
        whole, as without a policy), or a
        named selective policy:
        'save_attn' (keep attention outputs), 'dots' (keep every
        matmul output — recompute only elementwise/norm work; the
        highest-memory selective tier), 'dots_no_batch' (keep only
        batch-free dot outputs — in a transformer block effectively
        full remat, kept for completeness).
        """
        cfg = self.cfg
        block_fn = (block or self.block).apply
        if tables is not None:
            block_fn = functools.partial(block_fn, tables=tables)
        if stats:
            block_fn = functools.partial(block_fn, stats=True)
        if enters:
            on_streams = block_fn

            def block_fn(layer_params, row):
                return on_streams(layer_params, self._entered(row))
        if isinstance(cfg.remat, str):
            policies = {
                'save_attn':
                    jax.checkpoint_policies.save_only_these_names(
                        'attn_out'),
                'dots': jax.checkpoint_policies.checkpoint_dots,
                'dots_no_batch':
                    jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable,
            }
            if cfg.remat not in policies:
                raise ValueError(
                    'unknown remat mode %r (expected False, True, or '
                    'one of %s)' % (cfg.remat, sorted(policies)))
            return jax.checkpoint(block_fn, policy=policies[cfg.remat])
        if cfg.remat:
            return jax.checkpoint(
                block_fn,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *self._saved_names()))
        return block_fn

    def _saved_names(self):
        """What ``remat=True`` keeps of a block: what the flash kernel's
        forward names and, with expert layers, what they name of the
        order of their rows (``models/moe.py``)."""
        if not self.cfg.moe_experts:
            return fa.CHECKPOINT_NAMES
        from autodist_tpu.models import moe
        return fa.CHECKPOINT_NAMES + moe.CHECKPOINT_NAMES

    def hidden_with_aux(self, params, tokens):
        """Final hidden states (post ln_f) and the MoE aux loss —
        everything except the lm-head, so losses can chunk the head.
        Under the block-diffusion objective ``tokens`` are the ``2 L``
        rows of each sequence (:meth:`_step_rows`) and the states are
        those of the ``L`` noised rows."""
        cfg = self.cfg
        pipe_axis = manual_axis(AXIS_PIPELINE)
        if cfg.hc_streams and manual_axis(AXIS_SEQUENCE) is not None:
            raise ValueError(
                'hc_streams=%d under sequence parallelism: the connections\' '
                'coefficients are made from whole rows of [b, s, streams * '
                'dim] and no sequence-parallel path has been run with them; '
                'use sp=1' % cfg.hc_streams)
        x = self._embedded(params, tokens)
        tables = self._position_tables(x)
        aux_total = jnp.zeros((), jnp.float32)
        # the expert layers' load (and the stream connections' err) rides
        # beside aux through the layer loops (not through the pipeline
        # schedules, whose aux is a scalar)
        stats = bool(cfg.moe_experts or cfg.hc_streams) \
            and pipe_axis is None
        if stats:
            aux_total = (aux_total, jnp.zeros(
                (3 if cfg.hc_streams else 2,), jnp.float32))
        self._note_layers()
        self._note_streams(x)
        self._note_remat(x)
        if pipe_axis is not None:
            self._check_pipelined()
            if cfg.block_length is not None:
                raise ValueError(
                    'the block-diffusion objective under pipeline '
                    'parallelism: the schedules score every row of a '
                    'microbatch against `targets`, and here the clean copy '
                    'is input and half the rows have no loss; use pp=1')
            from autodist_tpu.parallel.pipeline import gpipe, one_f_one_b
            pipe_fn = one_f_one_b \
                if ctx_option('pp_schedule', 'gpipe') == '1f1b' else gpipe
            # (a stage's layers make their own tables: the schedules
            # differentiate the block themselves, and see microbatches)
            x, aux_pipe = pipe_fn(self._block_fn(), params['blocks'], x,
                                  pipe_axis, ctx_option('microbatches', 1))
            aux_total = aux_total + aux_pipe
        elif cfg.scan_layers and not self.patterned:
            block_fn = self._block_fn(tables=tables(self.block), stats=stats)

            def body(carry, layer_params):
                h, aux = carry
                h, a = block_fn(layer_params, h)
                return (h, _add(aux, a)), None
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), params['blocks'])
        else:
            for i, block in enumerate(self._lead_blocks):
                # (at the entry every stream is the embedding row:
                # `_embedded`)
                enters = bool(cfg.hc_streams) and i == 0
                x, a = self._block_fn(block, tables(block), stats, enters)(
                    params['block_%03d' % i],
                    x[..., :cfg.dim] if enters else x)
                aux_total = _add(aux_total, a)
            if cfg.scan_layers and not cfg.mixers:
                x, aux_total = self._scan_periods(params['blocks'], x,
                                                  aux_total, tables, stats)
        if stats:
            aux_total, load = aux_total
            self._count_load(load)
        with jax.named_scope('head_loss'):
            if cfg.block_length is not None:
                x = x[:, :x.shape[1] // 2]          # the noised rows
            if cfg.hc_streams:
                # the residual streams' exit: their sum
                x = sum(split_streams(x, cfg.hc_streams)).astype(x.dtype)
            x = self.ln_f.apply(params['ln_f'], x)
        return x, aux_total

    def _step_rows(self, batch):
        """The ids the stack runs on: ``batch['tokens']``, or under the
        block-diffusion objective each sequence's noised copy followed
        by its clean one, ``[b, 2 L]``, with the step's counter
        ``bd_mask_rows``: the share of those rows that the noise
        replaced (whose id is the mask's)."""
        tokens = batch['tokens']
        if self.cfg.block_length is None:
            return tokens
        clean = batch['targets']
        if tokens.shape[1] % self.cfg.block_length:
            raise ValueError('a sequence of %d positions is no whole number '
                             'of blocks of %d' % (tokens.shape[1],
                                                  self.cfg.block_length))
        if not active_manual_axes():
            record_counter('bd_mask_rows', jnp.mean(
                (tokens != clean).astype(jnp.float32)) / 2)
        return jnp.concatenate([tokens, clean], axis=1)

    def _count_load(self, load):
        """The step's counters of the expert layers (``load``: rows held
        here and the largest load of a held expert, summed over the
        layers; with residual streams the connections' ``err`` behind
        them), for the trainer to read back with the loss
        (``core.record_counter``): the mean over the layers of the rows
        held here, of the largest and of the mean load of a held
        expert. Not inside a manual region, whose values cannot leave
        it this way."""
        if active_manual_axes():
            return
        cfg = self.cfg
        if cfg.hc_streams:
            # the connections' err (HyperConnection.leave), mean over
            # the layers' two sublayers
            record_counter('hc_res_col_sum_err',
                           load[2] / (2 * cfg.n_layers))
        if not cfg.moe_experts:
            return
        layers = self._expert_layers()
        rows, largest = load[0] / layers, load[1] / layers
        record_counter('moe_rows_here', rows)
        record_counter('moe_load_max', largest)
        record_counter('moe_load_mean', rows / cfg.held_experts()[1])

    def _expert_layers(self):
        cfg = self.cfg
        if not cfg.moe_experts:
            return 0
        if cfg.mixers:
            return cfg.mixers.count('E')
        return cfg.n_layers - cfg.dense_lead

    def _scan_periods(self, stacks, x, aux_total, tables, stats=False):
        """``periods`` scan steps over ``stacks[kind]``, each running one
        period's layers in order, each under the remat policy: a kind's
        stack ``[periods * c, ...]`` is seen as ``[periods, c, ...]``
        and the period's ``c`` layers of that kind index the second.
        ``tables``: ``_position_tables``."""
        fns = {kind: self._block_fn(block, tables(block), stats)
               for kind, block in self._kind_blocks.items()}
        per_period = {
            kind: jax.tree.map(
                lambda a, c=self._period.count(kind): a.reshape(
                    (self._periods, c) + a.shape[1:]), stack)
            for kind, stack in stacks.items()}

        def body(carry, period_params):
            h, aux = carry
            seen = dict.fromkeys(period_params, 0)
            for kind in self._period:
                layer = jax.tree.map(lambda a, i=seen[kind]: a[i],
                                     period_params[kind])
                seen[kind] += 1
                h, a = fns[kind](layer, h)
                aux = _add(aux, a)
            return (h, aux), None
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), per_period)
        return x, aux_total

    def _check_pipelined(self):
        cfg = self.cfg
        if cfg.hc_streams:
            raise ValueError(
                'hc_streams=%d under pipeline parallelism: the schedules '
                'carry [b, s, dim] between stages, one stream; use pp=1'
                % cfg.hc_streams)
        if not cfg.scan_layers:
            raise ValueError(
                'pipeline parallelism requires scan_layers=True '
                '(blocks must be stage-stacked to shard over pipe)')
        if self.patterned:
            raise ValueError(
                'pipeline parallelism needs layers of one kind: this '
                'stack is %d unrolled layer(s) and %d periods of %s, and '
                'stages are not cut across a layer pattern; use pp=1'
                % (self._lead, self._periods, '/'.join(self._period)))

    def _note_layers(self):
        """One ``transformer.layers`` point event a trace of a patterned
        stack: how it is run (docs/design/observability.md). The plain
        model, one scan step a layer, leaves none."""
        cfg = self.cfg
        if not self.patterned and cfg.block_length is None \
                and not cfg.hc_streams:
            return
        kinds = cfg.layer_kinds()
        single = {} if not cfg.mixers else dict(
            mixers=cfg.mixers, ssm_layers=cfg.mixers.count('M'),
            mlp_layers=cfg.mixers.count('E'))
        if cfg.block_length is not None:
            single = dict(objective='block_diffusion',
                          block_length=cfg.block_length, rows_per_token=2)
        if cfg.hc_streams:
            single['streams'] = cfg.hc_streams
        telemetry.get().loop_event(
            'transformer.layers', n_layers=len(kinds),
            period=len(self._period), periods=self._periods,
            remainder=self._lead, pattern='/'.join(self._period),
            scanned=bool(cfg.scan_layers) and not cfg.mixers,
            global_layers=cfg.mixers.count('*') if cfg.mixers
            else kinds.count('global'),
            window_layers=kinds.count('window'),
            dense_lead=cfg.dense_lead,
            expert_layers=self._expert_layers(), **single)

    def _note_streams(self, x):
        """One ``hc.plan`` point event a trace of a model with residual
        streams: the connection as it runs on the streams ``x [b, s, n
        dim]`` (``models/hyper_connections.py``): ``path`` the kernels'
        (``'pallas'``, with the rows a grid step holds, the rows a pass of
        its body computes and the calls' VMEM limit) or ``jax.numpy``'s
        (``'xla'``, the three ``None``)."""
        cfg = self.cfg
        if not cfg.hc_streams:
            return
        # (every connection of the stack is built alike)
        how = self.block.hc_attn.kernel_plan(x.shape, x.dtype)
        telemetry.get().loop_event(
            'hc.plan', streams=cfg.hc_streams, iters=cfg.hc_iters,
            clamp=list(cfg.hc_clamp), eps=cfg.hc_eps,
            layout='streams [b, s, n dim]; coefficients [n (n + 2), b, s]',
            path='pallas' if how else 'xla',
            block_rows=how.block_rows if how else None,
            sub_rows=how.sub_rows if how else None,
            vmem_limit_bytes=how.vmem_limit_bytes if how else None)

    def _note_remat(self, x):
        """One ``transformer.remat`` point event a trace under
        ``remat=True``: what the blocks' checkpoint keeps of block input
        ``x [b, s, dim]``. ``layers`` of the stack call the flash kernel
        here (``MultiHeadAttention.kernel_shape``) and keep
        ``saved_bytes_per_layer`` each on a device, for as long as the
        layer inputs live; 0 layers, on any other attention path, is
        the checkpoint without a policy. With residual streams ``x`` is
        ``[b, s, streams * dim]`` and the bytes count it: what a layer
        keeps then is mostly its input, ``streams`` times a plain
        block's."""
        cfg = self.cfg
        if cfg.remat is not True:
            return
        b, s, _ = x.shape
        if not cfg.scan_layers:
            blocks = self._lead_blocks
        elif self.patterned:
            blocks = self._lead_blocks + self._periods * [
                self._kind_blocks[kind] for kind in self._period]
        else:
            blocks = [self.block] * cfg.n_layers
        shapes = [block.attn.kernel_shape(
            (b, cfg.n_heads, s, block.attn.head_dim)) for block in blocks
            if block.attn is not None]
        kept = [fa.saved_bytes(shape, cfg.dtype, cfg.latent_rank
                               and cfg.v_head_dim)
                for shape in shapes if shape is not None]
        # (a device's rows: the kernels' batch where they run)
        rows = next((shape[0] for shape in shapes if shape is not None), b)
        carried = rows * s * x.shape[-1] * x.dtype.itemsize \
            if cfg.hc_streams else 0
        telemetry.get().loop_event(
            'transformer.remat', policy='save_only_these_names',
            saved=list(self._saved_names()), layers=len(kept),
            saved_bytes_per_layer=max(kept, default=0) + carried)

    def per_token_loss(self, params, batch):
        return self.per_token_loss_with_aux(params, batch)[0]

    @property
    def aux_loss_weight(self):
        return self.cfg.moe_aux_coef if self.cfg.moe_experts else 0.0

    def per_token_loss_with_aux(self, params, batch):
        """([batch, seq] token NLL, aux loss); expects {'tokens',
        'targets'}. Under the block-diffusion objective
        (``cfg.block_length``) ``tokens`` is the noised copy of
        ``targets``, both run through the stack (:meth:`_step_rows`),
        and the NLL is that of the noised rows at their own positions.

        Shape-preserving on purpose: in sequence-parallel mode this runs
        inside shard_map over local seq shards and the trainer reduces.
        Under SP, MoE routing groups are the local seq shards (GShard
        grouping), so capacity/dropping is per-shard."""
        targets = batch['targets']
        pipe_axis = manual_axis(AXIS_PIPELINE)
        if pipe_axis is not None and \
                ctx_option('pp_schedule', 'gpipe') == '1f1b':
            return self._loss_1f1b(params, batch, pipe_axis)
        x, aux = self.hidden_with_aux(params, self._step_rows(batch))
        b, s = targets.shape
        n = self._ce_chunks(s, b * s)
        with jax.named_scope('head_loss'):
            if n > 1:
                # Chunked CE: scan over sequence chunks; jax.checkpoint
                # means backward recomputes each chunk's logits instead
                # of saving an [b, s, vocab] residual. Chunking the SEQ
                # dim (not flattened rows) keeps the batch dim intact, so
                # DP sharding propagates through the reshape without
                # communication.
                d = x.shape[-1]
                xs = x.reshape(b, n, s // n, d).swapaxes(0, 1)
                ts = targets.reshape(b, n, s // n).swapaxes(0, 1)
                ckpt = jax.checkpoint(self._chunk_nll)
                _, nll = jax.lax.scan(
                    lambda c, inp: (c, ckpt(params, *inp)), None,
                    (xs, ts))
                nll = nll.swapaxes(0, 1).reshape(b, s)
            else:
                nll = self._chunk_nll(params, x, targets)
        return nll, aux

    def _loss_1f1b(self, params, batch, pipe_axis):
        """Pipelined loss via the FUSED 1F1B schedule: the embedding
        folds into the first stage (``head_fn``) and the lm-head + NLL
        into the last (``tail_fn``), so the pipeline's interface is
        token-sized — no full-batch ``[B, s, dim]`` activation stack,
        ``[B, s, vocab]`` logits slab, or input cotangent ever
        materializes, and the custom-vjp backward bounds each rank's
        live activations at ``2(pp-1)+1`` microbatches (true 1F1B
        working set, independent of the microbatch count).
        ``loss_chunk`` is subsumed — each microbatch IS a head chunk."""
        cfg = self.cfg
        self._check_pipelined()
        from autodist_tpu.parallel.pipeline import one_f_one_b

        def head(p, tok_mb):
            return self._embedded(p, tok_mb)

        @jax.named_scope('head_loss')
        def tail(p, h, tgt):
            h = self.ln_f.apply(p['ln_f'], h)
            return self._chunk_nll(p, h, tgt)

        # Pass ONLY the subtrees head/tail actually touch: the fused
        # backward carries + psums a zeros-like of these trees, so
        # handing it the full params dict would add two block-stack-
        # sized gradient buffers for nothing.
        head_params = {k: params[k]
                       for k in ('embed', 'pos_embed', 'ln_embed')
                       if k in params}
        tail_params = {
            k: params[k]
            for k in ('ln_f', 'head',
                      'embed' if cfg.tied_embeddings else 'lm_head')
            if k in params}
        return one_f_one_b(self._block_fn(), params['blocks'],
                           batch['tokens'], pipe_axis,
                           ctx_option('microbatches', 1),
                           tail_fn=tail, extra=batch['targets'],
                           tail_params=tail_params,
                           head_fn=head, head_params=head_params,
                           variant=ctx_option('pp_variant', 'auto'))

    def _chunk_nll(self, params, x, targets):
        logits = constrain(self._head_logits(params, x).astype(jnp.float32),
                           ('batch', 'seq', 'vocab'))
        logz = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction, not take_along_axis: partitions cleanly
        # when the vocab dim is tensor-sharded
        gold = jnp.sum(logits * jax.nn.one_hot(targets, logits.shape[-1],
                                               dtype=logits.dtype), axis=-1)
        return logz - gold

    def _ce_chunks(self, s, rows):
        """Number of sequence chunks for chunked CE: the largest chunk
        count that divides ``s`` while keeping >= loss_chunk rows per
        chunk (0 or rows <= loss_chunk -> 1 = unchunked)."""
        chunk = self.cfg.loss_chunk
        if not chunk or rows <= chunk:
            return 1
        n = max(1, min(s, rows // chunk))
        while s % n:
            n -= 1
        return n

    def loss(self, params, batch):
        """Mean token cross-entropy (+ MoE balance loss). With
        ``batch['mask']``, ``[batch, seq]``, the positions' WEIGHTS: 0 /
        1 to leave positions out, or any non-negative numbers (the
        block-diffusion step's ``1 / t`` on the masked positions); the
        loss is ``sum(w nll) / sum(w)`` (divided by the weights' sum, not
        the count of positions; by 1 where the sum is smaller)."""
        nll, aux = self.per_token_loss_with_aux(params, batch)
        mask = batch.get('mask')
        with jax.named_scope('head_loss'):
            if mask is not None:
                ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
            else:
                ce = jnp.mean(nll)
        return ce + self.cfg.moe_aux_coef * aux


class _Stacked(Module):
    """A module's params stacked n times along a leading 'stage' axis."""

    def __init__(self, inner, n):
        self.inner = inner
        self.n = n

    def init(self, rng):
        keys = jax.random.split(rng, self.n)
        return jax.vmap(self.inner.init)(keys)

    def axes(self):
        inner_axes = self.inner.axes()
        return jax.tree.map(
            lambda a: ('stage',) + tuple(a),
            inner_axes,
            is_leaf=lambda x: isinstance(x, tuple) and
            all(isinstance(v, (str, type(None))) for v in x))

    def param_defs(self):  # pragma: no cover - init/axes overridden
        return {'inner': self.inner}


class _Kinds(Module):
    """The scanned stacks of a patterned model, one per layer kind."""

    def __init__(self, stacks):
        self._stacks = stacks

    def param_defs(self):
        return self._stacks


class _HeadTransform(Module):
    """The prediction head before the decoder: ``norm(act(dense(x)))``
    (``cfg.head_transform``), and the decoder's bias
    (``cfg.decoder_bias``), which ``_head_logits`` adds."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.dense = Dense(cfg.dim, cfg.dim, 'embed', 'head_out',
                           use_bias=False, dtype=cfg.dtype)
        self.norm = _norm(cfg)
        self.act = _act(cfg)

    def param_defs(self):
        d = {}
        if self.cfg.head_transform:
            d.update(dense=self.dense, norm=self.norm)
        if self.cfg.decoder_bias:
            d['decoder_bias'] = ParamDef((self.cfg.vocab,), ('vocab',),
                                         'zeros')
        return d

    def apply(self, params, x):
        return self.norm.apply(params['norm'],
                               self.act(self.dense.apply(params['dense'],
                                                         x)))
