"""Transformer language model — the framework's flagship model family.

Covers the reference's BERT-large benchmark role (BASELINE.md: BERT-large
tokens/s) and the lm1b LSTM example's role as the language-model case,
built TPU-first: bfloat16 matmuls on the MXU, logical-axis sharding for
DP/TP/SP/EP, ring attention for long context, remat-friendly block
structure (scan-over-layers so XLA compiles one block).
"""
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.const import AXIS_PIPELINE, AXIS_SEQUENCE
from autodist_tpu.models.attention import MultiHeadAttention
from autodist_tpu.models.core import (Dense, Embedding, LayerNorm, Mlp,
                                      Module, ParamDef, constrain)
from autodist_tpu.parallel.axes import ctx_option, manual_axis


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
    # remat: False = none; True = checkpoint each block (recompute the
    # whole block in backward); 'save_attn' = checkpoint each block but
    # SAVE the post-attention residual, so backward recomputes only the
    # LN2+MLP half at one extra [b,s,d] save per layer. On v5e BERT
    # bench shapes the two are perf-equal (step time is dominated
    # elsewhere); 'save_attn' matters when attention is the expensive
    # recompute (long sequences without the flash kernel). Also
    # 'dots' (save every matmul output — recompute only elementwise
    # work; highest-memory selective tier, exceeds a 16 GB chip for
    # bert_large from batch 128) and 'dots_no_batch' (save only
    # batch-free dots — effectively full remat here). See _block_fn.
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
    moe_experts: int = 0         # >0: MoE MLP with this many experts
    moe_top_k: int = 2
    moe_aux_coef: float = 0.01   # load-balance loss weight

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


class Block(Module):
    """Pre-LN transformer block; MoE MLP when cfg.moe_experts > 0.

    ``apply`` returns ``(x, aux)`` where aux is the router load-balance
    loss contribution (0.0 for dense blocks)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.ln1 = LayerNorm(cfg.dim, dtype=cfg.dtype)
        self.attn = MultiHeadAttention(cfg.dim, cfg.n_heads,
                                       causal=cfg.causal, dtype=cfg.dtype)
        self.ln2 = LayerNorm(cfg.dim, dtype=cfg.dtype)
        if cfg.moe_experts:
            from autodist_tpu.models.moe import MoeMlp
            self.mlp = MoeMlp(cfg.dim, cfg.dim * cfg.mlp_ratio,
                              cfg.moe_experts, top_k=cfg.moe_top_k,
                              dtype=cfg.dtype)
        else:
            self.mlp = Mlp(cfg.dim, cfg.dim * cfg.mlp_ratio,
                           dtype=cfg.dtype)

    def param_defs(self):
        return {'ln1': self.ln1, 'attn': self.attn,
                'ln2': self.ln2, 'mlp': self.mlp}

    @jax.named_scope('block')
    def apply(self, params, x):
        with jax.named_scope('attention'):
            x = x + self.attn.apply(params['attn'],
                                    self.ln1.apply(params['ln1'], x))
        # named so remat='save_attn' can keep it while recomputing the rest
        x = checkpoint_name(x, 'attn_out')
        with jax.named_scope('mlp'):
            h = self.mlp.apply(params['mlp'],
                               self.ln2.apply(params['ln2'], x))
            aux = jnp.zeros((), jnp.float32)
            if self.cfg.moe_experts:
                h, aux = h
            x = x + h
        return constrain(x, ('batch', 'seq', 'embed')), aux


class TransformerLM(Module):
    """Embedding -> N blocks -> final LN -> logits.

    With ``scan_layers`` the block params are stacked along a leading
    ``stage`` logical axis and the forward is a ``lax.scan`` — one
    compiled block regardless of depth, and the natural substrate for
    pipeline parallelism (the ``stage`` axis shards over ``pipe``).
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab, cfg.dim, dtype=cfg.dtype)
        # 'pos' is deliberately unmapped (replicated): in sequence-parallel
        # mode every shard looks up its own global positions locally.
        self.pos_embed = Embedding(cfg.max_len, cfg.dim,
                                   vocab_axis='pos', dtype=cfg.dtype)
        self.block = Block(cfg)
        self.ln_f = LayerNorm(cfg.dim, dtype=cfg.dtype)
        if not cfg.tied_embeddings:
            self.lm_head = Dense(cfg.dim, cfg.vocab, 'embed', 'vocab',
                                 use_bias=False, dtype=cfg.dtype)

    def param_defs(self):
        d = {'embed': self.embed, 'pos_embed': self.pos_embed,
             'ln_f': self.ln_f}
        if not self.cfg.tied_embeddings:
            d['lm_head'] = self.lm_head
        if self.cfg.scan_layers:
            d['blocks'] = _Stacked(self.block, self.cfg.n_layers)
        else:
            for i in range(self.cfg.n_layers):
                d['block_%03d' % i] = self.block
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
        leading shape (..., dim)."""
        if self.cfg.tied_embeddings:
            return self.embed.attend(params['embed'], x)
        return self.lm_head.apply(params['lm_head'], x)

    @jax.named_scope('embed')
    def _embedded(self, params, tokens):
        """Embedding + positions (the pipeline prologue)."""
        _, s = tokens.shape
        x = self.embed.apply(params['embed'], tokens)
        # global positions: offset by the manual seq-shard index when the
        # sequence axis runs inside shard_map (ring attention mode)
        seq_axis = manual_axis(AXIS_SEQUENCE)
        pos = jnp.arange(s)
        if seq_axis is not None:
            pos = pos + jax.lax.axis_index(seq_axis) * s
        x = x + self.pos_embed.apply(params['pos_embed'], pos)[None]
        return constrain(x, ('batch', 'seq', 'embed'))

    def _block_fn(self):
        """Single-block apply with the remat policy applied.

        ``cfg.remat``: False (no remat), True (full — recompute the
        whole block in the backward), or a named selective policy:
        'save_attn' (keep attention outputs), 'dots' (keep every
        matmul output — recompute only elementwise/norm work; the
        highest-memory selective tier), 'dots_no_batch' (keep only
        batch-free dot outputs — in a transformer block effectively
        full remat, kept for completeness).
        """
        cfg = self.cfg
        block_fn = self.block.apply
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
            return jax.checkpoint(block_fn)
        return block_fn

    def hidden_with_aux(self, params, tokens):
        """Final hidden states (post ln_f) and the MoE aux loss —
        everything except the lm-head, so losses can chunk the head."""
        cfg = self.cfg
        x = self._embedded(params, tokens)
        block_fn = self._block_fn()
        aux_total = jnp.zeros((), jnp.float32)
        pipe_axis = manual_axis(AXIS_PIPELINE)
        if pipe_axis is not None:
            if not cfg.scan_layers:
                raise ValueError(
                    'pipeline parallelism requires scan_layers=True '
                    '(blocks must be stage-stacked to shard over pipe)')
            from autodist_tpu.parallel.pipeline import gpipe, one_f_one_b
            pipe_fn = one_f_one_b \
                if ctx_option('pp_schedule', 'gpipe') == '1f1b' else gpipe
            x, aux_pipe = pipe_fn(block_fn, params['blocks'], x, pipe_axis,
                                  ctx_option('microbatches', 1))
            aux_total = aux_total + aux_pipe
        elif cfg.scan_layers:
            def body(carry, layer_params):
                h, aux = carry
                h, a = block_fn(layer_params, h)
                return (h, aux + a), None
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), params['blocks'])
        else:
            for i in range(cfg.n_layers):
                x, a = block_fn(params['block_%03d' % i], x)
                aux_total = aux_total + a
        with jax.named_scope('head_loss'):
            x = self.ln_f.apply(params['ln_f'], x)
        return x, aux_total

    def per_token_loss(self, params, batch):
        return self.per_token_loss_with_aux(params, batch)[0]

    @property
    def aux_loss_weight(self):
        return self.cfg.moe_aux_coef if self.cfg.moe_experts else 0.0

    def per_token_loss_with_aux(self, params, batch):
        """([batch, seq] token NLL, aux loss); expects {'tokens',
        'targets'}.

        Shape-preserving on purpose: in sequence-parallel mode this runs
        inside shard_map over local seq shards and the trainer reduces.
        Under SP, MoE routing groups are the local seq shards (GShard
        grouping), so capacity/dropping is per-shard."""
        targets = batch['targets']
        pipe_axis = manual_axis(AXIS_PIPELINE)
        if pipe_axis is not None and \
                ctx_option('pp_schedule', 'gpipe') == '1f1b' and \
                ctx_option('pp_variant', 'auto') != 'legacy':
            return self._loss_1f1b(params, batch, pipe_axis)
        x, aux = self.hidden_with_aux(params, batch['tokens'])
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
        if not cfg.scan_layers:
            raise ValueError(
                'pipeline parallelism requires scan_layers=True '
                '(blocks must be stage-stacked to shard over pipe)')
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
        head_params = {k: params[k] for k in ('embed', 'pos_embed')}
        tail_params = {
            k: params[k]
            for k in ('ln_f',
                      'embed' if cfg.tied_embeddings else 'lm_head')}
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
        """Mean token cross-entropy (+ MoE balance loss), optional mask."""
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
