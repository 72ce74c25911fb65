"""Functional-path tests: Trainer + model zoo + parallel modes.

The key invariant (reference c0's spirit, cases/c0.py:92-120): every
parallel lowering of the same model/optimizer/batch must produce the
same numbers — here checked across DP/TP/SP/FSDP meshes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from autodist_tpu.api import Trainer
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec
from autodist_tpu.parallel.ring_attention import (local_flash_attention,
                                                  ring_attention)


@pytest.fixture(scope='module')
def tiny_lm():
    cfg = TransformerConfig.tiny(dtype=jnp.float32)
    return TransformerLM(cfg)


@pytest.fixture(scope='module')
def batch():
    rng = np.random.RandomState(0)
    return {'tokens': rng.randint(0, 256, (8, 32)),
            'targets': rng.randint(0, 256, (8, 32))}


def run_losses(model, spec, batch, steps=2):
    tr = Trainer(model, optax.adam(1e-2), spec=spec)
    state = tr.init(jax.random.PRNGKey(0))
    out = []
    for _ in range(steps):
        state, m = tr.step(state, batch)
        out.append(float(m['loss']))
    return out


@pytest.fixture(scope='module')
def dp_losses(tiny_lm, batch):
    return run_losses(tiny_lm, ParallelSpec(), batch)


@pytest.mark.parametrize('spec_kw', [
    dict(tp=2),
    dict(tp=2, sp=2),
    dict(sp=8, dp=1),
    dict(sp=4, dp=2, sp_mode='ulysses'),
    dict(tp=2, sp=2, sp_mode='ulysses'),
    dict(zero=2),
    dict(zero=3),
    dict(tp=4, dp=2),
], ids=lambda d: '_'.join('%s%s' % kv for kv in d.items()))
def test_parallel_modes_match_dp(tiny_lm, batch, dp_losses, spec_kw):
    losses = run_losses(tiny_lm, ParallelSpec(**spec_kw), batch)
    assert np.allclose(losses, dp_losses, atol=2e-4), \
        (losses, dp_losses)


def test_loss_decreases(tiny_lm, batch, dp_losses):
    assert dp_losses[-1] < dp_losses[0]


def test_pipeline_parallel_matches_dp(batch):
    """GPipe over pipe=2 (with tp=2) reproduces the DP numbers exactly."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=4)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch)
    pp = run_losses(model, ParallelSpec(pp=2, tp=2, microbatches=4),
                    batch)
    assert np.allclose(pp, base, atol=2e-4), (pp, base)


@pytest.mark.parametrize('variant', ['remat', 'stash'])
def test_pipeline_1f1b_matches_dp(batch, variant):
    """The 1F1B schedule (per-rank microbatch residency) is numerically
    identical to DP, like GPipe — in both backward variants (remat:
    chain re-forward, pp-bounded stash; stash: saved boundary
    activations, no chain re-forward)."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=4)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch)
    f1b = run_losses(model, ParallelSpec(pp=2, tp=2, microbatches=4,
                                         pp_schedule='1f1b',
                                         pp_variant=variant), batch)
    assert np.allclose(f1b, base, atol=2e-4), (f1b, base)


@pytest.mark.parametrize('variant', ['remat', 'stash'])
def test_pipeline_1f1b_ragged_microbatches(batch, variant):
    """M % pp may be ragged — even M < pp (round-4: residency slots are
    padded and masked, lifting the round-3 M %% pp == 0 restriction):
    parity with DP holds at M=2, pp=4, in both backward variants."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=4)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch, steps=2)
    f1b = run_losses(model, ParallelSpec(pp=4, microbatches=2,
                                         pp_schedule='1f1b',
                                         pp_variant=variant), batch,
                     steps=2)
    assert np.allclose(f1b, base, atol=2e-4), (f1b, base)


@pytest.mark.parametrize('variant', ['remat', 'stash'])
def test_fused_1f1b_direct_no_head(variant):
    """Direct pipeline API, fused mode WITHOUT a head (float x enters
    the pipe, loss folded in the tail): gradients for blocks, tail
    params, and x itself match the single-stage (pp=1) reference —
    EXACT cotangent scaling, in both backward variants (an e2e loss
    parity test once missed a 1/pp block-grad bug this catches)."""
    from autodist_tpu.parallel.pipeline import one_f_one_b

    pp, M, mb, dim = 2, 4, 2, 8
    B = M * mb
    rng = np.random.RandomState(0)
    sp = {'w': jnp.asarray(rng.randn(pp, 2, dim, dim).astype('f4') / 4)}
    tp = {'out': jnp.asarray(rng.randn(dim).astype('f4'))}
    x = jnp.asarray(rng.randn(B, dim).astype('f4'))
    tgt = jnp.asarray(rng.randint(0, 2, (B, 1)).astype(np.int32))

    def block_fn(p, h):
        return jnp.tanh(h @ p), jnp.zeros((), jnp.float32)

    def tail_fn(tpp, h, e):
        # per-mb scalar-ish output with leading mb dim
        return (h @ tpp['out'])[:, None] * (1.0 + e.astype(h.dtype))

    def run(n_stages):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:n_stages]), ('pipe',))

        def loss(sp_, tp_, x_):
            def inner(sp__, tp__, x__, tgt_):
                # local shard of the stage-stacked params: [1, L, ...]
                out, _ = one_f_one_b(
                    block_fn, sp__['w'][0], x__, 'pipe', M,
                    tail_fn=tail_fn, extra=tgt_, tail_params=tp__,
                    variant=variant)
                return out

            mapped = jax.shard_map(
                inner, mesh=mesh,
                in_specs=({'w': P('pipe')}, P(), P(), P()),
                out_specs=P(), axis_names={'pipe'}, check_vma=False)
            # reduce OUTSIDE the region (replicated-out cotangent is
            # then unambiguous)
            return jnp.sum(mapped(sp_, tp_, x_, tgt)
                           .astype(jnp.float32) ** 2)

        # under jit like every real caller (eager shard_map transpose
        # uses a different unreduced-cotangent convention)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
            sp, tp, x)

    # pp=1 reference path via plain composition
    def ref_loss(sp_, tp_, x_):
        h = x_
        for s in range(pp):
            for l in range(2):
                h, _ = block_fn(sp_['w'][s, l], h)
        out = tail_fn(tp_, h, tgt)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    ref_val, ref_g = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        sp, tp, x)
    val, g = run(pp)
    assert np.isclose(float(val), float(ref_val), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_pp_variant_legacy_is_refused(batch):
    """The autodiff-through-the-schedule variant is gone (PR 44): the
    spec's value reaches the schedule's own check, which names the
    three that exist."""
    model = TransformerLM(TransformerConfig.tiny(dtype=jnp.float32,
                                                 n_layers=4))
    tr = Trainer(model, optax.adam(1e-2), spec=ParallelSpec(
        pp=2, microbatches=4, pp_schedule='1f1b', pp_variant='legacy'))
    state = tr.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="'legacy'.*'auto', 'remat', "
                                         "'stash'"):
        tr.step(state, batch)


def test_1f1b_closure_style_tail_is_refused():
    """A ``tail_fn(h, extra)`` that closes over its parameters would
    lose their gradients in the hand-written backward: refused at
    pp 2, where it used to select the autodiff schedule."""
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.parallel.pipeline import one_f_one_b
    mesh = Mesh(np.array(jax.devices()[:2]), ('pipe',))
    w = jnp.zeros((2, 1, 8, 8), jnp.float32)
    out = jnp.ones((8,), jnp.float32)

    def inner(w_, x_):
        return one_f_one_b(
            lambda p, h: (jnp.tanh(h @ p), jnp.zeros((), jnp.float32)),
            w_[0], x_, 'pipe', 2, tail_fn=lambda h, e: h @ out)[0]

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=(P('pipe'), P()),
                           out_specs=P(), axis_names={'pipe'},
                           check_vma=False)
    with pytest.raises(ValueError, match='tail_params'):
        jax.eval_shape(mapped, w, jnp.zeros((4, 8), jnp.float32))


def test_pipeline_1f1b_reduces_peak_memory():
    """The point of 1F1B: the custom-vjp backward interleaves
    recompute-forwards and backwards with a 2(pp-1)+1-slot circular
    stash, so live activations are bounded by the PIPE DEPTH — while
    GPipe's autodiff-of-scan holds all M+pp-1 microbatch residuals at
    the fwd/bwd boundary (plus the full-batch logits slab the folded
    tail eliminates). At pp=4, M=16 the compiled step's temp memory
    must come in at less than HALF of GPipe's (round-2 target; the
    round-3 masked-psum approximation managed only ~13%)."""
    import dataclasses

    import optax as _optax

    from autodist_tpu.api import Trainer
    cfg = dataclasses.replace(
        TransformerConfig.tiny(dtype=jnp.float32, n_layers=4,
                               max_len=128), vocab=4096)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    big = {'tokens': rng.randint(0, 4096, (32, 128)),
           'targets': rng.randint(0, 4096, (32, 128))}

    def temp_bytes(schedule, microbatches, variant='remat'):
        tr = Trainer(model, _optax.sgd(0.1),
                     spec=ParallelSpec(pp=4, dp=1,
                                       microbatches=microbatches,
                                       pp_schedule=schedule,
                                       pp_variant=variant))
        state = tr.init(jax.random.PRNGKey(0))
        compiled = tr.compile_step(state, big)
        return compiled.memory_analysis().temp_size_in_bytes

    gpipe_bytes = temp_bytes('gpipe', 16)
    f1b_bytes = temp_bytes('1f1b', 16)
    assert f1b_bytes < 0.5 * gpipe_bytes, (f1b_bytes, gpipe_bytes)
    # the 1F1B bound is set by pp, not M: doubling the microbatch
    # count must not grow the working set materially (>15%)
    f1b_m8 = temp_bytes('1f1b', 8)
    assert f1b_bytes < 1.15 * f1b_m8, (f1b_bytes, f1b_m8)
    # the stash variant trades that M-independence for fewer recompute
    # passes: still well under GPipe (one boundary activation per
    # microbatch vs GPipe's per-layer residual stacks)
    stash_bytes = temp_bytes('1f1b', 16, variant='stash')
    assert stash_bytes < gpipe_bytes, (stash_bytes, gpipe_bytes)


def test_moe_aux_loss_kept_under_pipelining(batch):
    """The MoE router balance loss survives GPipe: with microbatches=1
    the pipelined loss (incl. aux) matches the DP loss exactly; a
    zero-aux model would differ by moe_aux_coef * aux."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2,
                                 moe_experts=4, moe_aux_coef=1.0)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch)
    pp = run_losses(model, ParallelSpec(pp=2, microbatches=1), batch)
    assert np.allclose(pp, base, atol=3e-4), (pp, base)


@pytest.mark.parametrize('variant', ['remat', 'stash'])
def test_moe_aux_loss_through_fused_1f1b(batch, variant):
    """The aux cotangent path through BOTH fused-1F1B backwards: with a
    nonzero router balance loss, multi-step training (losses depend on
    step-1 gradients, incl. the aux term's router gradients) matches DP
    — a dropped validity mask double-counting bubble-step aux grads
    would break the second step."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2,
                                 moe_experts=4, moe_aux_coef=1.0)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch)
    # microbatches=1: per-microbatch routing groups coincide with the
    # full-batch statistic only there (GShard grouping, see gpipe doc)
    f1b = run_losses(model, ParallelSpec(pp=2, microbatches=1,
                                         pp_schedule='1f1b',
                                         pp_variant=variant), batch)
    assert np.allclose(f1b, base, atol=3e-4), (f1b, base)
    # the aux term is genuinely nonzero (the parity above is meaningful)
    params = model.init(jax.random.PRNGKey(0))
    _, aux = model.per_token_loss_with_aux(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    assert float(aux) > 1e-4


def test_moe_expert_parallel_matches_dp(batch):
    """MoE routing/capacity math is sharding-invariant over ep/tp."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2,
                                 moe_experts=4)
    model = TransformerLM(cfg)
    base = run_losses(model, ParallelSpec(), batch)
    ep = run_losses(model, ParallelSpec(ep=2, tp=2), batch)
    assert np.allclose(ep, base, atol=3e-4), (ep, base)
    assert base[-1] < base[0]


@pytest.mark.parametrize('causal', [True, False])
def test_ring_attention_matches_dense(causal):
    from jax.sharding import Mesh, PartitionSpec as P
    B, H, S, D = 2, 4, 64, 16
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype('f4'))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ('seq',))
    ref = local_flash_attention(q, k, v, causal=causal)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, 'seq', causal=causal),
        mesh=mesh, in_specs=(P(None, None, 'seq'),) * 3,
        out_specs=P(None, None, 'seq')))
    err = float(jnp.max(jnp.abs(f(q, k, v) - ref)))
    assert err < 1e-5, err


@pytest.mark.parametrize('causal', [True, False])
def test_ulysses_attention_matches_dense(causal):
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.parallel.ulysses import ulysses_attention
    B, H, S, D = 2, 4, 64, 16
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype('f4'))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('seq',))
    ref = local_flash_attention(q, k, v, causal=causal)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, 'seq', causal=causal),
        mesh=mesh, in_specs=(P(None, None, 'seq'),) * 3,
        out_specs=P(None, None, 'seq')))
    err = float(jnp.max(jnp.abs(f(q, k, v) - ref)))
    assert err < 1e-5, err


def test_ulysses_attention_grads_match_dense():
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.parallel.ulysses import ulysses_attention
    B, H, S, D = 1, 4, 32, 8
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype('f4'))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('seq',))

    def loss_ulysses(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, 'seq',
                                              causal=True),
            mesh=mesh, in_specs=(P(None, None, 'seq'),) * 3,
            out_specs=P(None, None, 'seq'))
        return jnp.sum(jnp.square(f(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            local_flash_attention(q, k, v, causal=True)))

    g1 = jax.jit(jax.grad(loss_ulysses, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_ulysses_rejects_indivisible_heads():
    from jax.sharding import Mesh, PartitionSpec as P

    from autodist_tpu.parallel.ulysses import ulysses_attention
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 3, 32, 8).astype('f4'))  # 3 heads, sp=4
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('seq',))
    f = jax.shard_map(
        lambda q: ulysses_attention(q, q, q, 'seq'),
        mesh=mesh, in_specs=(P(None, None, 'seq'),),
        out_specs=P(None, None, 'seq'))
    with pytest.raises(ValueError, match='heads'):
        jax.jit(f)(q)


def test_ring_attention_grads_match_dense():
    from jax.sharding import Mesh, PartitionSpec as P
    B, H, S, D = 1, 2, 32, 8
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype('f4'))
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ('seq',))

    def loss_ring(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, 'seq', causal=True),
            mesh=mesh, in_specs=(P(None, None, 'seq'),) * 3,
            out_specs=P(None, None, 'seq'))
        return jnp.sum(jnp.square(f(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            local_flash_attention(q, k, v, causal=True)))

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_grad_accum_matches_single_pass(tiny_lm, batch, dp_losses):
    """Mean-of-chunk-means == single-pass mean for equal chunks, so
    grad_accum must reproduce the plain DP numbers exactly (at 1/k the
    activation memory)."""
    losses = run_losses(tiny_lm, ParallelSpec(grad_accum=4), batch)
    assert np.allclose(losses, dp_losses, atol=2e-4), (losses, dp_losses)


def test_grad_accum_rejects_indivisible_batch(tiny_lm, batch):
    tr = Trainer(tiny_lm, optax.adam(1e-2),
                 spec=ParallelSpec(grad_accum=3))
    state = tr.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match='grad_accum'):
        tr.step(state, batch)   # batch dim 8 % 3 != 0


def test_fit_and_evaluate(tiny_lm, batch):
    """c7 role: Model.fit/evaluate over an iterable of batches."""
    tr = Trainer(tiny_lm, optax.adam(1e-2), spec=ParallelSpec())
    state = tr.init(jax.random.PRNGKey(0))
    data = [batch] * 5
    state, hist = tr.fit(state, data, eval_data=[batch], eval_every=2)
    assert len(hist['loss']) == 5
    assert hist['loss'][-1] < hist['loss'][0]
    # eval at steps 2, 4 and the final partial interval (5)
    assert [s for s, _ in hist['eval_loss']] == [2, 4, 5]
    # eval loss is the loss of the CURRENT params (lower than step-1 train)
    assert hist['eval_loss'][-1][1] < hist['loss'][0]
    # steps= caps the iterator
    state, hist2 = tr.fit(state, iter(data), steps=2)
    assert len(hist2['loss']) == 2
    # evaluate with custom metrics returns a dict of means
    def acc(params, b):
        logits = tiny_lm.apply(params, jnp.asarray(b['tokens']))
        hit = jnp.argmax(logits, -1) == jnp.asarray(b['targets'])
        return {'accuracy': jnp.mean(hit.astype(jnp.float32))}
    out = tr.evaluate(state, [batch], metrics_fn=acc)
    assert set(out) == {'loss', 'accuracy'} and 0 <= out['accuracy'] <= 1

    def always_one(params, b):
        return {'one': jnp.ones(())}
    # a different metrics_fn on the same batch signature must not reuse
    # the previous compiled evaluator
    out2 = tr.evaluate(state, [batch], metrics_fn=always_one)
    assert set(out2) == {'loss', 'one'} and out2['one'] == 1.0


def test_trainer_get_params_logical_layout(tiny_lm, batch):
    tr = Trainer(tiny_lm, optax.sgd(0.1), spec=ParallelSpec(tp=2))
    state = tr.init(jax.random.PRNGKey(0))
    host = tr.get_params(state)
    # logical (unsharded) shapes on host
    assert host['embed']['table'].shape == (256, 64)
    assert host['blocks']['mlp']['up']['kernel'].shape[0] == 2  # stacked


def test_scan_vs_unrolled_layers(batch):
    cfg_s = TransformerConfig.tiny(dtype=jnp.float32, scan_layers=True)
    cfg_u = TransformerConfig.tiny(dtype=jnp.float32, scan_layers=False)
    m_s, m_u = TransformerLM(cfg_s), TransformerLM(cfg_u)
    ps = m_s.init(jax.random.PRNGKey(0))
    # copy stacked params into the unrolled layout
    pu = m_u.init(jax.random.PRNGKey(0))
    for i in range(cfg_u.n_layers):
        pu['block_%03d' % i] = jax.tree.map(lambda x, i=i: x[i],
                                            ps['blocks'])
    for k in ('embed', 'pos_embed', 'ln_f'):
        pu[k] = ps[k]
    l_s = float(m_s.loss(ps, {k: jnp.asarray(v) for k, v in batch.items()}))
    l_u = float(m_u.loss(pu, {k: jnp.asarray(v) for k, v in batch.items()}))
    assert np.allclose(l_s, l_u, atol=1e-5)


def test_trainer_profile_writes_trace_and_preserves_state(tmp_path):
    """Trainer.profile captures a trace without consuming the caller's
    state (the compiled step donates; profile must run on a copy)."""
    import glob
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 64, (4, 8), dtype=np.int32),
             'targets': rng.randint(0, 64, (4, 8), dtype=np.int32)}
    cfg = TransformerConfig.tiny(dtype=jnp.float32, vocab=64, max_len=8)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=2))
    state = tr.init(jax.random.PRNGKey(0))
    out = tr.profile(state, batch, str(tmp_path / 'tr'), steps=2)
    assert glob.glob(out + '/**/*.pb*', recursive=True) or \
        glob.glob(out + '/**/*.json*', recursive=True), \
        'no trace artifacts written'
    # caller's state survived donation and still steps
    state2, m = tr.step(state, batch)
    assert np.isfinite(float(m['loss']))
