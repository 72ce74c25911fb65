"""Layers of two kinds in one ``TransformerLM`` (PR 26): the scan over
periods against the unrolled stack, the parallel layouts that take a
window and a layer pattern and those that refuse them, the XLA band
path, rotary positions and the gated MLP's sharding."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.api import Trainer
from autodist_tpu.models.attention import rotary
from autodist_tpu.models.core import GatedMlp
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec
from autodist_tpu.parallel.ring_attention import (_band_block,
                                                  local_flash_attention)


def tiny(**kw):
    """ModernBERT's structure at a size the CPU runs in a second: 7
    layers = layer 0 + 2 periods of (window, window, global), 4 heads of
    16, a window of 8 keys each side, seq 64."""
    d = dict(vocab=256, dim=64, n_layers=7, n_heads=4, max_len=64,
             window=8, mlp_dim=96, dtype=jnp.float32, remat=True)
    d.update(kw)
    return TransformerConfig.modernbert_large(**d)


def batch(n=4, seq=64, seed=0):
    rng = np.random.RandomState(seed)
    return {'tokens': rng.randint(0, 256, (n, seq), dtype=np.int32),
            'targets': rng.randint(0, 256, (n, seq), dtype=np.int32)}


def unrolled_from_scanned(model, params):
    """The scanned tree's layers under the unrolled model's names."""
    kinds = model.cfg.layer_kinds()
    out = {k: v for k, v in params.items() if k != 'blocks'}
    seen = dict.fromkeys(params['blocks'], 0)
    for i in range(model._lead, len(kinds)):
        row = seen[kinds[i]]
        out['block_%03d' % i] = jax.tree.map(lambda a, r=row: a[r],
                                             params['blocks'][kinds[i]])
        seen[kinds[i]] += 1
    return out


@pytest.mark.parametrize('n_layers,want', [
    (28, (1, ('window', 'window', 'global'), 9)),   # ModernBERT-large
    (22, (1, ('window', 'window', 'global'), 7)),   # ModernBERT-base
    (7, (1, ('window', 'window', 'global'), 2)),
    (8, (2, ('window', 'global', 'window'), 2)),
    (6, (3, ('global', 'window', 'window'), 1)),    # layer 0 kept out
])
def test_layer_arithmetic(n_layers, want):
    model = TransformerLM(tiny(n_layers=n_layers))
    assert model._layers() == want and model.patterned
    kinds = model.cfg.layer_kinds()
    lead, period, periods = want
    assert kinds[:lead] + list(period) * periods == kinds
    assert kinds.count('global') == len(range(0, n_layers, 3))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    for kind in set(period):
        assert shapes['blocks'][kind]['ln2']['scale'].shape[0] == \
            periods * period.count(kind)
    assert 'ln1' not in shapes['block_000'] and 'pos_embed' not in shapes
    assert model.axes()['blocks']['window']['mlp']['up']['kernel'] == (
        'stage', 'embed', None, 'mlp')


def test_plain_models_keep_their_tree_and_stack():
    """BERT and GPT-2: one kind, no lead, ``blocks`` stacked directly."""
    for cfg in (TransformerConfig.tiny(), TransformerConfig.tiny(
            causal=False)):
        model = TransformerLM(cfg)
        assert model._layers() == (0, ('global',), cfg.n_layers)
        assert not model.patterned
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert sorted(shapes) == ['blocks', 'embed', 'ln_f', 'pos_embed']
        assert sorted(shapes['blocks']) == ['attn', 'ln1', 'ln2', 'mlp']
        assert sorted(shapes['blocks']['ln1']) == ['bias', 'scale']


@pytest.mark.parametrize('loss_chunk', [0, 64])
def test_scan_over_periods_equals_the_unrolled_stack(loss_chunk):
    scanned = TransformerLM(tiny(loss_chunk=loss_chunk))
    plain = TransformerLM(tiny(scan_layers=False, remat=False))
    params = scanned.init(jax.random.PRNGKey(0))
    t_before = time.perf_counter()
    got, got_g = jax.value_and_grad(scanned.loss)(params, batch())
    want, want_g = jax.value_and_grad(plain.loss)(
        unrolled_from_scanned(scanned, params), batch())
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    got_g = unrolled_from_scanned(scanned, got_g)
    assert jax.tree.structure(got_g) == jax.tree.structure(want_g)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=1e-4)
    events = [r['tags'] for r in telemetry.get().loop_records()
              if r['t0'] >= t_before and r['name'] == 'transformer.layers']
    assert events[0] == dict(
        n_layers=7, period=3, periods=2, remainder=1,
        pattern='window/window/global', scanned=True, global_layers=3,
        window_layers=4, dense_lead=0, expert_layers=0)


def test_dp2_tp2_equals_one_device():
    """The window, the rotary bases and the gated MLP's halves under
    GSPMD: a dp=2 x tp=2 step moves the parameters as a dp=1 step."""
    model = TransformerLM(tiny())
    after = {}
    for name, spec in (('one', ParallelSpec(dp=1)),
                       ('mesh', ParallelSpec(dp=2, tp=2))):
        tr = Trainer(model, optax.sgd(0.1), spec=spec)
        state = tr.init(jax.random.PRNGKey(0))
        if name == 'mesh':
            up = state.params['blocks']['window']['mlp']['up']['kernel']
            # tp cuts inside each half: a shard holds half of input AND gate
            assert {s.data.shape for s in up.addressable_shards} == {
                (4, 64, 2, 48)}
        state, metrics = tr.step(state, batch())
        after[name] = (float(metrics['loss']),
                       jax.tree.map(np.asarray, state.params))
    np.testing.assert_allclose(after['mesh'][0], after['one'][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(after['mesh'][1]),
                    jax.tree.leaves(after['one'][1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


@pytest.mark.parametrize('spec_kw', [dict(dp=1), dict(dp=2),
                                     dict(dp=2, tp=2)],
                         ids=['one_device', 'dp2', 'dp2_tp2'])
def test_rotary_inside_the_kernels_is_rotary_outside_them(monkeypatch,
                                                          spec_kw):
    """PR 32: where the flash kernels run, q and k are rotated on the
    tile from ``cos`` / ``sin`` tables made once a step; everywhere else
    by ``rotary()``. One step of a patterned model with the kernels on
    every layer (on one device, on dp shards, and on (batch, head)
    shards, which see the whole tables and q, k, v apart) moves the
    parameters as the step that runs XLA's attention does."""
    from autodist_tpu.kernels import flash_attention as fa

    model = TransformerLM(tiny(n_layers=4))
    after = {}
    for crossover in (16, 10 ** 9):
        monkeypatch.setattr(fa, 'MIN_KERNEL_SEQ', crossover)
        tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(**spec_kw))
        state = tr.init(jax.random.PRNGKey(0))
        t_before = time.perf_counter()
        state, metrics = tr.step(state, batch())
        plans = [r['tags']['rotary']
                 for r in telemetry.get().loop_records()
                 if r['t0'] >= t_before and r['name'] == 'flash.plan']
        assert (plans and all(plans)) if crossover == 16 else not plans
        after[crossover] = (float(metrics['loss']),
                            jax.tree.map(np.asarray, state.params))
    np.testing.assert_allclose(after[16][0], after[10 ** 9][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(after[16][1]),
                    jax.tree.leaves(after[10 ** 9][1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


@pytest.mark.parametrize('spec_kw,complaint', [
    (dict(dp=1, sp=2), 'window .* under sequence parallelism'),
    (dict(dp=1, sp=2, sp_mode='ulysses'),
     'window .* under sequence parallelism'),
    (dict(dp=1, pp=2), 'pipeline parallelism needs layers of one kind'),
    (dict(dp=1, pp=2, pp_schedule='1f1b'),
     'pipeline parallelism needs layers of one kind'),
])
def test_layouts_that_take_no_pattern_say_so(spec_kw, complaint):
    tr = Trainer(TransformerLM(tiny()), optax.sgd(0.1),
                 spec=ParallelSpec(**spec_kw))
    with pytest.raises(ValueError, match=complaint):
        state = tr.init(jax.random.PRNGKey(0))
        tr.step(state, batch())


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match='global_every >= 2'):
        TransformerConfig.tiny(causal=False, window=8)
    with pytest.raises(ValueError, match='no place in a period'):
        TransformerConfig.tiny(causal=False, window=8, global_every=2,
                               global_at=2)
    with pytest.raises(ValueError, match="'layer' or 'rms'"):
        TransformerConfig.tiny(norm='batch')
    with pytest.raises(ValueError, match='activation must be one of'):
        TransformerConfig.tiny(gelu='relu')
    # a window under a causal mask is the causal band since PR 33
    assert TransformerConfig.tiny(window=8, global_every=2).layer_kinds() \
        == ['global', 'window']
    with pytest.raises(ValueError, match="'learned' or 'rotary'"):
        TransformerConfig.tiny(positions='alibi')
    with pytest.raises(ValueError, match='rotary positions and no window'):
        TransformerConfig.tiny(latent_rank=16)
    with pytest.raises(ValueError, match='lead a stack of expert layers'):
        TransformerConfig.tiny(dense_lead=1)
    with pytest.raises(ValueError, match='lead a stack of expert layers'):
        TransformerConfig.tiny(dense_lead=2, moe_experts=4)


@pytest.mark.parametrize('seq,window', [(64, (8, 8)), (640, (8, 24)),
                                        (1024, (64, 64)), (520, (3, 0))])
def test_xla_band_path_matches_a_dense_mask(seq, window):
    """``local_flash_attention(window=...)``: dense under a mask for a
    short sequence, in query blocks (no [s, s] tensor) for a long one."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 2, seq, 16), jnp.float32)
               for _ in range(3))
    ahead = np.arange(seq)[None, :] - np.arange(seq)[:, None]
    keep = (ahead >= -window[0]) & (ahead <= window[1])
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) / 4.0
    want = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(
        jnp.where(keep, scores, -jnp.inf), axis=-1), v)
    blocked = _band_block(seq, window) is not None
    assert blocked == (seq > 4 * (sum(window) + 1))
    text = jax.jit(lambda q, k, v: local_flash_attention(
        q, k, v, causal=False, window=window)).lower(q, k, v).as_text()
    assert ('x%dx%dxf32' % (seq, seq) in text) == (not blocked)
    got = local_flash_attention(q, k, v, causal=False, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_rotary_is_a_rotation_by_relative_position():
    """Scores of rotated q and k depend on the distance only, and the
    base sets the lowest frequency."""
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(1, 1, 1, 16), jnp.float32)
            for _ in range(2))

    def score(i, j, theta=10000.0):
        return float(jnp.sum(rotary(q, jnp.asarray([i]), theta)
                             * rotary(k, jnp.asarray([j]), theta)))
    assert score(5, 3) == pytest.approx(score(40, 38), rel=1e-5)
    assert score(5, 3) != pytest.approx(score(5, 4), rel=1e-3)
    assert score(0, 0) == pytest.approx(float(jnp.sum(q * k)), rel=1e-6)
    assert score(900, 0) != pytest.approx(score(900, 0, 160000.0), rel=1e-3)
    # rotate-half: dims j and j + d/2 turn together at theta ** (-2j/d)
    x = jnp.zeros((1, 1, 1, 16)).at[..., 1].set(1.0)
    turned = np.asarray(rotary(x, jnp.asarray([1]), 100.0))[0, 0, 0]
    angle = 100.0 ** (-2 / 16)
    assert turned[1] == pytest.approx(np.cos(angle), rel=1e-6)
    assert turned[9] == pytest.approx(np.sin(angle), rel=1e-6)


def test_gated_mlp_is_the_published_fused_matrix():
    mlp = GatedMlp(8, 12)
    params = mlp.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(3, 8), jnp.float32)
    fused = params['up']['kernel'].reshape(8, 24)
    inp, gate = jnp.split(x @ fused, 2, axis=-1)
    want = (jax.nn.gelu(inp, approximate=False) * gate) \
        @ params['down']['kernel']
    np.testing.assert_allclose(np.asarray(mlp.apply(params, x)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    assert 'bias' not in params['up'] and 'bias' not in params['down']
    # with biases (zero at init, so moved off it): one for each half
    biased = GatedMlp(8, 12, use_bias=True)
    p2 = jax.tree.map(lambda a: a + 0.1, biased.init(jax.random.PRNGKey(0)))
    assert p2['up']['bias'].shape == (2, 12)
    inp, gate = jnp.split(x @ p2['up']['kernel'].reshape(8, 24)
                          + p2['up']['bias'].reshape(24), 2, axis=-1)
    want = (jax.nn.gelu(inp, approximate=False) * gate) \
        @ p2['down']['kernel'] + p2['down']['bias']
    np.testing.assert_allclose(np.asarray(biased.apply(p2, x)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


# -- remat=True keeps the flash kernel's o and lse (PR 27) ------------------

def without_the_policy(monkeypatch):
    """``remat=True`` as ``jax.checkpoint(block)`` with no policy."""
    monkeypatch.setattr(jax.checkpoint_policies, 'save_only_these_names',
                        lambda *names: None)


def remat_events(t_before):
    return [r['tags'] for r in telemetry.get().loop_records()
            if r['t0'] >= t_before and r['name'] == 'transformer.remat']


# (cfg, kernel calls of the gradient with the policy: forward, dq and
# dkv once a layer). seq 64, with the crossover lowered to it.
_REMAT_STACKS = {
    'bert_scanned': (lambda: TransformerConfig.tiny(
        causal=False, max_len=64, dtype=jnp.float32, remat=True),
        {'flash_fwd': 2, 'flash_dq': 2, 'flash_dkv': 2}),
    # layer 0 unrolled, then one period of (window, window, global)
    'modernbert': (lambda: tiny(n_layers=4), {
        'flash_fwd': 2, 'flash_dq': 2, 'flash_dkv': 2,
        'flash_fwd_band': 2, 'flash_dq_band': 2, 'flash_dkv_band': 2}),
}


@pytest.mark.parametrize('stack', sorted(_REMAT_STACKS))
def test_remat_runs_the_forward_kernel_once_a_layer(stack, monkeypatch,
                                                    kernel_calls):
    from autodist_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, 'MIN_KERNEL_SEQ', 64)
    make_cfg, want = _REMAT_STACKS[stack]
    model = TransformerLM(make_cfg())
    params = model.init(jax.random.PRNGKey(0))
    data = batch(n=2)

    def traced():     # a new function each time: nothing traced is reused
        return jax.jit(jax.grad(lambda p, b: model.loss(p, b))).trace(
            params, data)

    t_before = time.perf_counter()
    kept = traced()
    assert kernel_calls(kept.jaxpr) == want
    assert remat_events(t_before) == [dict(
        policy='save_only_these_names', saved=['flash_o', 'flash_lse'],
        layers=model.cfg.n_layers,
        saved_bytes_per_layer=2 * 4 * 64 * (16 * 4 + 4))]
    without_the_policy(monkeypatch)
    whole = traced()
    assert kernel_calls(whole.jaxpr) == dict(
        want, **{name: 2 * want[name] for name in want if 'fwd' in name})
    for a, b in zip(*(jax.tree.leaves(t.lower().compile()(params, data))
                      for t in (kept, whole))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_keeps_the_forward_kernel_under_dp2_tp2(monkeypatch,
                                                      kernel_calls):
    """Through ``_tp_manual_flash``: the names are given inside the
    nested manual region and the policy outside it still finds them."""
    from autodist_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, 'MIN_KERNEL_SEQ', 64)
    model = TransformerLM(tiny(n_layers=4))

    def step(forward_calls):
        tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(dp=2, tp=2))
        state = tr.init(jax.random.PRNGKey(0))
        fn = tr._ensure_step(tr._step_key(batch()), state, batch())
        calls = kernel_calls(jax.make_jaxpr(fn)(
            state, tr.shard_batch(batch())))
        assert calls['flash_fwd'] + calls['flash_fwd_band'] == forward_calls
        assert calls['flash_dq'] + calls['flash_dq_band'] == 4
        state, _ = tr.step(state, batch())
        return jax.tree.map(np.asarray, state.params)

    t_before = time.perf_counter()
    kept = step(4)
    # a device's shard: batch 2 of 4, heads 2 of 4
    assert remat_events(t_before)[0]['saved_bytes_per_layer'] == \
        2 * 2 * 64 * (16 * 4 + 4)
    without_the_policy(monkeypatch)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(step(8))):
        np.testing.assert_array_equal(a, b)


def test_a_block_off_the_kernel_is_the_checkpoint_without_a_policy(
        monkeypatch):
    """Below the crossover (seq 128: ``bert-large.s128.c1``) no block
    names anything, the policy keeps nothing, and the step lowers to the
    same StableHLO as under ``jax.checkpoint(block)``."""
    model = TransformerLM(TransformerConfig.tiny(
        causal=False, n_layers=3, max_len=128, remat=True))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    data = jax.eval_shape(lambda: batch(seq=128))

    def lowered():
        return jax.jit(jax.value_and_grad(
            lambda p, b: model.loss(p, b))).lower(shapes, data).as_text()

    t_before = time.perf_counter()
    with_policy = lowered()
    assert remat_events(t_before) == [dict(
        policy='save_only_these_names', saved=['flash_o', 'flash_lse'],
        layers=0, saved_bytes_per_layer=0)]
    without_the_policy(monkeypatch)
    assert lowered() == with_policy
    # unrolled layers count one by one; no tier but True leaves the event
    t_before = time.perf_counter()
    for kw in (dict(remat=True, scan_layers=False), dict(remat='save_attn'),
               dict()):
        other = TransformerLM(TransformerConfig.tiny(**kw))
        jax.eval_shape(other.loss, jax.eval_shape(
            other.init, jax.random.PRNGKey(0)), batch(seq=64))
    assert [e['layers'] for e in remat_events(t_before)] == [0]


# -- a leading dense layer, then expert layers; latent attention -----------

def latent_stack(**kw):
    """kanana-2's structure at a size the CPU runs in a second: latent
    attention (4 heads of 8 + 4 on one rotary key, v heads of 6, a latent
    of 16), a dense layer of 48 that leads, then expert layers (2 of 4,
    sigmoid scores with a selection bias, a scale, a shared expert)."""
    d = dict(vocab=256, dim=32, n_layers=4, n_heads=4, max_len=64,
             causal=True, tied_embeddings=False, dtype=jnp.float32,
             remat=True, positions='rotary', rope_theta=1e6, latent_rank=16,
             qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, mlp_dim=16,
             gated_mlp=True, gelu='silu', norm='rms', mlp_bias=False,
             moe_experts=4, moe_top_k=2, moe_aux_coef=0.0, dense_lead=1,
             dense_mlp_dim=48, moe_scoring='sigmoid', moe_scale=2.448,
             moe_shared_dim=24)
    d.update(kw)
    return TransformerConfig(**d)


@pytest.mark.parametrize('n_layers,dense_lead', [(4, 1), (5, 2)])
def test_dense_layers_lead_unrolled_and_the_expert_layers_scan(n_layers,
                                                               dense_lead):
    cfg = latent_stack(n_layers=n_layers, dense_lead=dense_lead)
    scanned = TransformerLM(cfg)
    assert scanned._layers() == (dense_lead, ('global',),
                                 n_layers - dense_lead) and scanned.patterned
    params = scanned.init(jax.random.PRNGKey(0))
    for i in range(dense_lead):
        mlp = params['block_%03d' % i]['mlp']
        assert mlp['up']['kernel'].shape == (32, 2, 48) and 'router' not in mlp
    stack = params['blocks']['global']
    assert stack['mlp']['up'].shape == (n_layers - dense_lead, 4, 32, 2, 16)
    assert stack['mlp']['select_bias'].shape == (n_layers - dense_lead, 4)
    assert stack['mlp']['shared']['up']['kernel'].shape[1:] == (32, 2, 24)
    assert sorted(stack['attn']) == ['kv_a', 'kv_b', 'kv_norm', 'out', 'q']
    assert stack['attn']['kv_a']['kernel'].shape[1:] == (32, 4 + 16)
    # the bias selects: moved off zero, it takes no gradient
    stack['mlp']['select_bias'] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), stack['mlp']['select_bias'].shape)
    plain = TransformerLM(latent_stack(n_layers=n_layers,
                                       dense_lead=dense_lead,
                                       scan_layers=False, remat=False))
    t_before = time.perf_counter()
    got, got_g = jax.jit(jax.value_and_grad(scanned.loss))(params,
                                                           batch(seq=32))
    want, want_g = jax.jit(jax.value_and_grad(plain.loss))(
        unrolled_from_scanned(scanned, params), batch(seq=32))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    assert not np.any(np.asarray(
        got_g['blocks']['global']['mlp']['select_bias']))
    got_g = unrolled_from_scanned(scanned, got_g)
    assert jax.tree.structure(got_g) == jax.tree.structure(want_g)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=1e-4)
    events = [r['tags'] for r in telemetry.get().loop_records()
              if r['t0'] >= t_before and r['name'] == 'transformer.layers'
              and r['tags']['scanned']]
    assert events[0] == dict(
        n_layers=n_layers, period=1, periods=n_layers - dense_lead,
        remainder=dense_lead, pattern='global', scanned=True,
        global_layers=n_layers, window_layers=0, dense_lead=dense_lead,
        expert_layers=n_layers - dense_lead)
    plans = [r['tags'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'moe.plan']
    assert plans and all(
        (p['scoring'], p['bias'], p['scale'], p['shared'])
        == ('sigmoid', True, 2.448, 24) for p in plans)


def test_the_step_counters_count_the_expert_layers_only():
    """With every expert held each pair is a row here: ``moe_rows_here``,
    the mean over the EXPERT layers, is ``tokens x top_k`` whatever the
    dense layers before them."""
    trainer = Trainer(TransformerLM(latent_stack(n_layers=3)),
                      optax.sgd(0.1))
    _, metrics = trainer.step(trainer.init(jax.random.PRNGKey(0)),
                              batch(n=8, seq=32))
    assert float(metrics['moe_rows_here']) == 8 * 32 * 2
    assert float(metrics['moe_load_mean']) == 8 * 32 * 2 / 4
    assert float(metrics['moe_load_max']) >= 8 * 32 * 2 / 4


def test_latent_attention_through_the_kernels_is_the_xla_path(monkeypatch):
    """``LatentAttention`` past the kernels' crossover (interpret mode)
    against the same module under XLA, output and every gradient, and
    the scopes it leaves: ``mla_latent`` holds the three projections."""
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.models.attention import LatentAttention
    attn = LatentAttention(64, 2, 64, 128, 64, 128, dtype=jnp.float32,
                           rope_theta=1e6)
    params = attn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 64))
    shape = (1, 2, 512, 192)
    assert attn.kernel_shape(shape) == shape and attn.v_dim == 128

    def run(params, x):
        return jnp.sum(jnp.sin(attn.apply(params, x)))
    with jax.default_matmul_precision('highest'):
        got = jax.value_and_grad(run, (0, 1))(params, x)
        text = jax.jit(jax.grad(run)).lower(params, x).as_text(
            debug_info=True)
        monkeypatch.setattr(fa, 'MIN_KERNEL_SEQ', 10 ** 9)
        assert attn.kernel_shape(shape) is None
        assert attn.position_tables(shape) is None
        want = jax.value_and_grad(run, (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
    assert 'flash_dkv_mla' in text and 'mla_latent' in text



# The five older configurations' structures at tiny sizes (BERT's and
# GPT-2's plain stacks, ModernBERT's periods, Mellum2's causal band,
# grouped kv heads, YaRN and softmax experts, kanana-2's latent
# attention, leading dense layer and sigmoid experts with a shared one),
# each lowered (loss and gradient, bf16) to the StableHLO text it
# lowered to on PR 41's parent (jax 0.9.0): the single-mixer mechanism
# was added beside them, and their steps are what they were.
def _older_configurations():
    yarn = dict(factor=16.0, original_max_position_embeddings=64,
                beta_fast=32.0, beta_slow=1.0,
                attention_factor=1.2772588722239782)
    half = dict(max_len=64, dtype=jnp.bfloat16, remat=True)
    sparse = dict(vocab=256, causal=True, tied_embeddings=False,
                  positions='rotary', gated_mlp=True, gelu='silu',
                  norm='rms', mlp_bias=False, moe_top_k=2, moe_aux_coef=0.0,
                  **half)
    return {
        'bert': (TransformerConfig.tiny(causal=False, **half),
                 'b82480e23cf962d4'),
        'gpt2': (TransformerConfig.tiny(causal=True, **half),
                 'ac71f1802497965a'),
        'modernbert': (TransformerConfig.modernbert_large(
            vocab=256, dim=64, n_layers=4, n_heads=4, window=8, mlp_dim=96,
            **half), '13d913251b58bc49'),
        'mellum2': (TransformerConfig(
            dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
            rope_theta=500000.0, rope_yarn=yarn, window=7, global_every=4,
            global_at=3, mlp_dim=32, moe_experts=8, moe_held=4,
            embed_init_scale=8.0, **sparse), 'cd391cab4e274eba'),
        'kanana2': (TransformerConfig(
            dim=32, n_layers=3, n_heads=2, rope_theta=1e6, latent_rank=16,
            qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, mlp_dim=16,
            dense_lead=1, dense_mlp_dim=48, moe_experts=4,
            moe_scoring='sigmoid', moe_scale=2.448, moe_shared_dim=24,
            embed_init_scale=16.0, **sparse), 'db644ddb651d39a9'),
    }


@pytest.mark.parametrize('name', ['bert', 'gpt2', 'modernbert', 'mellum2',
                                  'kanana2'])
def test_older_configurations_lower_to_the_step_they_had(name):
    import hashlib
    cfg, want = _older_configurations()[name]
    model = TransformerLM(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
              for k in ('tokens', 'targets')}
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        params, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
