"""Layers that are ONE mixer each (PR 41, ``TransformerConfig.mixers``):
nemotron_h's pattern of Mamba-2 layers, expert layers and attention
without positions at a tiny size; how the stack is unrolled and named,
the scopes it runs under, the parallel layouts that take it and those
that refuse it, ``relu2``, the gated norm over groups of lanes and the
causal conv."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.api import Trainer
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec


def batch(n=4, seq=64, seed=0):
    rng = np.random.RandomState(seed)
    return {'tokens': rng.randint(0, 256, (n, seq), dtype=np.int32),
            'targets': rng.randint(0, 256, (n, seq), dtype=np.int32)}


# -- layers that are one mixer each (PR 41) ---------------------------------

def single_mixers(**kw):
    """nemotron_h's structure at a size the CPU runs in a second: layers
    of ONE mixer by a pattern's letters, Mamba-2 layers of 2 heads of 64
    (the scan's kernels in interpret mode at seq 128), attention over
    grouped kv heads without positions, relu2 experts (2 of 4, sigmoid
    scores, a shared expert)."""
    d = dict(vocab=256, dim=32, n_layers=4, mixers='ME*M', n_heads=4,
             n_kv_heads=2, head_dim=8, max_len=128, causal=True,
             tied_embeddings=False, dtype=jnp.float32, remat=True,
             positions='none', mlp_dim=16, gelu='relu2', norm='rms',
             norm_eps=1e-5, mlp_bias=False, moe_experts=4, moe_top_k=2,
             moe_aux_coef=0.0, moe_scoring='sigmoid', moe_scale=2.5,
             moe_shared_dim=24,
             ssm=dict(heads=2, head_dim=64, groups=1, state=128, conv=4))
    d.update(kw)
    return TransformerConfig(**d)


def test_single_mixer_layers_are_unrolled_by_their_pattern():
    cfg = single_mixers()
    model = TransformerLM(cfg)
    assert model._layers() == (4, (), 0) and model.patterned
    params = model.init(jax.random.PRNGKey(0))
    assert sorted(params) == ['block_000', 'block_001', 'block_002',
                              'block_003', 'embed', 'lm_head', 'ln_f']
    assert [sorted(params['block_%03d' % i]['mixer'])[0] for i in range(4)] \
        == ['a_log', 'down', 'out', 'a_log']
    assert all(sorted(params['block_%03d' % i]) == ['mixer', 'norm']
               for i in range(4))
    # an expert without a gate has the one matrix
    assert params['block_001']['mixer']['up'].shape == (4, 32, 16)
    assert params['block_001']['mixer']['shared']['up']['kernel'].shape \
        == (32, 24)
    t_before = time.perf_counter()
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params,
                                                          batch(2, 128))
    assert np.isfinite(float(loss))
    assert not np.any(np.asarray(grads['block_001']['mixer']['select_bias']))
    events = [r['tags'] for r in telemetry.get().loop_records()
              if r['t0'] >= t_before and r['name'] == 'transformer.layers']
    assert events[0] == dict(
        n_layers=4, period=0, periods=0, remainder=4, pattern='',
        scanned=False, global_layers=1, window_layers=0, dense_lead=0,
        expert_layers=1, mixers='ME*M', ssm_layers=2, mlp_layers=1)
    # scan_layers or not, the same unrolled stack and tree
    plain = TransformerLM(single_mixers(scan_layers=False, remat=False))
    want = jax.jit(plain.loss)(params, batch(2, 128))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


def test_single_mixer_layers_run_under_their_scopes():
    """Each layer under the scope of its mixer inside ``block``: ``ssm``
    (and in it ``ssm_mixer`` round the kernels ``ssd_fwd`` /
    ``ssd_bwd``), ``mlp`` and ``attention``: what the benchmark's
    ``ssm_ms_per_step``, ``mlp_ms_per_step`` and ``attention_ms_per_step``
    read."""
    import re
    model = TransformerLM(single_mixers())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    text = jax.jit(jax.grad(model.loss)).lower(params, batch(2, 128)).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ('ssm/ssm_mixer/', 'mlp/moe_shared/', 'mlp/moe_route/',
                  'attention/'):
        assert any(re.search(r'block\)?/' + scope, n) for n in names), scope
    kernels = [n for n in names if re.search(r'ssd_(fwd|bwd)\W+pallas', n)]
    assert len(kernels) >= 3 and all(
        re.search(r'block\)?/ssm/ssd_', n) for n in kernels)
    assert not any('/ssm/' in n and '/attention/' in n for n in names)


def test_attention_without_positions_sees_a_set_of_keys():
    """``positions='none'``: no table, no rotation; a causal attention
    layer's output at the last position does not change when the
    earlier tokens change places (with a position table it does)."""
    tokens = np.random.RandomState(0).randint(0, 256, (1, 32))
    moved = tokens.copy()
    moved[0, :31] = tokens[0, :31][::-1]
    logits = {}
    for positions in ('none', 'learned'):
        model = TransformerLM(single_mixers(
            n_layers=1, mixers='*', positions=positions, moe_experts=0,
            ssm=None, max_len=32))
        params = model.init(jax.random.PRNGKey(0))
        assert ('pos_embed' in params) == (positions == 'learned')
        logits[positions] = [np.asarray(model.apply(params, t)[0, -1])
                             for t in (tokens, moved)]
    np.testing.assert_allclose(*logits['none'], rtol=1e-5, atol=1e-5)
    assert np.max(np.abs(logits['learned'][0] - logits['learned'][1])) > 1e-3


def test_single_mixer_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="'learned' or 'rotary' or 'none'"):
        TransformerConfig.tiny(positions='alibi')
    with pytest.raises(ValueError, match="one of 'M', 'E', '\\*' for each "
                       "of the 4 layers"):
        single_mixers(mixers='MEM')
    with pytest.raises(ValueError, match='one of'):
        single_mixers(mixers='MEXM')
    with pytest.raises(ValueError, match='give their sizes in `ssm`'):
        single_mixers(ssm=None)
    with pytest.raises(ValueError, match='no window'):
        single_mixers(window=8, global_every=2)
    with pytest.raises(ValueError, match='relu2'):
        TransformerConfig.tiny(gelu='relu')
    # a pattern given letter by letter is the string
    assert single_mixers(mixers=['M', 'E', '*', 'M']).mixers == 'ME*M'


def test_relu2_and_the_gated_group_norm():
    from autodist_tpu.models.core import (ACTIVATIONS, GatedGroupRMSNorm,
                                          relu2)
    assert ACTIVATIONS['relu2'] is relu2
    np.testing.assert_array_equal(
        np.asarray(relu2(jnp.asarray([-2.0, 0.0, 0.5, 3.0]))),
        [0.0, 0.0, 0.25, 9.0])
    norm = GatedGroupRMSNorm(24, 3, eps=1e-5)
    params = {'scale': 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(0),
                                                   (24,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 24))
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 24))

    def by_reshape(params, x, z):
        u = (x * jax.nn.silu(z)).reshape(2, 5, 3, 8)
        u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
        return u.reshape(2, 5, 24) * params['scale']
    got = jax.value_and_grad(
        lambda p, x, z: jnp.sum(jnp.sin(norm.apply(p, x, z))),
        argnums=(0, 1, 2))(params, x, z)
    want = jax.value_and_grad(
        lambda p, x, z: jnp.sum(jnp.sin(by_reshape(p, x, z))),
        argnums=(0, 1, 2))(params, x, z)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    # the gate is INSIDE the norm: not norm(x) * silu(z)
    outside = by_reshape(params, x, jnp.full_like(z, 1e4)) * jax.nn.silu(z)
    assert float(jnp.max(jnp.abs(norm.apply(params, x, z) - outside))) > 0.1
    with pytest.raises(ValueError, match='do not divide'):
        GatedGroupRMSNorm(24, 5)


def test_the_conv_is_four_shifted_products_and_its_backward_is_written_out():
    from autodist_tpu.models.ssm import causal_conv
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))

    def plain(x, taps, bias):
        out = jnp.zeros_like(x)
        for t in range(9):
            for i in range(4):
                u = t - 3 + i
                if u >= 0:
                    out = out.at[:, t].add(taps[i] * x[:, u])
        return out + bias
    got = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(causal_conv(*a))), argnums=(0, 1, 2))(
            x, taps, bias)
    want = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(plain(*a))), argnums=(0, 1, 2))(
            x, taps, bias)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize('spec_kw,complaint', [
    (dict(dp=1, sp=2), 'Mamba2Mixer under sequence parallelism'),
    (dict(dp=1, tp=2), 'shards more than the batch'),
    (dict(dp=1, pp=2), 'pipeline parallelism needs layers of one kind'),
])
def test_layouts_that_take_no_single_mixer_stack_say_so(spec_kw, complaint):
    tr = Trainer(TransformerLM(single_mixers(mixers='MMMM', moe_experts=0)),
                 optax.sgd(0.1), spec=ParallelSpec(**spec_kw))
    with pytest.raises(ValueError, match=complaint):
        state = tr.init(jax.random.PRNGKey(0))
        tr.step(state, batch(2, 128))


def test_single_mixer_stack_under_dp2_equals_one_device():
    """The scan's kernels on each device's batch in a manual region: a
    dp=2 step moves the parameters as a dp=1 step."""
    model = TransformerLM(single_mixers())
    after = {}
    for name, spec in (('one', ParallelSpec(dp=1)),
                       ('mesh', ParallelSpec(dp=2))):
        tr = Trainer(model, optax.sgd(0.1), spec=spec)
        state, metrics = tr.step(tr.init(jax.random.PRNGKey(0)),
                                 batch(2, 128))
        after[name] = (float(metrics['loss']),
                       jax.tree.map(np.asarray, state.params))
    np.testing.assert_allclose(after['mesh'][0], after['one'][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(after['mesh'][1]),
                    jax.tree.leaves(after['one'][1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


def test_the_step_counters_count_the_expert_layers_of_a_pattern():
    trainer = Trainer(TransformerLM(single_mixers(mixers='EMEE')),
                      optax.sgd(0.1))
    _, metrics = trainer.step(trainer.init(jax.random.PRNGKey(0)),
                              batch(n=8, seq=128))
    assert float(metrics['moe_rows_here']) == 8 * 128 * 2
    assert float(metrics['moe_load_mean']) == 8 * 128 * 2 / 4
