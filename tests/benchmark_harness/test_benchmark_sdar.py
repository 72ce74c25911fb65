"""Family ``sdar`` (PR 45): the block-diffusion step of the program
against the plain reference at a tiny size that keeps the model's
structure (grouped kv heads, a per-head norm on q and k whose weights
are not one, rotary positions that repeat, 2 of 8 experts a row with 4
held, the loss on the noised rows weighed by ``1 / t``), each deliberate
fault against the same limits, the shares against the uncut expert
layer, the generator, the analytic FLOPs and the new kernels' costs
against hand counts, and a rehearsal of both new cells' run loops on the
CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json, tiny_config as bert_tiny

from benchmark import bd_kinds, harness
from benchmark.generators import block_diffusion_lm
from benchmark.models import sdar

TRAFFIC = dict(generator='block_diffusion_lm', seq=32, global_batch=4,
               block_length=4, t_low=0.45, t_high=0.95, zipf_exponent=0.5)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'sdar-30b-a3b-chat.s8192.c1'

# Both sides compute in f32 on the CPU: what separates them is the order
# of their sums (as test_benchmark_mellum2.py).
LEAF_RTOL = 1e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32', **over):
    config = dict(
        name='tiny-sdar', family='sdar', num_hidden_layers=2, hidden_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        vocab_size=64, max_position_embeddings=64, attention_bias=False,
        decoder_sparse_step=1, mlp_only_layers=[], hidden_act='silu',
        intermediate_size=96, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, use_sliding_window=False,
        tie_word_embeddings=False, rms_norm_eps=1e-6, num_experts_held=4,
        moe_aux_coef=0.0, embed_init_scale=1.0,
        mask_row_init_scale=0.02, qk_norm_init_scale=1.0,
        qk_proj_init_factor=1.0, out_proj_init_factor=1.0,
        router_init_factor=1.0,
        block_length=4, mask_token_id=63,
        dtype=dtype, remat=True, scan_layers=True, loss_chunk=0,
        task='block_diffusion_lm')
    config.update(over)
    return config


def seeded_params(model):
    """Seeded weights with every norm scale moved off its initial 1 (the
    per-head ones too: a norm after the rotation is then told from one
    before it)."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    return jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape)
        if a.shape[-1] in (8, 32) and a.ndim <= 2 and a.size <= 128 else a,
        params)


def reference_grads(config, ref_params, probe, **switches):
    """(loss, gradient) of the reference on the whole probe: the
    sequences' weighted sums over the batch's sum of weights."""
    def loss(p):
        sums = [sdar.reference_sum(p, *(jnp.asarray(probe[k][i]) for k in (
            'tokens', 'targets', 'mask')), config, **switches)
            for i in range(len(probe['tokens']))]
        return sum(s for s, _ in sums) / sum(w for _, w in sums)
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = sdar.build(config)
    params = seeded_params(model)
    probe = next(block_diffusion_lm.batches(TRAFFIC, config, 0, batch=4,
                                            stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    return config, model, params, probe, (
        got[0], sdar.to_reference_params(got[1]))


def worst_leaf(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return max(float(jnp.max(jnp.abs(a - b))
                     / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, model, params, probe, (got_loss, got_grads) = case
    assert not model.patterned and model.cfg.block_length == 4
    want_loss, want_grads = reference_grads(
        config, sdar.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    for side in (got_grads, want_grads):
        assert all(float(jnp.max(jnp.abs(g))) > 0
                   for g in jax.tree.leaves(side))
    # the reference's mask is the rules' own array, never the kernels'
    # two-source form
    from autodist_tpu.kernels import flash_attention as fa
    np.testing.assert_array_equal(sdar.attention_mask(16, 4),
                                  fa.block_diffusion_mask(32, 4))


WRONG = [dict(mask_kind='causal'), dict(mask_kind='own_clean'),
         dict(qk_norm=None), dict(qk_norm='after'),
         dict(positions='index'), dict(shift=1), dict(weighted=False),
         dict(matmul_dtype=jnp.bfloat16)]
WRONG_IDS = ['a_plain_causal_mask', 'a_noised_block_sees_its_own_clean',
             'no_qk_norm', 'norm_after_the_rotation', 'positions_0_to_2L',
             'loss_shifted_by_one', 'unweighted', 'bf16_products']


@pytest.mark.parametrize('broken', WRONG, ids=WRONG_IDS)
def test_a_wrong_reference_misses_the_tolerance_severalfold(case, broken):
    config, _, params, probe, (_, got_grads) = case
    _, wrong = reference_grads(
        config, sdar.to_reference_params(params), probe, **broken)
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


@pytest.fixture(scope='module')
def probed(case):
    """The program's side of the cell's own comparison, once: the probe
    of ``trainer_leaves`` on two sequences."""
    from benchmark.engines import trainer_leaves
    config, _, params, probe, _ = case
    probe = {k: v[:2] for k, v in probe.items()}
    engine = trainer_leaves.Engine(sdar.build(config), {'dp': 1},
                                   jax.devices()[:1])
    state = engine.trainer.init(None, params=params)
    got = engine.loss_and_grad_norm(state, probe)
    assert set(trainer_leaves.PROBE) == {'gradients'}
    return probe, got, trainer_leaves.PROBE.pop('gradients')


@pytest.mark.parametrize('broken,fails', [({}, False)] + [
    (b, True) for b in WRONG[:-1]] + [
        (dict(matmul_dtype=jnp.float8_e4m3fn), True)],
    ids=['sound'] + WRONG_IDS[:-1] + ['fp8_products'])
def test_the_cells_own_comparison_catches_each_fault(case, probed, capsys,
                                                     broken, fails):
    """Through the engine and the family as ``harness.py`` calls them,
    at the cell's own limits: the sound reference passes and each
    deliberate fault of ISSUE 45's list fails one of the two checks."""
    from benchmark.engines import trainer_leaves
    config, _, params, _, _ = case
    probe, got, gradients = probed
    trainer_leaves.PROBE['gradients'] = gradients
    want = sdar.reference_loss_and_grad_norm(
        config, sdar.to_reference_params(params), probe, **broken)
    assert 'gradients' not in trainer_leaves.PROBE     # taken, not left
    loss_ok = harness.close(got[0], want[0], harness.LOSS_RTOL)
    norm_ok = harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['limits'] == {'leaf': sdar.LEAF_RTOL,
                              'routed_leaf': sdar.ROUTED_LEAF_RTOL,
                              'router_leaf': sdar.ROUTER_LEAF_RTOL}
    # embed, ln_final, head; 9 a layer of two
    assert len(line['gradient_leaves']) == 3 + 9 * 2
    assert sdar.leaf_limit('layers/w_down/1') == sdar.ROUTED_LEAF_RTOL
    assert sdar.leaf_limit('layers/w_router/0') == sdar.ROUTER_LEAF_RTOL
    assert sdar.leaf_limit('layers/g_q/0') == sdar.LEAF_RTOL \
        == sdar.leaf_limit('head')
    assert want[1] == pytest.approx(line['reference_global_grad_norm'] * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-9)
    assert (loss_ok and norm_ok) is not fails
    if not fails:
        assert line['worst_difference'] < 1e-4


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """What ties the share to the model: the parts that the shares of an
    expert layer give (each chip its ``num_experts_held`` of the 8: here
    8 shares of one) are what the uncut layer gives, in the program and
    in the reference's own MoE alike; the ``2 L`` rows are just rows."""
    from autodist_tpu.models.moe import MoeMlp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 32, 32), jnp.float32)
    whole = MoeMlp(32, 16, 8, top_k=2, act=jax.nn.silu, gated=True)
    params = whole.init(jax.random.PRNGKey(0))
    got_whole, _, _ = whole.apply(params, x)
    share = MoeMlp(32, 16, 8, top_k=2, held=(0, 1), act=jax.nn.silu,
                   gated=True)
    routed = jax.jit(lambda up, down, first: share._held_part(
        x, params['router'], up, down, first)[0])
    total = sum(routed(params['up'][e:e + 1], params['down'][e:e + 1], e)
                for e in range(8))
    np.testing.assert_allclose(total, got_whole, rtol=2e-5, atol=2e-6)
    # the reference's uncut layer: a one-layer model's MoE, by the
    # difference its residual makes
    with jax.default_matmul_precision('highest'):
        probs = jax.nn.softmax(x[0] @ params['router']['kernel'], -1)
        vals, idx = jax.lax.top_k(probs, 2)
        w = vals / vals.sum(-1, keepdims=True)
        want = sum(
            jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
            * ((jax.nn.silu(x[0] @ params['up'][e][:, 0])
                * (x[0] @ params['up'][e][:, 1])) @ params['down'][e])
            for e in range(8))
    np.testing.assert_allclose(got_whole[0], want, rtol=2e-5, atol=2e-6)


def test_generator():
    config = tiny_config()
    traffic = dict(TRAFFIC, seq=64, global_batch=8)
    first = [next(block_diffusion_lm.batches(traffic, config, 7))
             for _ in range(2)]
    again = block_diffusion_lm.batches(traffic, config, 7)
    other = block_diffusion_lm.batches(traffic, config, 8)
    batch = next(again)
    for k in ('tokens', 'targets', 'mask'):
        np.testing.assert_array_equal(batch[k], first[0][k])
    assert not np.array_equal(next(other)['targets'], batch['targets'])
    probe = next(block_diffusion_lm.batches(traffic, config, 7, batch=2,
                                            stream=1))
    assert probe['tokens'].shape == (2, 64)
    assert (batch['tokens'].dtype, batch['targets'].dtype,
            batch['mask'].dtype) == (np.int32, np.int32, np.float32)
    assert batch['tokens'].shape == batch['mask'].shape == (8, 64)
    masked = batch['tokens'] == 63
    # ids below the mask id; the mask id where and only where w > 0;
    # elsewhere the clean id
    assert batch['targets'].max() < 63 and batch['targets'].min() >= 0
    np.testing.assert_array_equal(masked, batch['mask'] > 0)
    np.testing.assert_array_equal(batch['tokens'][~masked],
                                  batch['targets'][~masked])
    # one level a block, inside its range, stratified over the batch's
    # blocks: sorted, the levels are one to a stratum
    levels = block_diffusion_lm.noise_levels(
        np.random.default_rng(0), 128, 0.45, 0.95)
    assert levels.min() >= 0.45 and levels.max() < 0.95
    strata = np.sort((levels - 0.45) / 0.5 * 128)
    assert np.all(np.floor(strata) == np.arange(128))
    assert not np.all(np.diff(levels) > 0)          # dealt, not in order
    w = batch['mask'].reshape(8, 16, 4)
    # (a block's masked positions carry one weight, 1 / t of the block)
    assert np.all((w == w.max(-1, keepdims=True)) | (w == 0))
    seen = 1.0 / w.max(-1)[w.max(-1) > 0]
    assert seen.min() >= 0.45 and seen.max() < 0.95
    # the mask id on t of a block's positions in expectation: over many
    # batches the share of masked positions is the mean level, 0.7
    gen = block_diffusion_lm.batches(traffic, config, 11)
    share = np.mean([(next(gen)['tokens'] == 63).mean() for _ in range(40)])
    assert abs(share - 0.7) < 0.02
    assert block_diffusion_lm.tokens_per_step(traffic) == 8 * 64
    with pytest.raises(ValueError, match='built with block_length'):
        next(block_diffusion_lm.batches(dict(traffic, block_length=8),
                                        config, 0))
    with pytest.raises(ValueError, match='unknown task'):
        next(block_diffusion_lm.batches(traffic, dict(config,
                                                      task='causal_lm'), 0))


# By hand, this chip's share at seq 8192 (forward, a trained token, which
# is two rows): attention's matrices 2048 x 5120 + 4096 x 2048 =
# 18,874,368 a row, the router 262,144, held experts at the expected 8 x
# 16 / 128 = 1 pair 4,718,592: 23,855,104 multiply-adds a row, 47,710,208
# a token and layer, 95,420,416 FLOPs; QK^T and PV over the mask's L + B =
# 8196 keys a token: 4 x 8196 x 4096 = 134,283,264; five layers
# 1,148,518,400; the head 2 x 2048 x 18992 = 77,791,232: 1,226,309,632.
def test_flops_per_token_equal_a_hand_count():
    with open(os.path.join(BENCH, 'configs', 'sdar-30b-a3b-chat.json')) as f:
        config = json.load(f)
    assert sdar.flops_per_token(config, 8192) == 3 * 1226309632
    assert sdar.flops_per_token(config, 32) == 3 * (
        5 * (95420416 + 4 * 36 * 4096) + 77791232)
    assert bd_kinds.live_pairs(8192, 4) == 8192 * 8192 + 8192 * 4
    # the masked core is 55% of the forward
    assert round(5 * 134283264 / 1226309632, 2) == 0.55
    # every published number is in the file as published but the cut
    assert sorted(config['reduced']) == [
        'num_experts_held', 'num_hidden_layers', 'vocab_size']
    for key, value in config['published'].items():
        if key not in config['reduced']:
            assert config[key] == value, key
    assert (config['published']['num_hidden_layers'],
            config['published']['vocab_size']) == (48, 151936)
    assert config['num_experts_held'] * 8 == config['num_experts']
    assert config['vocab_size'] * 8 == config['published']['vocab_size']
    assert config['mask_token_id'] == config['vocab_size'] - 1
    assert config['block_length'] == 4
    assumed = ' '.join(config['assumed'])
    for said in ('block length', 'U[0.45, 0.95', 'no shift', 'mask id',
                 'sum of the weights', 'q_norm', 'embed_init_scale'):
        assert said in assumed, said
    # 551.0M parameters here, 8.82 GB at 16 bytes
    shapes = jax.eval_shape(sdar.build(config).init, jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 550984960 and round(count * 16 / 1e9, 2) == 8.82
    assert str(count) in config['deployment'].replace(',', '')


def test_new_kernel_costs_equal_a_hand_count():
    # a product over the live pairs at 128 lanes: 2 x 2 x 32 x (8192^2 +
    # 8192 x 4) x 128 = 1,100,048,498,688 FLOPs; a [2, 16384, 32 x 128]
    # tensor in bf16 is 268,435,456 bytes, one of the 4 kv heads'
    # 33,554,432
    shape = dict(batch=2, heads=32, kv_heads=4, seq=8192, block=4,
                 head_dim=128)
    product, q, kv = 1100048498688, 268435456, 33554432
    assert bd_kinds.live_pairs(8192, 4) == 67141632
    assert bd_kinds.call_cost('flash_fwd_bd', **shape) == (
        2 * product, 2 * q + 2 * kv)
    assert bd_kinds.call_cost('flash_dq_bd', **shape) == (
        3 * product, 4 * q + 2 * kv)
    assert bd_kinds.call_cost('flash_dkv_bd', **shape) == (
        4 * product, 2 * q + 4 * kv)
    # twice a causal call's pairs, but for the blocks' diagonal
    from benchmark import moe_kinds
    causal, _ = moe_kinds.gqa_call_cost(2, 32, 4, 8192, 4096, 128, False)
    assert 2 * causal < bd_kinds.call_cost('flash_fwd_bd', **shape)[0] \
        < 2.002 * causal


def test_kernels_scope_and_counters_are_read_by_name():
    line = ('%%%s = f32[8]{0} %s(%%p), metadata={op_name="jit(step_fn)/'
            '%s"}')
    kernel = ', custom_call_target="tpu_custom_call"'
    hlo = '\n'.join([
        line % ('flash_fwd_bd.1', 'custom-call', 'jvp()/block/attention/'
                'flash_fwd_bd/pallas_call') + kernel,
        'ROOT ' + line % ('flash_dkv_bd.2', 'custom-call', 'transpose(jvp())/'
                          'block/attention/flash_dkv_bd/pallas_call')
        + kernel,
        line % ('flash_fwd.3', 'custom-call', 'jvp()/block/attention/'
                'flash_fwd/pallas_call') + kernel,
        line % ('fusion.4', 'fusion', 'jvp()/block/attention/qk_norm/mul'),
        line % ('fusion.5', 'fusion', 'jvp()/block/mlp/moe_route/top_k'),
    ])
    from benchmark import mla_kinds
    assert mla_kinds.kernel_heads(hlo, 'flash_fwd_bd') == {'%flash_fwd_bd.1'}
    assert mla_kinds.kernel_heads(hlo, 'flash_dkv_bd') == {'%flash_dkv_bd.2'}
    assert mla_kinds._named_heads(hlo, ('qk_norm',), False) == {'%fusion.4'}
    # a program without the names (the parent): nothing to read, no error
    said = []
    run = {'hlo': hlo.replace('_bd', ''), 'say': said.append,
           'config': {}, 'traffic': {}}

    class NoTrace:
        ops, steps = {0: []}, 1
    assert bd_kinds.kernels_ms(NoTrace, run) is None
    assert bd_kinds.roofline_pct(NoTrace, run, 'flash_dq_bd') is None
    assert bd_kinds.counters(NoTrace, run) is None
    assert said and 'nothing to read' in said[0]
    from benchmark.layer_metrics import (bd_mask_rows_pct,
                                         bd_moe_load_max_over_mean,
                                         bd_moe_ms_per_step,
                                         bd_moe_rows_here_pct,
                                         flash_bd_ms_per_step,
                                         qk_norm_ms_per_step)
    bare = dict(run, hlo=line % ('fusion.9', 'fusion', 'jvp()/block/mlp/mul'))
    for module in (bd_mask_rows_pct, bd_moe_load_max_over_mean,
                   bd_moe_ms_per_step, bd_moe_rows_here_pct,
                   flash_bd_ms_per_step, qk_norm_ms_per_step):
        assert module.reduce(NoTrace, bare) is None


def test_name_map_covers_every_parameter(case):
    _, _, params, _, _ = case
    ref = sdar.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    assert ref['layers']['w_gate_up'] is params['blocks']['mlp']['up']
    assert ref['layers']['w_qkv'].shape == (2, 32, (4 + 2 * 2) * 8)
    assert ref['layers']['g_q'].shape == ref['layers']['g_k'].shape == (2, 8)


def test_build_draws_the_mask_row_and_the_head_norms_as_the_file_says():
    """The draw is the family's (``sdar.build``), not the library's: the
    mask id's row and the per-head norms' weights at the file's scales,
    every other leaf what ``TransformerLM`` draws from the same key."""
    from autodist_tpu.models.transformer import TransformerLM
    config = tiny_config(embed_init_scale=8.0, mask_row_init_scale=0.02,
                         qk_norm_init_scale=1.5, qk_proj_init_factor=32.0,
                         out_proj_init_factor=10.0, router_init_factor=8.0)
    model = sdar.build(config)
    params = model.init(jax.random.PRNGKey(3))
    plain = TransformerLM(model.cfg).init(jax.random.PRNGKey(3))
    table = np.asarray(params['embed']['table'])
    assert 0.01 < table[63].std() < 0.03 and 6 < table[:63].std() < 10
    np.testing.assert_array_equal(table[:63], plain['embed']['table'][:63])
    attn = params['blocks']['attn']
    for name in ('q_norm', 'k_norm'):
        np.testing.assert_array_equal(attn[name]['scale'],
                                      np.full((2, 8), 1.5, np.float32))
    theirs = plain['blocks']
    for name in ('ln1', 'ln2'):
        jax.tree.map(np.testing.assert_array_equal, params['blocks'][name],
                     theirs[name])
    mlp = params['blocks']['mlp']
    for name in ('up', 'down'):
        np.testing.assert_array_equal(mlp[name], theirs['mlp'][name])
    np.testing.assert_allclose(mlp['router']['kernel'],
                               8 * theirs['mlp']['router']['kernel'])
    np.testing.assert_allclose(attn['out']['kernel'],
                               10 * theirs['attn']['out']['kernel'])
    qk = (4 + 2) * 8                      # the q and k columns; v behind them
    got, want = attn['qkv']['kernel'], theirs['attn']['qkv']['kernel']
    np.testing.assert_allclose(got[..., :qk], 32 * want[..., :qk])
    np.testing.assert_array_equal(got[..., qk:], want[..., qk:])


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace):
    """The run loop with the new family, generator and metrics at the
    tiny size on the CPU, under the real cell's name so that
    ``BENCHMARK.json``'s lists apply (f32: at these widths bf16's
    rounding is outside the leaves' limits; batches large enough and ids
    skewed enough that ten steps' loss falls past their scatter)."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer_leaves', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    traffic = dict(TRAFFIC, seq=64, global_batch=32, zipf_exponent=1.3)
    result, lines = harness.rehearse(
        cell, tiny_config('float32'), traffic, PEAKS, seed=2147483693,
        trace=trace, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert result['device']['platform'] == 'cpu'
    bench = benchmark_json()
    if trace:
        # no device plane on the CPU: the readers of the trace find
        # nothing and say so without raising; the counters are the
        # program's and are read here as on the chip
        assert set(result['metrics']) == {
            'compile_s', 'compile_cache_miss', 'step_wall_ms', 'step_hbm_gb',
            'bd_moe_rows_here_pct', 'bd_moe_load_max_over_mean',
            'bd_mask_rows_pct'}
        listed = harness.metrics_for(CELL, bench['per_layer'])
        assert {'flash_bd_ms_per_step', 'flash_bd_fwd_roofline_pct',
                'flash_bd_dq_roofline_pct', 'flash_bd_dkv_roofline_pct',
                'qk_norm_ms_per_step', 'bd_moe_ms_per_step'} <= set(listed)
        assert 'flash_roofline_pct' not in listed
        assert 'moe_rows_here_pct' not in listed
        assert 0 < result['metrics']['bd_moe_rows_here_pct']['value'] <= 100
        assert 1 <= result['metrics'][
            'bd_moe_load_max_over_mean']['value'] <= 4
        assert 30 < result['metrics']['bd_mask_rows_pct']['value'] < 40
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}
        # a trained token is counted once
        assert report['whole_call_tokens_per_s_per_chip'] > 0


def test_rehearsal_of_the_strong_scaling_cell(tmp_path):
    """``bert-large.s512.b24.dp4``: files and entries only; its run loop
    over dp=4 at the tiny size on the CPU's virtual devices."""
    with open(os.path.join(BENCH, 'workloads',
                           'bert-large.s512.b24.dp4.json')) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, 'workloads',
                           'bert-large.s512.dp4.json')) as f:
        sibling = json.load(f)
    assert {k: cell[k] for k in ('config', 'chips', 'engine', 'parallel',
                                 'trace_steps', 'expects')} == {
        k: sibling[k] for k in ('config', 'chips', 'engine', 'parallel',
                                'trace_steps', 'expects')}
    # .s512.c1's traffic under a name of its own: a pair of configuration
    # and traffic appears once in BENCHMARK.json
    assert cell['traffic'] == 'mlm-s512-gb96-dp4'
    with open(os.path.join(BENCH, 'traffic', 'mlm-s512-gb96.json')) as f:
        one_chip = json.load(f)
    with open(os.path.join(BENCH, 'traffic', 'mlm-s512-gb96-dp4.json')) as f:
        mine = json.load(f)
    assert {k: v for k, v in mine.items() if k != 'why'} == {
        k: v for k, v in one_chip.items() if k != 'why'}
    assert mine['global_batch'] == 24 * cell['chips']
    traffic = dict(generator='zipf_lm', seq=32, global_batch=8,
                   zipf_exponent=1.1)
    result, lines = harness.rehearse(
        dict(cell, expects=dict(cell['expects'], pallas_custom_calls=False)),
        bert_tiny(causal=False), traffic, PEAKS, seed=2147483693,
        trace=False, out_dir=str(tmp_path))
    report = json.loads(lines[-1])
    assert result['correct'] is True, report['checks']
    assert 'all-reduce' in report['hlo']['collectives']
    # it reports the metrics that have no list and, since PR 50 listed it
    # under them, the three collective_*
    listed = harness.metrics_for(cell['name'], benchmark_json()['per_layer'])
    assert sorted(m for m in listed if m.startswith('collective_')) == [
        'collective_bytes_per_step', 'collective_exposed_pct',
        'collective_ms_per_step']
    assert {'device_step_ms', 'flash_ms_per_step', 'attention_ms_per_step',
            'step_hbm_gb'} <= set(listed)
