"""Family ``kanana2`` (PR 39): the program against the plain reference at
a tiny size that keeps the model's structure (latent attention with one
rotary key for all heads and a v head narrower than q's and k's, a
leading dense layer, expert layers with sigmoid scores, a selection
bias that is not zero, a scale and a shared expert; 2 of 8 experts a
token, 4 held), each deliberate fault against the same limits, the
eight shares against the uncut layer, the analytic FLOPs and the new
kernels' costs against hand counts, and a rehearsal of the cell's run
loop on the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, benchmark_json

from benchmark import harness, mla_kinds
from benchmark.generators import zipf_lm
from benchmark.models import kanana2

TRAFFIC = dict(generator='zipf_lm', seq=32, global_batch=4,
               zipf_exponent=0.5)
PEAKS = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}
CELL = 'kanana-2-30b-a3b.s8192.c1'

# Both sides compute in f32 on the CPU: what separates them is the order
# of their sums, about 1e-6 of a leaf's largest element (as
# test_benchmark_mellum2.py).
LEAF_RTOL = 1e-4
LOSS_RTOL = 1e-5


def tiny_config(dtype='float32', **over):
    config = dict(
        name='tiny-kanana2', family='kanana2', num_hidden_layers=3,
        hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
        head_dim=4, vocab_size=64, max_position_embeddings=64,
        attention_bias=False, first_k_dense_replace=1, hidden_act='silu',
        intermediate_size=48, kv_lora_rank=16, q_lora_rank=None,
        qk_head_dim=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=6, rope_interleave=True, rope_scaling=None,
        rope_theta=1000000, moe_intermediate_size=16, moe_layer_freq=1,
        n_group=1, topk_group=1, n_routed_experts=8, n_shared_experts=2,
        num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=2.448, scoring_func='sigmoid',
        topk_method='noaux_tc', tie_word_embeddings=False,
        rms_norm_eps=1e-6, num_experts_held=4, moe_aux_coef=0.0,
        embed_init_scale=1.0, dtype=dtype, remat=True, scan_layers=True,
        loss_chunk=0, task='causal_lm')
    config.update(over)
    return config


def seeded_params(model, bias=0.3):
    """Seeded weights with every norm scale moved off its initial 1 and
    a selection bias that is not zero, so that selecting by ``s + b``
    and weighing by ``s`` is told from doing both by one of them."""
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.shape[-1] in (16, 32) and a.ndim <= 2 and a.size <= 128 else a,
        params)
    mlp = params['blocks']['global']['mlp']
    mlp['select_bias'] = bias * jax.random.normal(
        jax.random.PRNGKey(2), mlp['select_bias'].shape)
    return params


def reference_grads(config, ref_params, probe, **switches):
    def loss(p):
        return jnp.mean(jnp.stack([
            kanana2.reference_loss(p, jnp.asarray(t), jnp.asarray(y),
                                   config, **switches)
            for t, y in zip(probe['tokens'], probe['targets'])]))
    return jax.jit(jax.value_and_grad(loss))(ref_params)


@pytest.fixture(scope='module')
def case():
    config = tiny_config()
    model = kanana2.build(config)
    params = seeded_params(model)
    probe = next(zipf_lm.batches(TRAFFIC, config, 0, batch=4, stream=1))
    got = jax.jit(jax.value_and_grad(model.loss))(params, probe)
    return config, model, params, probe, (
        got[0], kanana2.to_reference_params(got[1]))


def worst_leaf(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return max(float(jnp.max(jnp.abs(a - b))
                     / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_program_agrees_with_the_reference_on_every_gradient_leaf(case):
    config, model, params, probe, (got_loss, got_grads) = case
    # the dense layer leads, unrolled; the expert layers scan
    assert (model._lead, model._period, model._periods) == (
        1, ('global',), 2)
    want_loss, want_grads = reference_grads(
        config, kanana2.to_reference_params(params), probe)
    assert abs(float(got_loss) - float(want_loss)) \
        <= LOSS_RTOL * float(want_loss)
    assert worst_leaf(got_grads, want_grads) <= LEAF_RTOL
    # every leaf but the selection bias's has a gradient that is not
    # nothing; the bias's is nothing on both sides
    for side in (got_grads, want_grads):
        layers = dict(side['layers'])
        assert not np.any(np.asarray(layers.pop('b_select')))
        assert all(float(jnp.max(jnp.abs(g))) > 0
                   for g in jax.tree.leaves(dict(side, layers=layers)))


@pytest.mark.parametrize('broken', [
    dict(rope_scores=False), dict(scale_dim=8), dict(select_bias=False),
    dict(shared=False), dict(drop_expert=1),
    dict(matmul_dtype=jnp.bfloat16)],
    ids=['no_rotary_part', 'scale_of_the_nope_width', 'selected_without_b',
         'no_shared_expert', 'an_expert_dropped', 'bf16_products'])
def test_a_wrong_reference_misses_the_tolerance_severalfold(case, broken):
    config, _, params, probe, (_, got_grads) = case
    _, wrong = reference_grads(
        config, kanana2.to_reference_params(params), probe, **broken)
    assert worst_leaf(got_grads, wrong) > 5 * LEAF_RTOL


@pytest.fixture(scope='module')
def probed(case):
    """The program's side of the cell's own comparison, once: the probe
    of ``trainer_leaves`` on two sequences (loss, global norm, and the
    gradient it left for the family)."""
    from benchmark.engines import trainer_leaves
    config, _, params, probe, _ = case
    probe = {k: v[:2] for k, v in probe.items()}
    engine = trainer_leaves.Engine(kanana2.build(config), {'dp': 1},
                                   jax.devices()[:1])
    state = engine.trainer.init(None, params=params)
    got = engine.loss_and_grad_norm(state, probe)
    assert set(trainer_leaves.PROBE) == {'gradients'}
    return probe, got, trainer_leaves.PROBE.pop('gradients')


def through_the_harness(config, params, probed, **switches):
    """``(checks pass, reference)`` of the cell's own comparison: the
    family's reference beside the probe's gradient, under
    ``harness.close`` at the harness's limits."""
    from benchmark.engines import trainer_leaves
    probe, got, gradients = probed
    trainer_leaves.PROBE['gradients'] = gradients
    want = kanana2.reference_loss_and_grad_norm(
        config, kanana2.to_reference_params(params), probe, **switches)
    assert 'gradients' not in trainer_leaves.PROBE     # taken, not left
    return (harness.close(got[0], want[0], harness.LOSS_RTOL),
            harness.close(got[1], want[1], harness.GRAD_NORM_RTOL)), want


@pytest.mark.parametrize('broken,fails', [
    ({}, False), (dict(rope_scores=False), True), (dict(scale_dim=8), True),
    (dict(select_bias=False), True), (dict(shared=False), True),
    (dict(drop_expert=1), True),
    (dict(matmul_dtype=jnp.float8_e4m3fn), True)],
    ids=['sound', 'no_rotary_part', 'scale_of_the_nope_width',
         'selected_without_b', 'no_shared_expert', 'an_expert_dropped',
         'fp8_products'])
def test_the_cells_own_comparison_catches_each_fault(case, probed, capsys,
                                                     broken, fails):
    """Through the engine and the family as ``harness.py`` calls them,
    at the cell's own limits: the sound reference passes and each
    deliberate fault of ISSUE 39's list fails ``reference_grad_norm``."""
    config, _, params, _, _ = case
    (loss_ok, norm_ok), want = through_the_harness(config, params, probed,
                                                   **broken)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line['limits'] == {'leaf': kanana2.LEAF_RTOL,
                              'routed_leaf': kanana2.ROUTED_LEAF_RTOL,
                              'router_leaf': kanana2.ROUTER_LEAF_RTOL}
    leaves = line['gradient_leaves']
    # embed, ln_final, head; the dense layer's 9; 13 a layer of two
    assert len(leaves) == 3 + 9 + 13 * 2
    assert leaves['layers/b_select/0'] == leaves['layers/b_select/1'] == 0
    assert kanana2.leaf_limit('layers/w_down/1') == kanana2.ROUTED_LEAF_RTOL
    assert kanana2.leaf_limit('layers/w_router/0') \
        == kanana2.ROUTER_LEAF_RTOL
    assert kanana2.leaf_limit('dense/w_ffn_down') == kanana2.LEAF_RTOL \
        == kanana2.leaf_limit('layers/ws_down/0')
    assert want[1] == pytest.approx(line['reference_global_grad_norm'] * (
        1 + harness.GRAD_NORM_RTOL * line['worst_in_limits']), rel=1e-9)
    assert norm_ok is not fails
    if not fails:
        assert loss_ok and line['worst_difference'] < 1e-4


def test_a_selection_bias_with_a_gradient_is_a_thousand_limits(case):
    config, _, params, probe, (_, got_grads) = case
    _, want = reference_grads(config, kanana2.to_reference_params(params),
                              probe)
    got = dict(got_grads, layers=dict(
        got_grads['layers'], b_select=jnp.full((2, 8), 1e-9)))
    norm = kanana2.held_to_every_leaf(1.0, got, want, 1)
    assert norm == pytest.approx(1 + harness.GRAD_NORM_RTOL * 1e3)


def test_the_two_rotary_conventions_are_equal_under_the_permutation():
    """The published rotation (adjacent pairs, moved to the two halves
    and then rotated by halves) of the published columns is the
    program's rotation by halves of its own columns:
    ``published_q_columns`` and ``published_kva_columns`` are that fixed
    permutation, and the scores agree."""
    from autodist_tpu.models.attention import rotary
    heads, dims, rank, s = 4, (8, 4, 6), 16, 16
    nope, rope, _ = dims
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(s, heads * (nope + rope)), jnp.float32)
    c = jnp.asarray(rng.randn(s, rope + rank), jnp.float32)
    q_pub = q[:, np.asarray(kanana2.published_q_columns(heads, dims))]
    c_pub = c[:, np.asarray(kanana2.published_kva_columns(rank, rope))]
    # published: each head nope | rope, adjacent pairs
    inv_freq = 1e6 ** (-2.0 * np.arange(rope // 2) / rope)
    angle = np.arange(s)[:, None] * inv_freq[None]
    cos, sin = np.cos(angle), np.sin(angle)

    def by_pairs(x):                          # [..., rope], pairs (2j, 2j+1)
        a, b = x[..., 0::2], x[..., 1::2]
        return np.stack([a * cos - b * sin, a * sin + b * cos], -1)
    qp = np.asarray(q_pub).reshape(s, heads, nope + rope)
    want = np.einsum('qhjt,kjt->hqk', by_pairs(qp[..., nope:].transpose(
        1, 0, 2)).transpose(1, 0, 2, 3), by_pairs(
            np.asarray(c_pub)[:, rank:]))
    # the program: the rope parts after a group's nope parts, by halves
    group = 4                                 # all the heads: a tiny model's
    q_rope = q.reshape(s, heads // group, -1)[..., group * nope:].reshape(
        s, heads, rope)
    got = jnp.einsum(
        'hqd,kd->hqk',
        rotary(q_rope.transpose(1, 0, 2)[None], jnp.arange(s), 1e6)[0],
        rotary(c[None, None, :, :rope], jnp.arange(s), 1e6)[0, 0])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # and the nope parts are the same columns
    np.testing.assert_array_equal(
        qp[..., :nope], np.asarray(q.reshape(s, heads // group, -1)[
            ..., :group * nope].reshape(s, heads, nope)))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: the routed parts that the
    shares of an expert layer give (each chip its ``num_experts_held`` of
    the 8: here 4 shares of 2) plus the shared expert counted ONCE are
    what the uncut reference gives for the whole layer, in the program
    and in the reference alike."""
    from autodist_tpu.models.moe import MoeMlp
    config = tiny_config(num_experts_held=8, num_hidden_layers=2)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 32, 32), jnp.float32)
    whole = MoeMlp(32, 16, 8, top_k=2, act=jax.nn.silu, gated=True,
                   scoring='sigmoid', select_bias=True, scale=2.448,
                   shared=32)
    params = whole.init(jax.random.PRNGKey(0))
    params['select_bias'] = 0.3 * jax.random.normal(jax.random.PRNGKey(1),
                                                    (8,))
    # the uncut layer by the reference: its MoE as a model of one
    # expert layer would run it
    w = {'w_router': params['router']['kernel'],
         'b_select': params['select_bias'], 'w_gate_up': params['up'],
         'w_down': params['down'],
         'ws_gate_up': params['shared']['up']['kernel'],
         'ws_down': params['shared']['down']['kernel']}
    want = _reference_moe(config, w, x[0])
    got_whole, _, _ = whole.apply(params, x)
    np.testing.assert_allclose(got_whole[0], want, rtol=2e-5, atol=2e-6)
    # each share's routed part (one expert a share; `first` is traced, so
    # the eight are one compiled program), the shared expert ONCE
    share = MoeMlp(32, 16, 8, top_k=2, held=(0, 1), act=jax.nn.silu,
                   gated=True, scoring='sigmoid', select_bias=True,
                   scale=2.448)
    routed = jax.jit(lambda up, down, first: share._held_part(
        x, params['router'], up, down, first, params['select_bias'])[0])
    total = sum(routed(params['up'][e:e + 1], params['down'][e:e + 1], e)
                for e in range(8))
    total = total + whole.shared.apply(params['shared'], x)
    np.testing.assert_allclose(total[0], want, rtol=2e-5, atol=2e-6)
    # and the reference's own shares (a share's experts first among the
    # router's outputs), the shared expert counted once
    def share_of(first, **switches):
        moved = {k: np.roll(w[k], -first, axis)
                 for k, axis in (('w_router', 1), ('b_select', 0))}
        return _reference_moe(
            dict(config, num_experts_held=2),
            dict(w, w_gate_up=w['w_gate_up'][first:first + 2],
                 w_down=w['w_down'][first:first + 2], **moved),
            x[0], **switches)
    parts = sum(share_of(first, shared=False) for first in range(0, 8, 2))
    shared_once = share_of(0) - share_of(0, shared=False)
    np.testing.assert_allclose(parts + shared_once, want, rtol=2e-5,
                               atol=2e-6)


def _reference_moe(config, w, x, **switches):
    with jax.default_matmul_precision('highest'):
        return kanana2.reference_expert_layer(
            jax.tree.map(jnp.asarray, w), x, config, **switches)


# By hand, this chip's share at seq 8192 (forward, a token): attention's
# matrices 2048 x 6144 + 2048 x 576 + 512 x 8192 + 4096 x 2048 =
# 26,345,472 a layer, five layers 131,727,360; the dense MLP 3 x 2048 x
# 6144 = 37,748,736; an expert layer's router 262,144, shared expert 3 x
# 2048 x 1536 = 9,437,184, held experts at the expected 6 x 16 / 128 =
# 0.75 pairs 0.75 x 4,718,592 = 3,538,944: 13,238,272, four of them
# 52,953,088; the head 2048 x 16032 = 32,833,536: 255,262,720
# multiply-adds, 510,525,440 FLOPs; QK^T at 192 and PV at 128 over 4096
# keys in five layers: 5 x 2 x 4096 x 32 x 320 = 419,430,400.
def test_flops_per_token_equal_a_hand_count():
    with open(os.path.join(BENCH, 'configs', 'kanana-2-30b-a3b.json')) as f:
        config = json.load(f)
    by_hand = 3 * (510525440 + 419430400)
    assert kanana2.flops_per_token(config, 8192) == by_hand == 2789867520
    assert kanana2.flops_per_token(config, 32) == 3 * (
        510525440 + 5 * 2 * 16 * 32 * 320)
    # every published number is in the file as published but the cut
    assert sorted(config['reduced']) == [
        'num_experts_held', 'num_hidden_layers', 'vocab_size']
    for key, value in config['published'].items():
        if key not in config['reduced']:
            assert config[key] == value, key
    assert (config['published']['num_hidden_layers'],
            config['published']['vocab_size']) == (48, 128256)
    assert config['num_experts_held'] * 8 == config['n_routed_experts']
    assert config['vocab_size'] * 8 == config['published']['vocab_size']
    assert config['num_hidden_layers'] == 1 + 4
    # 576.0M parameters here, 9.2 GB at 16 bytes
    shapes = jax.eval_shape(kanana2.build(config).init,
                            jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 575955968 and round(count * 16 / 1e9, 1) == 9.2


def test_new_kernel_costs_equal_a_hand_count():
    # one lane of contraction over the causal half of the square: 2 x 4
    # x 32 x 8192 x 4096 = 8,589,934,592 FLOPs; a [4, 8192, 32 x 128]
    # tensor in bf16 is 268,435,456 bytes, the rotary key 4,194,304
    shape = dict(batch=4, heads=32, seq=8192, nope=128, rope=64, v=128)
    lane, t128, key = 8589934592, 268435456, 4194304
    q = t128 * 3 // 2
    assert mla_kinds.call_cost('flash_fwd_mla', **shape) == (
        lane * (192 + 128), q + 3 * t128 + key)
    assert mla_kinds.call_cost('flash_dq_mla', **shape) == (
        lane * (2 * 192 + 128), 2 * q + 4 * t128 + key)
    assert mla_kinds.call_cost('flash_dkv_mla', **shape) == (
        lane * (2 * 192 + 2 * 128), q + 5 * t128 + 2 * key)


def test_kernels_and_scopes_are_read_by_name():
    line = ('%%%s = f32[8]{0} %s(%%p), metadata={op_name="jit(step_fn)/'
            '%s"}')
    call = 'custom-call'
    hlo = '\n'.join([
        line % ('flash_fwd_mla.1', call, 'jvp()/block/attention/'
                'flash_fwd_mla/pallas_call') + ', custom_call_target='
        '"tpu_custom_call"',
        'ROOT ' + line % ('flash_dkv_mla.2', call, 'transpose(jvp())/block/'
                          'attention/flash_dkv_mla/pallas_call')
        + ', custom_call_target="tpu_custom_call"',
        line % ('flash_fwd.3', call, 'jvp()/block/attention/flash_fwd/'
                'pallas_call') + ', custom_call_target="tpu_custom_call"',
        line % ('fusion.4', 'fusion', 'jvp()/block/attention/mla_latent/'
                'dot_general'),
        line % ('fusion.5', 'fusion', 'jvp()/block/mlp/moe_shared/mul'),
        line % ('fusion.6', 'fusion', 'jvp()/block/mlp/moe_route/top_k'),
        line % ('fusion.7', 'fusion', 'jvp()/block/mlp/while/body/'
                'moe_experts/mul'),
    ])
    assert mla_kinds.kernel_heads(hlo) == {'%flash_fwd_mla.1',
                                          '%flash_dkv_mla.2'}
    assert mla_kinds.kernel_heads(hlo, 'flash_fwd_mla') == {
        '%flash_fwd_mla.1'}
    assert mla_kinds._named_heads(hlo, ('mla_latent',), False) == {
        '%fusion.4'}
    assert mla_kinds._named_heads(hlo, ('moe_shared',), False) == {
        '%fusion.5'}
    assert mla_kinds._named_heads(hlo, mla_kinds.ROUTED_SCOPES, False) == {
        '%fusion.6', '%fusion.7'}
    # a program without the names: nothing to read, and no error
    said = []
    run = {'hlo': hlo.replace('_mla', ''), 'say': said.append,
           'config': {}, 'traffic': {}}

    class NoTrace:
        ops, steps = {0: []}, 1
    assert mla_kinds.kernels_ms(NoTrace, run) is None
    assert mla_kinds.roofline_pct(NoTrace, run, 'flash_dq_mla') is None
    assert said and 'nothing to read' in said[0]


def test_name_map_covers_every_parameter(case):
    _, _, params, _, _ = case
    ref = kanana2.to_reference_params(params)
    assert sum(a.size for a in jax.tree.leaves(ref)) == sum(
        a.size for a in jax.tree.leaves(params))
    assert ref['layers']['w_gate_up'] is \
        params['blocks']['global']['mlp']['up']
    assert ref['layers']['w_q'].shape == (2, 32, 4 * 12)
    assert ref['dense']['w_kva'].shape == (32, 16 + 4)
    assert ref['layers']['w_kvb'].shape == (2, 16, 4 * (8 + 6))
    # the three gathers are permutations
    for cols, n in ((kanana2.published_q_columns(4, (8, 4, 6)), 48),
                    (kanana2.published_kva_columns(16, 4), 20),
                    (kanana2.published_kvb_columns(4, (8, 4, 6)), 56)):
        assert sorted(cols) == list(range(n))


@pytest.mark.parametrize('trace', [False, True], ids=['untraced', 'traced'])
def test_rehearsal_of_the_cell(tmp_path, trace):
    """The run loop with the new family at the tiny size on the CPU,
    under the real cell's name so that ``BENCHMARK.json``'s lists apply."""
    cell = dict(name=CELL, config='tiny', traffic='tiny', chips=1,
                engine='trainer_leaves', parallel={'dp': 1}, trace_steps=3,
                expects={'pallas_custom_calls': False, 'collectives': []})
    result, lines = harness.rehearse(
        cell, tiny_config('bfloat16'), TRAFFIC, PEAKS, seed=2147483693,
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
            'moe_routed_rows_here_pct', 'moe_routed_load_max_over_mean'}
        listed = harness.metrics_for(CELL, bench['per_layer'])
        assert {'flash_mla_ms_per_step', 'flash_mla_fwd_roofline_pct',
                'flash_mla_dq_roofline_pct', 'flash_mla_dkv_roofline_pct',
                'mla_latent_ms_per_step', 'moe_shared_ms_per_step',
                'moe_routed_ms_per_step'} <= set(listed)
        assert 'flash_roofline_pct' not in listed
        assert 'moe_rows_here_pct' not in listed
        # the counters count the two expert layers, not the dense one
        assert 0 < result['metrics']['moe_routed_rows_here_pct']['value'] \
            <= 100
        assert 1 <= result['metrics'][
            'moe_routed_load_max_over_mean']['value'] <= 4
    else:
        assert set(result['metrics']) == {m['name']
                                          for m in bench['end_to_end']}
