"""Residual streams (PR 48): manifold-constrained hyper-connections
(``models/hyper_connections.py``) around the sublayers of
``models/transformer.Block``, latent attention's q down-projection and
YaRN, and what a configuration without streams still traces."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.api import Trainer
from autodist_tpu.models.hyper_connections import HyperConnection, sinkhorn
from autodist_tpu.models.transformer import (Block, TransformerConfig,
                                             TransformerLM)
from autodist_tpu.parallel.axes import ParallelSpec

N, DIM = 4, 32


def tiny(**kw):
    return TransformerConfig.tiny(**dict(dict(
        dim=DIM, n_heads=4, positions='rotary', norm='rms', gated_mlp=True,
        gelu='silu', mlp_bias=False, tied_embeddings=False, mlp_dim=48,
        latent_rank=16, latent_q_rank=12, qk_nope_dim=8, qk_rope_dim=4,
        v_head_dim=6, hc_streams=N, dtype=jnp.float32), **kw))


def drawn(params, key=7, alpha=1.0, spread=0.5):
    """Connections whose coefficients move with the token and lie away
    from the plain residual path."""
    keys = iter(jax.random.split(jax.random.PRNGKey(key), 64))

    def one(hc):
        return dict(hc, alpha=jnp.full_like(hc['alpha'], alpha),
                    bias=spread * jax.random.normal(next(keys),
                                                    hc['bias'].shape))

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        return {k: one(v) if k.startswith('hc_') else walk(v)
                for k, v in tree.items()}
    return walk(params)


# -- Sinkhorn-Knopp ---------------------------------------------------------

def test_sinkhorn_is_doubly_stochastic_at_the_tests_draw():
    logits = jax.random.normal(jax.random.PRNGKey(0), (N, N, 3, 50))
    h = sinkhorn(logits, 20, 1e-6)
    assert h.shape == logits.shape and bool(jnp.all(h >= 0))
    # rows (over j, axis 1) sum to one after any round; columns (over i,
    # axis 0) as far as the rounds have converged
    np.testing.assert_allclose(jnp.sum(h, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(h, axis=0), 1.0, atol=1e-3)
    # one round leaves the columns further off than twenty
    one = jnp.max(jnp.abs(jnp.sum(sinkhorn(logits, 1, 1e-6), axis=0) - 1))
    assert float(one) > 10 * float(jnp.max(jnp.abs(jnp.sum(h, axis=0) - 1)))


def test_sinkhorn_is_finite_at_the_clamp():
    """Logits of -30 and +30 side by side (a ratio of e^60 in one row),
    forward and through the twenty rounds' backward."""
    sign = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), 0.5,
                                          (N, N, 2, 8)), 1.0, -1.0)

    def total(x):
        h = sinkhorn(jnp.clip(x, -30.0, 30.0), 20, 1e-6)
        return jnp.sum(h * jnp.arange(N * N).reshape(N, N, 1, 1)), h
    (_, h), grad = jax.value_and_grad(total, has_aux=True)(40.0 * sign)
    assert bool(jnp.all(jnp.isfinite(h))) and bool(jnp.all(h >= 0))
    assert bool(jnp.all(jnp.isfinite(grad)))
    np.testing.assert_allclose(jnp.sum(h, axis=1), 1.0, atol=1e-5)


def test_coefficients_are_the_written_equations():
    """``HyperConnection.coefficients`` against the equations a token at a
    time in numpy: the norm over the n dim numbers, the three products
    with their gates and biases, sigmoid, 2 sigmoid, clamp and rounds."""
    hc = HyperConnection(DIM, N, iters=20, clamp=(-3.0, 3.0), eps=1e-6)
    params = hc.init(jax.random.PRNGKey(0))
    params['alpha'] = jnp.asarray([1.5, -0.7, 2.0])
    params['bias'] = jax.random.normal(jax.random.PRNGKey(1), (N * (N + 2),))
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 5, N * DIM))
    pre, post, res = (np.asarray(t) for t in hc.coefficients(params, x))
    phi, alpha, bias = (np.asarray(params[k], np.float64)
                        for k in ('phi', 'alpha', 'bias'))
    for b in range(2):
        for s in range(5):
            row = np.asarray(x[b, s], np.float64)
            v = row / np.sqrt(np.mean(row ** 2) + 1e-6)
            z = v @ phi
            p = alpha[0] * z[:N] + bias[:N]
            q = alpha[1] * z[N:2 * N] + bias[N:2 * N]
            m = np.exp(np.clip(alpha[2] * z[2 * N:] + bias[2 * N:], -3, 3)
                       ).reshape(N, N)
            for _ in range(20):
                m = m / (m.sum(0, keepdims=True) + 1e-6)
                m = m / (m.sum(1, keepdims=True) + 1e-6)
            np.testing.assert_allclose(pre[:, b, s], 1 / (1 + np.exp(-p)),
                                       rtol=1e-4)
            np.testing.assert_allclose(post[:, b, s], 2 / (1 + np.exp(-q)),
                                       rtol=1e-4)
            np.testing.assert_allclose(res[:, :, b, s], m, rtol=1e-3,
                                       atol=1e-6)
    # the mixes: u = H_pre x, x' = H_res x + H_post^T y
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 5, DIM))
    xs = np.asarray(x).reshape(2, 5, N, DIM)
    np.testing.assert_allclose(
        hc.read(x, jnp.asarray(pre)),
        np.einsum('nbs,bsnc->bsc', pre, xs), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(hc.write(x, y, jnp.asarray(post), jnp.asarray(res))
                   ).reshape(2, 5, N, DIM),
        np.einsum('ijbs,bsjc->bsic', res, xs)
        + np.einsum('ibs,bsc->bsic', post, np.asarray(y)),
        rtol=1e-5, atol=1e-5)


# -- the plain residual path as a special case ------------------------------

def plain_connection(hc, post):
    """Gates 0; ``H_pre`` one-hot on stream 0, ``H_res`` the identity,
    ``H_post`` ``post`` (1 where ``post`` says so, else 0)."""
    big = 40.0
    return dict(hc, alpha=jnp.zeros_like(hc['alpha']), bias=jnp.concatenate([
        jnp.asarray([big] + [-big] * (N - 1)),
        jnp.asarray([0.0 if on else -big for on in post]),
        (2 * big * jnp.eye(N) - big).ravel()]) + 0 * hc['bias'])


def test_a_block_with_one_hot_coefficients_is_the_plain_block_on_stream_0():
    """Gates 0 and biases that make ``H_pre`` and ``H_post`` one-hot and
    ``H_res`` the identity: stream 0 leaves the block as ``x0 + f(norm(
    x0))`` twice over, the plain block on the same weights, and the other
    streams as they came."""
    cfg = tiny(scan_layers=False)
    block = Block(cfg)
    params = block.init(jax.random.PRNGKey(0))
    for name in ('hc_attn', 'hc_mlp'):
        params[name] = plain_connection(params[name], (1, 0, 0, 0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, N * DIM))
    got, _ = block.apply(params, x)
    plain = Block(tiny(scan_layers=False, hc_streams=None))
    want, _ = plain.apply({k: v for k, v in params.items()
                           if not k.startswith('hc_')}, x[..., :DIM])
    np.testing.assert_allclose(got[..., :DIM], want, rtol=1e-5, atol=1e-5)
    # (every round's sums carry eps: the identity to within 1e-5)
    np.testing.assert_allclose(got[..., DIM:], x[..., DIM:], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('scan', [True, False], ids=['scanned', 'unrolled'])
def test_the_model_with_plain_coefficients_is_the_one_stream_model(scan):
    """... and with ``H_post`` one on every stream the streams stay copies
    of the one-stream model's state, their sum is ``n`` times it, and the
    final norm takes the ``n`` out: the loss and every shared gradient are
    the one-stream model's on the same weights."""
    cfg = tiny(scan_layers=scan, n_layers=2, remat=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    blocks = [params['blocks']] if scan else [
        params['block_%03d' % i] for i in range(2)]
    for block in blocks:
        for name in ('hc_attn', 'hc_mlp'):
            one = plain_connection(jax.tree.map(lambda a: a[0] if scan
                                                else a, block[name]),
                                   (1, 1, 1, 1))
            block[name] = jax.tree.map(
                lambda a, ref: jnp.broadcast_to(a, ref.shape), one,
                block[name])
    plain = TransformerLM(tiny(scan_layers=scan, n_layers=2, remat=True,
                               hc_streams=None))

    def without(tree):
        return {k: without(v) for k, v in tree.items()
                if not k.startswith('hc_')} if isinstance(tree, dict) \
            else tree
    batch = {'tokens': jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                          256),
             'targets': jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                                           256)}
    got, got_grads = jax.value_and_grad(model.loss)(params, batch)
    want, want_grads = jax.value_and_grad(plain.loss)(without(params), batch)
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(without(got_grads)),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


# -- what a configuration without streams traces ----------------------------

def scan_carries(jaxpr):
    """Shapes of the floating carries of every scan in ``jaxpr``."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'scan':
                n_consts = eqn.params['num_consts']
                n_carry = eqn.params['num_carry']
                found.extend(
                    v.aval.shape for v in
                    eqn.invars[n_consts:n_consts + n_carry]
                    if jnp.issubdtype(v.aval.dtype, jnp.floating))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize('streams', [None, N], ids=['unset', 'four'])
def test_the_carry_and_the_scopes_follow_the_streams(streams):
    model = TransformerLM(tiny(hc_streams=streams, n_layers=3))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ('tokens', 'targets')}
    carries = scan_carries(jax.make_jaxpr(model.loss)(params, batch))
    text = jax.jit(model.loss).lower(params, batch).as_text(debug_info=True)
    if streams is None:
        # no stream axis and no scope of the connections: [b, s, dim]
        assert (2, 32, DIM) in carries
        assert not [c for c in carries if N * DIM in c]
        assert '/hc' not in text and 'hc_' not in text
        assert 'hc_attn' not in params['blocks']
    else:
        assert (2, 32, N * DIM) in carries
        assert (2, 32, DIM) not in carries
        for scope in ('block/hc/hc_coeff/', 'block/hc/hc_mix/',
                      'block/attention/', 'block/mlp/'):
            assert scope in text, scope
        # beside attention and mlp, not around them nor inside them
        for wrong in ('hc/attention', 'hc/mlp', 'attention/hc', 'mlp/hc',
                      'hc_coeff/attention', 'hc_mix/mlp'):
            assert wrong not in text, wrong


def test_the_first_unrolled_layer_makes_its_streams_inside_its_checkpoint():
    """Where the stack's first layer runs unrolled, its checkpoint takes
    the embedding row ``[b, s, dim]`` and makes the streams inside
    (``_block_fn(enters=True)``): the step keeps the row for that layer's
    backward, not ``hc_streams`` copies of it; every later layer's takes
    the streams. The loss and the gradients are those of the streams made
    outside."""
    model = TransformerLM(tiny(scan_layers=False, n_layers=2, remat=True))
    params = drawn(model.init(jax.random.PRNGKey(0)))
    batch = {k: jnp.asarray(np.random.RandomState(i).randint(
        0, 256, (2, 32))) for i, k in enumerate(('tokens', 'targets'))}
    taken = [[v.aval.shape for v in eqn.invars
              if hasattr(v.aval, 'shape') and len(v.aval.shape) == 3]
             for eqn in jax.make_jaxpr(model.loss)(params, batch).eqns
             if eqn.primitive.name in ('checkpoint', 'remat2')]
    assert len(taken) == 2
    assert (2, 32, DIM) in taken[0] and (2, 32, N * DIM) not in taken[0]
    assert (2, 32, N * DIM) in taken[1]
    got = jax.value_and_grad(model.loss)(params, batch)
    outside = TransformerLM(tiny(scan_layers=False, n_layers=2, remat=True))
    inside = outside._block_fn

    def streams_made_outside(block, tables, stats, enters=False):
        fn = inside(block, tables, stats)
        return (lambda p, row: fn(p, outside._entered(row))) if enters \
            else fn
    outside._block_fn = streams_made_outside
    want = jax.value_and_grad(outside.loss)(params, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_streams_refuse_what_carries_one_stream():
    with pytest.raises(ValueError, match='hc_streams=4.*mixers'):
        TransformerConfig.tiny(n_layers=2, mixers='E*', hc_streams=4)
    with pytest.raises(ValueError, match='hc_streams=1'):
        TransformerConfig.tiny(hc_streams=1)
    with pytest.raises(ValueError, match='latent_q_rank=8.*latent_rank'):
        TransformerConfig.tiny(latent_q_rank=8)
    cfg = TransformerConfig.tiny(hc_streams=2, n_layers=4)
    batch = {k: np.zeros((4, 32), np.int32) for k in ('tokens', 'targets')}
    for spec, said in ((dict(dp=1, pp=2), 'hc_streams=2 under pipeline'),
                       (dict(dp=1, pp=2, pp_schedule='1f1b'),
                        'hc_streams=2 under pipeline'),
                       (dict(dp=1, sp=2), 'hc_streams=2 under sequence')):
        tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                     spec=ParallelSpec(**spec))
        state = tr.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match=said):
            tr.step(state, batch)


# -- events, counters, training ---------------------------------------------

def test_events_and_the_counter_of_a_training_step():
    cfg = tiny(n_layers=2, remat=True, moe_experts=4, moe_top_k=2,
               dense_lead=1, dense_mlp_dim=48, mlp_dim=16,
               moe_scoring='sigmoid')
    model = TransformerLM(cfg)
    tr = Trainer(model, optax.adamw(1e-3), spec=ParallelSpec(dp=1))
    params = drawn(model.init(jax.random.PRNGKey(0)))
    state = tr.init(None, params=params)
    batch = {k: np.random.RandomState(i).randint(0, 256, (2, 32)).astype(
        np.int32) for i, k in enumerate(('tokens', 'targets'))}
    t_before = time.perf_counter()
    state, metrics = tr.step(state, batch)
    events = {}
    for r in telemetry.get().loop_records():
        if r['t0'] >= t_before:
            events.setdefault(r['name'], []).append(r['tags'])
    plan = events['hc.plan'][0]
    assert (plan['streams'], plan['iters'], plan['clamp'], plan['eps'],
            plan['path']) == (N, 20, [-30.0, 30.0], 1e-6, 'xla')
    # streams of 32 lanes: no kernels, and none of their tags
    assert (plan['block_rows'], plan['sub_rows'],
            plan['vmem_limit_bytes']) == (None, None, None)
    assert 'n dim' in plan['layout']
    assert len(events['hc.plan']) == 1          # once a trace
    layers = events['transformer.layers'][0]
    assert (layers['streams'], layers['dense_lead'],
            layers['expert_layers']) == (N, 1, 1)
    # the streams' bytes in what a layer keeps: [2, 32, 4 * 32] f32
    assert events['transformer.remat'][0]['saved_bytes_per_layer'] \
        == 2 * 32 * N * DIM * 4
    # the counter: a mean of max_j |column sum - 1| over four connections
    assert 0 <= float(metrics['hc_res_col_sum_err']) < 1e-2
    assert {'moe_rows_here', 'moe_load_max', 'moe_load_mean'} <= set(metrics)
    # and the streams train: the loss falls on one batch
    first = float(metrics['loss'])
    for _ in range(4):
        state, metrics = tr.step(state, batch)
    assert float(metrics['loss']) < first


def test_events_of_a_training_step_on_a_shape_the_kernels_take():
    """Streams of 128 lanes and 128 rows a device: ``hc.plan`` says
    ``'pallas'`` with the kernels' plan, once a trace; the four calls are
    in the lowered step inside the scopes their metrics read; the counter
    is the held ``H_res``'s and the loss falls."""
    from autodist_tpu.kernels import hyper_connections as hk
    cfg = TransformerConfig.tiny(
        dim=128, n_heads=4, n_layers=2, positions='rotary', norm='rms',
        gated_mlp=True, gelu='silu', mlp_bias=False, tied_embeddings=False,
        mlp_dim=48, hc_streams=2, remat=True, max_len=64)
    model = TransformerLM(cfg)
    tr = Trainer(model, optax.adamw(1e-3), spec=ParallelSpec(dp=1))
    state = tr.init(None, params=drawn(model.init(jax.random.PRNGKey(0))))
    batch = {k: np.random.RandomState(i).randint(0, 256, (2, 64)).astype(
        np.int32) for i, k in enumerate(('tokens', 'targets'))}
    t_before = time.perf_counter()
    state, metrics = tr.step(state, batch)
    plans = [r['tags'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'hc.plan']
    assert len(plans) == 1                      # once a trace
    how = hk.plan(2 * 64, 2, 128, cfg.dtype)
    assert (plans[0]['path'], plans[0]['block_rows'], plans[0]['sub_rows'],
            plans[0]['vmem_limit_bytes']) == (
        'pallas', 128, 64, how.vmem_limit_bytes)
    assert (plans[0]['streams'], plans[0]['iters']) == (2, 20)
    text = jax.jit(jax.grad(model.loss)).lower(
        state.params, {k: jnp.asarray(v) for k, v in batch.items()}
    ).as_text(debug_info=True)
    for call in ('hc/hc_coeff/jit(_enter_fwd_call)',
                 'hc/hc_coeff/jit(_enter_bwd_call)',
                 'hc/hc_mix/jit(_leave_fwd_call)',
                 'hc/hc_mix/jit(_leave_bwd_call)'):
        assert call in text, call
    for name in ('hc_enter_fwd', 'hc_enter_bwd', 'hc_leave_fwd',
                 'hc_leave_bwd'):
        assert name + '/pallas_call' in text, name
    assert 0 <= float(metrics['hc_res_col_sum_err']) < 1e-2
    first = float(metrics['loss'])
    for _ in range(4):
        state, metrics = tr.step(state, batch)
    assert float(metrics['loss']) < first


def test_a_model_without_experts_counts_the_connections_alone():
    model = TransformerLM(tiny(n_layers=2))
    tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {k: np.zeros((2, 32), np.int32) for k in ('tokens', 'targets')}
    _, metrics = tr.step(state, batch)
    assert set(metrics) == {'loss', 'hc_res_col_sum_err'}
    # the program's own draw is near the plain residual path: the rounds
    # have converged there
    assert float(metrics['hc_res_col_sum_err']) < 1e-3


# -- latent attention's q rank, YaRN and the caller's scale -----------------

def test_latent_attention_takes_the_q_rank_and_the_yarn_scale():
    from autodist_tpu.models.attention import LatentAttention
    yarn = dict(factor=64, original_max_position_embeddings=16, beta_fast=32,
                beta_slow=1, attention_factor=1.0, score_factor=2.0)
    attn = LatentAttention(DIM, 4, 16, 8, 4, 6, q_rank=12, rope_yarn=yarn)
    assert attn.sm_scale == pytest.approx(12 ** -0.5 * 2.0)
    assert isinstance(attn.rope, tuple) and len(attn.rope[0]) == 2
    params = attn.init(jax.random.PRNGKey(0))
    assert {name: p['kernel'].shape for name, p in params.items()
            if 'kernel' in p} == {
        'q_a': (DIM, 12), 'q': (12, 4 * 12), 'kv_a': (DIM, 4 + 16),
        'kv_b': (16, 4 * (8 + 6)), 'out': (4 * 6, DIM)}
    assert params['q_norm']['scale'].shape == (12,)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, DIM))
    y = attn.apply(params, x)
    # a plain computation of the same equations from the same weights
    plain = LatentAttention(DIM, 4, 16, 8, 4, 6, q_rank=12)
    assert plain.sm_scale == pytest.approx(12 ** -0.5)
    assert not np.allclose(y, plain.apply(params, x), atol=1e-4)
    # without the rank q comes straight from the hidden state, as before
    straight = LatentAttention(DIM, 4, 16, 8, 4, 6)
    assert set(straight.init(jax.random.PRNGKey(0))) == {
        'q', 'kv_a', 'kv_norm', 'kv_b', 'out'}


def test_the_latent_kernels_take_the_callers_scale():
    """``flash_attention_latent(sm_scale=)`` in interpret mode against
    the XLA path at the same scale, and ``flash.plan`` records it."""
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.models.attention import LatentAttention
    heads, dims, s = 2, (128, 64, 128), 256
    yarn = dict(factor=64, original_max_position_embeddings=64, beta_fast=32,
                beta_slow=1, attention_factor=1.0, score_factor=2.00474)
    attn = LatentAttention(64, heads, 32, *dims, rope_yarn=yarn)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, s, heads * 192))
    kv = jax.random.normal(keys[1], (1, s, heads * 256))
    c = jax.random.normal(keys[2], (1, s, 128))
    tables = fa.rotary_tables(jnp.arange(s), attn.rope, heads, 64)
    t_before = time.perf_counter()
    got = fa.flash_attention_latent(q, kv, c, heads, dims, tables,
                                    sm_scale=attn.sm_scale, interpret=True)
    want = attn._xla_attention(q, kv, c[..., :64])
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    other = fa.flash_attention_latent(q, kv, c, heads, dims, tables,
                                      interpret=True)
    assert not np.allclose(got, other, atol=1e-2)
    plans = [r['tags'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'flash.plan']
    assert plans[0]['sm_scale'] == pytest.approx(192 ** -0.5 * 2.00474)
    assert plans[1]['sm_scale'] == pytest.approx(192 ** -0.5)
