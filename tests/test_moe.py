"""The expert layer (``models/moe.py``), its grouped products
(``kernels/grouped_matmul.py``, PR 33) and its combine
(``kernels/moe_combine.py``, PR 34): the order of the held pairs against
a stable sort and what a block's checkpoint keeps of it (PR 38), the
kernels in interpret mode
against a loop over the groups and against XLA's scatter-add, the layer
against a loop over the experts (loss and every gradient), the share of
a deployment (the parts that all the shares give add up to the uncut
layer), and that no row is dropped at the worst routing the buffers are
sized for."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.api import Trainer
from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.kernels import grouped_matmul as gm
from autodist_tpu.kernels import moe_combine as mc
from autodist_tpu.models import moe
from autodist_tpu.models.moe import MoeMlp
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM

TILE = gm.TILE_ROWS


def layout(sizes, spare_tiles=1):
    """Rows by group, each group from a tile's start: ``(tile_group,
    live, rows of each group)`` as ``moe._order`` lays them out, with
    ``spare_tiles`` dead tiles after the live ones."""
    tiles = [-(-n // TILE) for n in sizes]
    tile_group = np.repeat(np.arange(len(sizes)), tiles)
    live = len(tile_group)
    tile_group = np.concatenate(
        [tile_group, np.full(spare_tiles, len(sizes) - 1)]).astype(np.int32)
    starts = np.cumsum([0] + tiles[:-1]) * TILE
    return (jnp.asarray(tile_group), jnp.asarray([live], jnp.int32),
            [np.arange(s, s + n) for s, n in zip(starts, sizes)])


# an empty group (twice: first and in the middle), a group of one row,
# every row in one group, groups that end inside a tile and on its edge
_GROUPS = {
    'an_empty_group': [0, 300, 0, 256],
    'a_group_of_one_row': [1, 257, 40],
    'every_row_in_one_group': [0, 0, 700],
    'whole_tiles': [256, 512],
}


@pytest.mark.parametrize('case', sorted(_GROUPS))
def test_grouped_products_match_a_loop_over_the_groups(case):
    sizes = _GROUPS[case]
    tile_group, live, rows = layout(sizes)
    m, k, n, g = TILE * len(tile_group), 24, 40, len(sizes)
    rng = np.random.RandomState(3)
    lhs = rng.randn(m, k).astype('f4')
    rhs = rng.randn(g, k, n).astype('f4')
    dy = rng.randn(m, n).astype('f4')
    acc = rng.randn(g, k, n).astype('f4')
    args = (tile_group, live)
    out = np.asarray(gm.gmm(jnp.asarray(lhs), jnp.asarray(rhs), *args))
    dx = np.asarray(gm.gmm(jnp.asarray(dy), jnp.asarray(rhs), *args,
                           transposed=True))
    dw = np.asarray(gm.gmm_dw(jnp.asarray(lhs), jnp.asarray(dy), *args,
                              jnp.asarray(acc)))
    for e, at in enumerate(rows):
        np.testing.assert_allclose(out[at], lhs[at] @ rhs[e], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(dx[at], dy[at] @ rhs[e].T, rtol=1e-5,
                                   atol=1e-5)
        # a group's padding rows count too: they are its tile's rows
        tile_rows = np.flatnonzero(np.repeat(np.asarray(tile_group), TILE)
                                   [:TILE * int(live[0])] == e)
        np.testing.assert_allclose(
            dw[e], acc[e] + lhs[tile_rows].T @ dy[tile_rows], rtol=1e-4,
            atol=1e-4)
    # the same through jax.lax.ragged_dot, the tests' second opinion
    live_rows = TILE * int(live[0])
    np.testing.assert_allclose(
        np.asarray(gm.reference(jnp.asarray(lhs), jnp.asarray(rhs),
                                *args))[:live_rows], out[:live_rows],
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gm.reference_dw(jnp.asarray(lhs), jnp.asarray(dy), *args,
                                   jnp.asarray(acc))), dw, rtol=1e-4,
        atol=1e-4)


def test_tiles_past_the_live_ones_are_skipped():
    """With no live tile a call computes nothing: ``gmm_dw`` hands its
    accumulator back as it came; with one live tile of three only that
    group's matrix moves."""
    tile_group = jnp.asarray([0, 1, 2], jnp.int32)
    rng = np.random.RandomState(0)
    lhs = jnp.asarray(rng.randn(3 * TILE, 8), jnp.float32)
    rhs = jnp.asarray(rng.randn(3 * TILE, 16), jnp.float32)
    acc = jnp.asarray(rng.randn(3, 8, 16), jnp.float32)
    none = gm.gmm_dw(lhs, rhs, tile_group, jnp.asarray([0], jnp.int32), acc)
    np.testing.assert_array_equal(np.asarray(none), np.asarray(acc))
    one = np.asarray(gm.gmm_dw(lhs, rhs, tile_group,
                               jnp.asarray([1], jnp.int32), acc))
    np.testing.assert_array_equal(one[1:], np.asarray(acc)[1:])
    np.testing.assert_allclose(
        one[0], np.asarray(acc[0] + lhs[:TILE].T @ rhs[:TILE]), rtol=1e-5,
        atol=1e-5)


def ordered(chosen):
    """``(row_of [tokens, groups], live rows)`` of the pairs ``chosen
    [tokens, groups]`` as ``moe._order`` lays them out: rows by group,
    each group from a tile's start, inside a group by token."""
    sizes = chosen.sum(0)
    tiles = -(-sizes // TILE)
    start = (np.cumsum(tiles) - tiles) * TILE
    rank = np.cumsum(chosen, 0) - chosen
    return (np.where(chosen, start[None] + rank, -1).astype(np.int32),
            int(tiles.sum()) * TILE)


def _combine_cases():
    rng = np.random.RandomState(5)
    even = rng.rand(300, 4) < 0.4
    # tokens 128..255 all take group 1, after five of the first block
    # do: a run of 128 rows that starts off a tile of the buffer
    one_block = rng.rand(256, 3) < 0.3
    one_block[:, 1] = False
    one_block[[3, 40, 41, 90, 127], 1] = True
    one_block[128:, 1] = True
    empty_group = rng.rand(300, 4) < 0.5
    empty_group[:, 2] = False
    # 540 rows of one group: token blocks whose runs cross the 256-row
    # tiles, and (in two calls of 512 rows, one onto the other) a chunk
    heavy = rng.rand(600, 2) < 0.9
    return {
        'even_routing': dict(chosen=even),
        'a_block_of_tokens_to_one_expert': dict(chosen=one_block),
        'an_expert_with_no_row': dict(chosen=empty_group),
        'no_live_row_at_all': dict(chosen=np.zeros((200, 3), bool)),
        'runs_straddle_a_tile_and_a_chunk': dict(chosen=heavy, calls=512),
        'weights_bf16_cannot_hold': dict(chosen=even, dtype=jnp.bfloat16,
                                         dim=128, fine_weights=True),
        'dim_no_multiple_of_128': dict(chosen=even, dim=40),
    }


@pytest.mark.parametrize('case', sorted(_combine_cases()))
def test_combine_matches_the_scatter_add(case):
    """``moe_combine`` against ``out.at[token].add(weight * row)``, with
    the layer's weights and with weights of one (the backward's), the
    rows past the live ones NaN: no window reaches them."""
    spec = _combine_cases()[case]
    chosen, dim = spec['chosen'], spec.get('dim', 32)
    dtype = spec.get('dtype', jnp.float32)
    row_of, live = ordered(chosen)
    rng = np.random.RandomState(7)
    rows = rng.randn(live + 2 * TILE, dim).astype('f4')
    rows[live:] = np.nan
    rows = jnp.asarray(rows, dtype)
    weight = rng.rand(*chosen.shape).astype('f4')
    if spec.get('fine_weights'):
        # 24 significant bits, of which bf16 keeps 8
        weight = (1.0 / 3.0 + weight * 2.0 ** -12).astype('f4')
        assert np.any(weight != np.asarray(
            jnp.asarray(weight, jnp.bfloat16), 'f4'))
    for w in (jnp.asarray(weight), None):
        want = np.asarray(mc.reference(rows, jnp.asarray(row_of), w,
                                       out_dtype=jnp.float32))
        # in calls of `calls` rows, the first over a sum nobody wrote
        calls = spec.get('calls', live)
        got = jnp.full(want.shape, np.nan, jnp.float32)
        for first in range(0, max(live, 1), max(calls, 1)):
            at = row_of - first
            at = np.where((at >= 0) & (at < calls), at, -1)
            got = mc.combine(rows[first:], jnp.asarray(at), w,
                             limit=jnp.int32(min(calls, live - first)),
                             onto=got, fresh=first == 0)
        assert got.shape == want.shape and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6)
        if calls == live:       # and in one call that carries no sum
            alone = mc.combine(rows, jnp.asarray(row_of), w,
                               limit=jnp.int32(live), out_dtype=jnp.float32)
            np.testing.assert_array_equal(np.asarray(alone), np.asarray(got))
    if not chosen.any():
        assert not np.asarray(got).any()


def test_layer_walks_its_chunks_in_passes(monkeypatch):
    """With chunks of one tile and passes of two chunks the layer's rows
    take several passes of the combine, each onto the sum so far, and a
    token block's run lies across two of them: output and every
    gradient are still those of the loop over the experts."""
    monkeypatch.setattr(moe, 'CHUNK_TILES', 1)
    monkeypatch.setattr(moe, 'PASS_CHUNKS', 2)
    layer = MoeMlp(32, 16, 8, top_k=3, held=(2, 4), act=jax.nn.silu,
                   gated=True)
    p = layer.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 300, 32))

    def plain(p, x):
        return whole_layer(p, x, 2, 4, 3, True, jax.nn.silu)
    y, _, stats = layer.apply(p, x)
    assert float(stats[0]) > 2 * 2 * TILE      # more than two passes
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain(p, x)),
                               atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(layer.apply(p, x)[0])),
                   argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(plain(p, x))),
                    argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_the_layer_leaves_its_plan_in_the_ring(events_of):
    """One ``moe.plan`` point event a trace of the layer, none for a
    call that is not traced again: the buffer a pass of the combine
    holds, and which of the two movements is a kernel."""
    layer = MoeMlp(32, 16, 8, top_k=2, held=(0, 4))
    p = layer.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 40, 32))
    run = jax.jit(lambda p, x: layer.apply(p, x)[0])
    with events_of('moe.plan') as plans:
        run(p, x)
        run(p, x)
    assert len(plans) == 1
    rows = moe.buffer_rows(80, 2, 4)
    # of the order the checkpoint keeps the sorted pairs [t * k], token
    # and weight by row, a tile's expert, the live tiles, row_of [t,
    # held] and the held experts' sizes
    assert plans[0]['tags'] == dict(
        rows=rows, chunk_tiles=moe.CHUNK_TILES, pass_chunks=1,
        token_block=mc.TOKEN_BLOCK, window_rows=mc.WINDOW_ROWS,
        combine='pallas', gather='xla', buffer_bytes=rows * 32 * 4,
        order='sort', order_scatters=0,
        order_saved_bytes=4 * (80 * 2 + 2 * rows + rows // TILE + 1
                               + 80 * 4 + 4),
        scoring='softmax', bias=False, scale=1.0, shared=0)


def plain_order(local, weights, held):
    """``moe._order`` in numpy: a stable sort of the held pairs by
    expert (so inside an expert by token), each expert's run from a
    tile's start."""
    t, k = local.shape
    rows = moe.buffer_rows(t, k, held)
    at_token, at_choice = np.divmod(np.arange(t * k), k)
    is_held = ((local >= 0) & (local < held)).ravel()
    at_token, at_choice = at_token[is_held], at_choice[is_held]
    expert = local.ravel()[is_held]
    by_expert = np.argsort(expert, kind='stable')
    sizes = np.bincount(expert, minlength=held)
    tiles = -(-sizes // TILE)
    start = (np.cumsum(tiles) - tiles) * TILE
    first = np.cumsum(sizes) - sizes
    out = dict(
        # the pairs as the sort leaves them: the held by expert, then
        # the others as they came
        pairs=np.concatenate([np.flatnonzero(is_held)[by_expert],
                              np.flatnonzero(~is_held)]),
        token=np.zeros(rows, int),
        weight=np.zeros(rows, 'f4'), row_of=np.full((t, held), -1),
        token_weight=np.zeros((t, held), 'f4'), sizes=sizes,
        live=np.asarray([tiles.sum()]),
        tile_group=np.concatenate([
            np.repeat(np.arange(held), tiles),
            np.full(rows // TILE - tiles.sum(), held - 1)]))
    for place, pair in enumerate(by_expert):
        e, i, j = expert[pair], at_token[pair], at_choice[pair]
        row = start[e] + place - first[e]
        out['row_of'][i, e] = row
        out['token'][row] = i
        out['weight'][row] = out['token_weight'][i, e] = weights[i, j]
    return out


def _order_cases():
    rng = np.random.RandomState(11)

    def choices(t, k, n, first):      # k distinct experts of n a token
        return np.argsort(rng.rand(t, n), axis=1)[:, :k] - first
    one = np.stack([np.full(600, 2), np.full(600, -3)], axis=1)
    return {
        'even_load': (choices(300, 2, 8, 2), 4),
        # test_no_row_is_dropped_at_the_worst_routing's: one expert's run
        # is every token, and every pair of every token is held
        'every_token_to_one_held_expert': (one, 4),
        'every_pair_held': (choices(600, 2, 2, 0), 2),
        'no_pair_held': (np.where(choices(200, 2, 8, 0) < 4, -1, 7), 3),
        'held_less_than_top_k': (choices(260, 4, 8, 3), 2),
        'held_is_n_experts': (choices(256, 3, 8, 0), 8),
        'tokens_no_multiple_of_128': (choices(333, 2, 8, 1), 5),
    }


@pytest.mark.parametrize('case', sorted(_order_cases()))
def test_order_is_a_stable_sort_of_the_held_pairs(case):
    local, held = _order_cases()[case]
    weights = np.random.RandomState(13).rand(*local.shape).astype('f4')
    want = plain_order(local, weights, held)
    got = jax.jit(moe._order, static_argnums=2)(
        jnp.asarray(local, jnp.int32), jnp.asarray(weights), held)
    assert set(got) == set(want)
    for name in sorted(want):
        assert got[name].shape == want[name].shape, name
        np.testing.assert_array_equal(np.asarray(got[name]), want[name],
                                      err_msg=name)
    assert {name for name, x in got.items() if x.dtype == jnp.float32} \
        == {'weight', 'token_weight'}


# -- what a block's checkpoint keeps of the order (PR 38) ------------------

def nested(jaxpr, recomputed=False):
    """``(equation, whether it lies in the body of a checkpoint
    equation)`` of ``jaxpr`` and of every jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn, recomputed
        inside = recomputed or eqn.primitive.name == 'remat2'
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    yield from nested(sub, inside)


def expert_model(**over):
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2,
                                 moe_experts=8, moe_held=4, moe_top_k=2,
                                 **over)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (2, 32)),
             'targets': rng.randint(0, 256, (2, 32))}
    return model, model.init(jax.random.PRNGKey(0)), batch


@pytest.mark.parametrize('scan', [False, True], ids=['unrolled', 'scanned'])
def test_the_recomputed_block_makes_no_order(scan):
    """Under ``remat=True`` the body of every checkpoint equation of
    ``grad(loss)`` (the block run again, and its backward) holds none of
    the order's making: no sort but the one that takes the rows'
    gradient back to the pairs (and ``top_k``'s own), no scatter, no
    gather of integers (what ``take_along_axis`` of the rank was) or
    out of a vector (what transposed the rows' scatter): the checkpoint
    keeps the order by ``moe.CHECKPOINT_NAMES``. The ``scatter-add``
    there transposes ``top_k``'s gather. Nowhere is a number scattered
    by row."""
    model, params, batch = expert_model(remat=True, scan_layers=scan)
    t_before = time.perf_counter()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.loss(p, batch)))(params)
    found = list(nested(jaxpr.jaxpr))
    assert any(recomputed for _, recomputed in found)
    bodies = [e for e, recomputed in found if recomputed]
    pairs = 2 * 32 * model.cfg.moe_top_k
    sorts = [[v.aval.shape for v in e.invars] for e in bodies
             if e.primitive.name == 'sort' and e.invars[0].aval.size == pairs]
    assert sorts == [[(pairs,), (pairs,)]] * (1 if scan else 2)
    assert not [e for e in bodies if e.primitive.name == 'gather' and (
        jnp.issubdtype(e.invars[0].aval.dtype, jnp.integer)
        or e.invars[0].aval.ndim == 1)]
    assert not [e for e, _ in found if e.primitive.name == 'scatter']
    named = {e.params['name'] for e, _ in found
             if e.primitive.name == 'name'}
    assert {n for n in named if n.startswith('moe_')} \
        == set(moe.CHECKPOINT_NAMES)
    events = [r['tags'] for r in telemetry.get().loop_records()
              if r['t0'] >= t_before and r['name'] == 'transformer.remat']
    assert [e['saved'] for e in events] == [
        list(fa.CHECKPOINT_NAMES + moe.CHECKPOINT_NAMES)]


@pytest.mark.parametrize('scan', [False, True], ids=['unrolled', 'scanned'])
def test_gradients_are_the_same_with_the_order_kept(scan):
    """Every leaf, the routers' among them, under ``remat=True`` (the
    order kept, the router run again) and under ``remat=False``."""
    grads = {}
    for remat in (True, False):
        model, params, batch = expert_model(remat=remat, scan_layers=scan)
        grads[remat] = jax.jit(jax.grad(
            lambda p: model.loss(p, batch)))(params)
    leaves = jax.tree_util.tree_leaves_with_path(grads[True])
    assert any('router' in jax.tree_util.keystr(path) and np.any(leaf)
               for path, leaf in leaves)
    for (path, a), b in zip(leaves, jax.tree.leaves(grads[False])):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_a_dense_block_is_kept_as_it_was(monkeypatch):
    """A block without an expert layer gives none of the order's names:
    ``grad(loss)`` of a dense model under ``remat=True`` is the same
    jaxpr whether or not the policy lists them."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2, remat=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {'tokens': np.zeros((2, 32), np.int32),
             'targets': np.ones((2, 32), np.int32)}
    assert model._saved_names() == fa.CHECKPOINT_NAMES

    def text():
        return re.sub(r' at 0x[0-9a-f]+', '', str(jax.make_jaxpr(
            jax.grad(lambda p: model.loss(p, batch)))(params)))
    as_it_is = text()
    monkeypatch.setattr(
        TransformerLM, '_saved_names',
        lambda self: fa.CHECKPOINT_NAMES + moe.CHECKPOINT_NAMES)
    assert text() == as_it_is and 'remat2' in as_it_is


# The router's variants (DeepSeek-V3's): sigmoid scores, the experts
# chosen by score + a bias that is NOT zero and weighed by the score
# alone (so that choosing and weighing by one of the two is told apart),
# a scale on the weights, a shared expert beside the routed ones.
SIGMOID = dict(scoring='sigmoid', select_bias=True, scale=2.448, shared=24)


def with_bias(p, key=7):
    if 'select_bias' in p:
        p = dict(p, select_bias=0.3 * jax.random.normal(
            jax.random.PRNGKey(key), p['select_bias'].shape))
    return p


def whole_layer(p, x, first, held, top_k, gated, act, scoring='softmax',
                select_bias=False, scale=1.0, shared=0, by_bias=True,
                weigh_biased=False):
    """The plain form: every held expert for every token, weighted."""
    t = x.reshape(-1, x.shape[-1])
    logits = t @ p['router']['kernel']
    if scoring == 'softmax':
        vals, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
        w = vals / vals.sum(-1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores + p['select_bias']
        _, idx = jax.lax.top_k(biased if by_bias else scores, top_k)
        vals = jnp.take_along_axis(biased if weigh_biased else scores, idx,
                                   -1)
        w = scale * vals / (vals.sum(-1, keepdims=True) + 1e-20)
    out = 0
    for e in range(first, first + held):
        we = jnp.sum(jnp.where(idx == e, w, 0), -1)
        up = p['up'][e - first]
        h = act(t @ up[:, 0]) * (t @ up[:, 1]) if gated else act(t @ up)
        out = out + we[:, None] * (h @ p['down'][e - first])
    if shared:
        up = p['shared']['up']['kernel']
        out = out + (act(t @ up[:, 0]) * (t @ up[:, 1])) \
            @ p['shared']['down']['kernel']
    return out.reshape(x.shape)


@pytest.mark.parametrize('gated,held,router', [
    (True, (2, 4), {}), (False, (0, 8), {}), (True, (7, 1), {}),
    (True, (2, 4), SIGMOID), (True, (0, 8), dict(SIGMOID, shared=0))],
    ids=['gated_2_4', 'plain_all', 'gated_last', 'sigmoid_bias_shared',
         'sigmoid_bias_all'])
def test_layer_matches_a_loop_over_its_experts(gated, held, router):
    act = jax.nn.silu if gated else jax.nn.gelu
    layer = MoeMlp(32, 16, 8, top_k=2, held=held, act=act, gated=gated,
                   **router)
    p = with_bias(layer.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))

    def plain(p, x, **how):
        return whole_layer(p, x, *held, 2, gated, act, **router, **how)
    y, aux, stats = layer.apply(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain(p, x)),
                               atol=2e-5)
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(layer.apply(p, x)[0])),
                   argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(plain(p, x))),
                    argnums=(0, 1))(p, x)
    if router:
        # the bias selects and takes no gradient; the plain form's goes
        # nowhere either (top_k's indices carry none)
        assert not np.any(np.asarray(got[0]['select_bias']))
        assert float(aux) == 0          # no Switch loss of sigmoid scores
        # choosing without the bias, or weighing with it, is another layer
        for other in (dict(by_bias=False), dict(weigh_biased=True)):
            assert float(jnp.max(jnp.abs(y - plain(p, x, **other)))) > 1e-2
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    # the counters: rows held here, and the largest load of a held expert
    logits = x.reshape(-1, 32) @ p['router']['kernel']
    _, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + p['select_bias']
                           if router else jax.nn.softmax(logits, -1), 2)
    local = np.asarray(idx) - held[0]
    loads = np.bincount(local[(local >= 0) & (local < held[1])],
                        minlength=held[1])
    np.testing.assert_array_equal(np.asarray(stats),
                                  [loads.sum(), loads.max()])


@pytest.mark.parametrize('router', [{}, SIGMOID],
                         ids=['softmax', 'sigmoid_bias_shared'])
def test_the_shares_of_a_deployment_add_up_to_the_whole_layer(router):
    """The ``model-configs`` guide's §4: experts ``0..3`` and ``4..7`` of
    the same weights, each share computing its own part, add up to what
    the uncut layer gives; so do eight shares of one expert. A shared
    expert is whole in every share: the shares' ROUTED parts and the
    shared expert counted once are the layer."""
    whole = MoeMlp(32, 16, 8, top_k=3, act=jax.nn.silu, gated=True, **router)
    p = with_bias(whole.init(jax.random.PRNGKey(4)))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    want = whole_layer(p, x, 0, 8, 3, True, jax.nn.silu, **router)
    np.testing.assert_allclose(np.asarray(whole.apply(p, x)[0]),
                               np.asarray(want), atol=2e-5)
    shared = whole.shared.apply(p['shared'], x) if router else 0
    for count in (4, 1):
        total, rows = shared, 0
        for first in range(0, 8, count):
            share = MoeMlp(32, 16, 8, top_k=3, held=(first, count),
                           act=jax.nn.silu, gated=True, **router)
            ps = dict(p, up=p['up'][first:first + count],
                      down=p['down'][first:first + count])
            y, _, stats = share.apply(ps, x)
            total, rows = total + (y - shared), rows + float(stats[0])
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   atol=3e-5)
        assert rows == 2 * 24 * 3     # every pair is some share's row


@pytest.mark.parametrize('parallel', [
    dict(dp=8), dict(dp=2, ep=2, tp=2), dict(dp=1, ep=4, tp=1)],
    ids=['dp8', 'dp2_ep2_tp2', 'ep4'])
def test_layer_on_shards_matches_the_layer_on_one_device(parallel):
    """Under a mesh that shards the tokens (data), the experts (expert)
    or their hidden units (model) the layer runs its kernels on each
    device's shard in a manual region and adds the parts up: output,
    every gradient, the auxiliary loss and the counters are those of
    the layer on one device."""
    from autodist_tpu.parallel.axes import ParallelSpec, sharding_ctx
    layer = MoeMlp(32, 16, 8, top_k=3, held=(2, 4), act=jax.nn.silu,
                   gated=True)
    p = layer.init(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 12, 32))

    def run(p, x):
        y, aux, stats = layer.apply(p, x)
        return jnp.sum(jnp.sin(y)) + 3.0 * aux, (y, aux, stats)
    want, want_grads = jax.value_and_grad(run, argnums=(0, 1),
                                          has_aux=True)(p, x)
    spec = ParallelSpec(**parallel)
    mesh = spec.build_mesh(devices=jax.devices()[:8])
    with sharding_ctx(mesh, spec.rules):
        got, got_grads = jax.jit(jax.value_and_grad(
            run, argnums=(0, 1), has_aux=True))(p, x)
    for a, b in zip(jax.tree.leaves((got, got_grads)),
                    jax.tree.leaves((want, want_grads))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_a_mesh_that_does_not_divide_the_experts_is_refused():
    from autodist_tpu.parallel.axes import ParallelSpec, sharding_ctx
    layer = MoeMlp(32, 16, 8, top_k=2, held=(0, 3))
    p = layer.init(jax.random.PRNGKey(0))
    spec = ParallelSpec(dp=4, ep=2)
    mesh = spec.build_mesh(devices=jax.devices()[:8])
    with sharding_ctx(mesh, spec.rules), \
            pytest.raises(ValueError, match='do not divide'):
        jax.jit(lambda p, x: layer.apply(p, x)[0])(p, jnp.zeros((4, 8, 32)))


@pytest.mark.parametrize('held,top_k', [((0, 2), 2), ((4, 4), 4),
                                        ((0, 3), 5)])
def test_no_row_is_dropped_at_the_worst_routing(held, top_k):
    """A router that sends every token to the held experts: the buffer
    is sized for ``tokens x min(top_k, held)`` rows and a tile of
    padding an expert, every one of them is live, and the layer still
    equals the plain form. (The capacity tensor this replaces dropped
    what overflowed ``capacity_factor x tokens x top_k / experts``.)"""
    first, count = held
    layer = MoeMlp(32, 16, 8, top_k=top_k, held=held, act=jax.nn.silu,
                   gated=True)
    p = layer.init(jax.random.PRNGKey(2))
    bias = jnp.where((jnp.arange(8) >= first)
                     & (jnp.arange(8) < first + count), 50.0, 0.0)
    # every token's logits favour the held experts by 50
    p['router']['kernel'] = 0.1 * p['router']['kernel'] + bias[None, :] \
        * jnp.ones((32, 1)) / 32
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (3, 200, 32))
    tokens = 600
    y, _, stats = layer.apply(p, x)
    assert float(stats[0]) == tokens * min(top_k, count)
    assert moe.buffer_rows(tokens, top_k, count) >= \
        tokens * min(top_k, count) + count * (TILE - 1)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(whole_layer(p, x, first, count, top_k, True,
                               jax.nn.silu)), atol=5e-5)
    # and one expert takes all of them
    assert float(stats[1]) == tokens


def test_buffer_is_whole_chunks_at_the_worst_case():
    chunk = moe.CHUNK_TILES * TILE
    assert moe.buffer_rows(32768, 8, 16) == 262144 + 4096 == 65 * chunk
    assert moe.buffer_rows(600, 4, 2) == chunk      # 1200 + 512 rows
    assert moe.buffer_rows(10, 8, 64) % chunk == 0


def test_counters_are_read_back_with_the_loss():
    """The step returns the expert layers' counters beside the loss, and
    ``fit`` leaves them in the loop ring as ``trainer.counters``."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2,
                                 moe_experts=4, moe_held=2, moe_top_k=2)
    trainer = Trainer(TransformerLM(cfg), optax.sgd(0.1))
    state = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}
    state, metrics = trainer.step(state, batch)
    assert set(metrics) == {'loss', 'moe_rows_here', 'moe_load_max',
                            'moe_load_mean'}
    rows = float(metrics['moe_rows_here'])
    assert 0 < rows <= 8 * 32 * 2
    assert float(metrics['moe_load_mean']) == pytest.approx(rows / 2)
    assert float(metrics['moe_load_max']) >= rows / 2
    state, history = trainer.fit(state, [batch, batch], steps=2)
    events = [r for r in telemetry.get().loop_records()
              if r['name'] == 'trainer.counters']
    assert len(events) >= 2 and events[-1]['step'] == 3
    assert set(events[-1]['tags']) == {'moe_rows_here', 'moe_load_max',
                                       'moe_load_mean', 'trainer'}
    # and the copies back are under a span of their own, after the loss's
    spans = [r for r in telemetry.get().loop_records()
             if r['name'] == 'trainer.counters_readback'
             and r['tags']['trainer'] == events[-1]['tags']['trainer']]
    assert [r['step'] for r in spans] == [2, 3]
    assert spans[-1]['t0'] + spans[-1]['dur'] <= events[-1]['t0']
    # a dense model's step returns the loss alone, as before
    dense = Trainer(TransformerLM(TransformerConfig.tiny(
        dtype=jnp.float32, n_layers=1)), optax.sgd(0.1))
    _, metrics = dense.step(dense.init(jax.random.PRNGKey(0)), batch)
    assert set(metrics) == {'loss'}
