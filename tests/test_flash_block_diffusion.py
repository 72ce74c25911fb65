"""The block-diffusion mask (PR 45), the flash kernels' third
description of live pairs: the three kernels in interpret mode and the
XLA path's boolean mask against dense masked attention (forward and
every gradient; one tile, two, many; a forward tile twice the backward
pair's (PR 47); grouped heads; with and without rotary positions; f32
and bf16), the plan and its counts, what the mask MEANS for a model's
rows, and the refusals."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.kernels import flash_attention as fa
from autodist_tpu.models.attention import MultiHeadAttention, rotary
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.ring_attention import local_flash_attention


def rules_mask(seq, block):
    """The mask by the four rules, in numpy loops' plain form."""
    rows = 2 * seq
    mask = np.zeros((rows, rows), bool)
    for r in range(rows):
        for c in range(rows):
            rb, cb = (r % seq) // block, (c % seq) // block
            if r < seq and c < seq:
                mask[r, c] = cb == rb
            elif r < seq:
                mask[r, c] = cb < rb
            elif c >= seq:
                mask[r, c] = cb <= rb
    return mask


def dense(q, k, v, mask):
    """softmax(q k^T / sqrt(d) under ``mask``) v in f32, grouped."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   precision='highest') * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p, v, precision='highest')


def rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize('seq,block', [(8, 4), (12, 2), (16, 8), (6, 1)])
def test_the_mask_array_is_the_four_rules(seq, block):
    got = np.asarray(fa.block_diffusion_mask(2 * seq, block))
    np.testing.assert_array_equal(got, rules_mask(seq, block))
    # L^2 + L B live pairs: a quarter of the square and the diagonals
    assert got.sum() == seq * seq + seq * block
    # every row sees something; a clean row never a noised key
    assert got.any(axis=1).all() and not got[seq:, :seq].any()


def case(seq, heads, kv_heads, d, dtype, seed=0, b=2):
    rng = np.random.RandomState(seed)
    rows = 2 * seq
    q = jnp.asarray(rng.randn(b, heads, rows, d), dtype)
    k = jnp.asarray(rng.randn(b, kv_heads, rows, d), dtype)
    v = jnp.asarray(rng.randn(b, kv_heads, rows, d), dtype)
    w = jnp.asarray(rng.randn(b, heads, rows, d), jnp.float32)
    return q, k, v, w


# (seq a copy, block length, tile, heads, kv heads, head dim, dtype):
# one tile a copy, two, many; heads that share a lane block, a head that
# is one; grouped kv heads (a head is then a lane block of its own). A
# tile that is a number is asked for, and all three kernels' alike; a
# pair is the (forward's, backward pair's) TARGETS of a plan that is
# asked nothing: the forward at one tile a copy and at two while dq and
# dkv have two and four (the forward then has its floor of heads a step,
# four where the heads or their group allow). In every case the noised
# rows of the sequence's first block meet no clean key: a noised query
# tile's clean tiles leave its first rows at NEG_INF, whatever the tile.
KERNEL_CASES = [
    (16, 4, 16, 2, 2, 16, jnp.float32),
    (32, 4, 16, 2, 2, 64, jnp.float32),
    (64, 4, 16, 4, 2, 128, jnp.float32),
    (64, 8, 16, 2, 2, 64, jnp.bfloat16),
    (48, 2, 16, 4, 1, 128, jnp.bfloat16),
    (128, 4, 32, 2, 2, 128, jnp.float32),
    (64, 4, (64, 32), 2, 2, 64, jnp.float32),
    (64, 4, (64, 32), 4, 2, 128, jnp.bfloat16),
    (128, 4, (64, 32), 4, 1, 128, jnp.float32),
    (128, 8, (64, 32), 2, 2, 64, jnp.bfloat16),
    (96, 4, (32, 16), 8, 2, 128, jnp.float32),
]


def tiles_of(tile, monkeypatch):
    """(what a call asks for, the forward's tile, the backward pair's);
    a pair of targets is put where ``_plan`` reads its own, and a step's
    budget at ONE head of the forward's tile, what it is at 1024 x 1024."""
    if isinstance(tile, int):
        return tile, tile, tile
    monkeypatch.setattr(fa, '_BD_TARGETS', {
        'fwd': (tile[0], 4), 'dq': (tile[1], 1), 'dkv': (tile[1], 1)})
    monkeypatch.setattr(fa, '_STEP_TILE_ELEMS', tile[0] ** 2)
    return (None,) + tile


@pytest.mark.parametrize('seq,block,tile,heads,kv,d,dtype', KERNEL_CASES)
def test_kernels_against_dense_masked_attention(seq, block, tile, heads, kv,
                                                d, dtype, monkeypatch):
    q, k, v, w = case(seq, heads, kv, d, dtype)
    mask = jnp.asarray(rules_mask(seq, block))
    asked, fwd_tile, bwd_tile = tiles_of(tile, monkeypatch)

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=False, block_q=asked,
                                  block_k=asked, block_diffusion=block)

    def of(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)
    want = dense(q, k, v, mask)
    want_grads = jax.grad(of(lambda *a: dense(*a, mask)), (0, 1, 2))(q, k, v)
    got = kernels(q, k, v)
    got_grads = jax.grad(of(kernels), (0, 1, 2))(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert rel(got, want) < tol
    for g, wg in zip(got_grads, want_grads):
        assert rel(g, wg) < tol
    plan = [r['tags'] for r in telemetry.get().loop_records()
            if r['name'] == 'flash.plan'][-1]
    assert plan['block_diffusion'] == block and plan['window'] is None
    # each kernel's counts are of its own tiles
    for kernel, tile in (('', fwd_tile), ('dq_', bwd_tile),
                         ('dkv_', bwd_tile)):
        n = seq // tile
        assert (plan[kernel + 'block_q'], plan[kernel + 'block_k']) == (
            tile, tile)
        assert (plan[kernel + 'tiles'], plan[kernel + 'live_tiles'],
                plan[kernel + 'masked_tiles']) == (
                    4 * n * n, n * n + 2 * n, 3 * n)
    if asked is None:
        # the budget is one head of the forward's tile: its floor of four
        # heads a step holds, as far as the heads (of a group) go
        assert plan['heads_per_step'] == min(
            4, heads if kv == heads else heads // kv)


@pytest.mark.parametrize('seq,block,tile,heads,kv,d,dtype', [
    (32, 4, 16, 2, 2, 64, jnp.float32),
    (64, 4, 16, 4, 2, 128, jnp.float32),
    (32, 4, 16, 4, 1, 128, jnp.bfloat16),
    (32, 4, (32, 16), 2, 2, 64, jnp.float32),
    (64, 4, (32, 16), 4, 2, 128, jnp.float32),
    (64, 8, (32, 16), 4, 1, 128, jnp.bfloat16),
])
def test_kernels_with_rotary_positions_that_repeat(seq, block, tile, heads,
                                                   kv, d, dtype, monkeypatch):
    """The merged layout, rotary on the tile, positions ``0 .. L - 1``
    twice: against the rotation under XLA and dense masked attention."""
    q, k, v, w = case(seq, heads, kv, d, dtype)
    tile, fwd_tile, bwd_tile = tiles_of(tile, monkeypatch)
    b, rows = q.shape[0], 2 * seq
    pos = jnp.arange(rows) % seq
    mask = jnp.asarray(rules_mask(seq, block))

    def merged(t):
        return jnp.transpose(t, (0, 2, 1, 3)).reshape(b, rows, -1)
    qkv = jnp.concatenate([merged(q), merged(k), merged(v)], axis=-1)
    runs = (heads * d, (heads + kv) * d)
    w = merged(w)

    def want_of(qkv):
        q, k, v = (jnp.transpose(t.reshape(b, rows, -1, d), (0, 2, 1, 3))
                   for t in jnp.split(qkv.astype(jnp.float32), runs, -1))
        return merged(dense(rotary(q, pos, 1e4), rotary(k, pos, 1e4), v,
                            mask))

    def got_of(qkv):
        plan = fa._plan((b, heads, rows, d), False, tile, tile, None, kv,
                        block)
        assert (plan.fwd.block_q, plan.dq.block_q, plan.dkv.block_k) == (
            fwd_tile, bwd_tile, bwd_tile)
        return fa._flash((qkv,), fa.rotary_tables(pos, 1e4, heads, d), heads,
                         kv, False, d ** -0.5, plan, True, None, True, block)

    def of(f):
        return lambda a: jnp.sum(f(a).astype(jnp.float32) * w)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    assert rel(got_of(qkv), want_of(qkv)) < tol
    assert rel(jax.grad(of(got_of))(qkv), jax.grad(of(want_of))(qkv)) < tol


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize('seq,block', [(16, 4), (24, 8)])
def test_the_xla_path_takes_the_mask_as_an_array(seq, block, dtype):
    q, k, v, w = case(seq, 2, 2, 16, dtype)
    mask = fa.block_diffusion_mask(2 * seq, block)

    def xla(q, k, v):
        return local_flash_attention(q, k, v, causal=False, mask=mask)

    def of(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert rel(xla(q, k, v), dense(q, k, v, mask)) < tol
    for g, wg in zip(jax.grad(of(xla), (0, 1, 2))(q, k, v),
                     jax.grad(of(lambda *a: dense(*a, mask)),
                              (0, 1, 2))(q, k, v)):
        assert rel(g, wg) < tol
    with pytest.raises(ValueError, match='causal=False and window=None'):
        local_flash_attention(q, k, v, causal=True, mask=mask)


@pytest.mark.parametrize('n', [1, 2, 3, 16])
def test_tile_counts_equal_a_walk_of_the_square(n):
    live = [(qi, ki) for qi in range(2 * n) for ki in range(2 * n)
            if fa._bd_tile_live(qi, ki, n)]
    crossed = [t for t in live if fa._bd_tile_crossed(*t, n)]
    assert fa._bd_tile_counts(n) == (4 * n * n, len(live), len(crossed))
    # a tile is live iff it holds a live pair, crossed iff a dead one too
    size, block = 8, 4
    mask = rules_mask(n * size, block)
    for qi in range(2 * n):
        for ki in range(2 * n):
            tile = mask[qi * size:(qi + 1) * size, ki * size:(ki + 1) * size]
            assert tile.any() == ((qi, ki) in live)
            if (qi, ki) in live:
                assert (not tile.all()) == ((qi, ki) in crossed)
                if (qi, ki) in crossed:
                    got = fa._bd_mask(qi, ki, size, fa.Bd(block, n))
                    np.testing.assert_array_equal(np.asarray(got), tile)
                    np.testing.assert_array_equal(np.asarray(fa._bd_mask(
                        qi, ki, size, fa.Bd(block, n), True)), tile.T)
    # the inner walks reach every live tile, and the index maps of a dead
    # step ask for a tile the step before it held or the next one needs
    kv_row = fa._kv_row(False, size, size, bd=fa.Bd(block, n))
    for qi in range(2 * n):
        walked = [int(fa._bd_inner(qi, j, n)) for j in range(n + 1)]
        assert {ki for q_, ki in live if q_ == qi} <= set(walked)
        for j, ki in enumerate(walked):
            fetched = int(kv_row(qi, j))
            if (qi, ki) in live:
                assert fetched == ki
            else:
                assert (qi, fetched) in live


def test_the_plan_at_the_published_shape():
    """32 query heads over 4 kv heads of 128 at 2 x 8192 rows. The
    forward: square tiles of 1024, four heads a step, 80 of the square's
    256 tiles live and 24 of them crossed; the backward pair: square
    tiles of 512, four heads a step, 288 of 1024 live (a quarter and the
    diagonals: under 0.30), 48 of them crossed."""
    shape = (2, 32, 16384, 128)
    assert fa.supports(shape, kv_heads=4, block_diffusion=4)
    assert fa.preferred(shape, kv_heads=4, block_diffusion=4)
    plan = fa._plan(shape, False, kv_heads=4, block_diffusion=4)
    assert plan.fwd == fa.Blocks(1024, 1024, 4)
    assert plan.dq == plan.dkv == fa.Blocks(512, 512, 4)
    tags = fa._plan_tags(plan, 16384, False, None, 4)
    assert tags['block_diffusion'] == 4 and tags['band_form'] is None
    assert (tags['tile_q'], tags['tile_k'], tags['tiles'],
            tags['live_tiles'], tags['masked_tiles']) == (
                1024, 1024, 256, 80, 24)
    for kernel in ('dq_', 'dkv_'):
        assert (tags[kernel + 'tile_q'], tags[kernel + 'tile_k']) == (
            512, 512)
        assert (tags[kernel + 'tiles'], tags[kernel + 'live_tiles'],
                tags[kernel + 'masked_tiles']) == (1024, 288, 48)
    assert not any(tags[kernel + 'one_pass'] for kernel in ('', 'dq_',
                                                             'dkv_'))
    assert tags['dq_live_tiles'] / tags['dq_tiles'] < 0.30
    # what the forward multiplies beyond the backward pair's tiles: 11%
    assert tags['live_tiles'] * tags['tile_q'] ** 2 / (
        tags['dq_live_tiles'] * tags['dq_tile_q'] ** 2) == pytest.approx(
            1.11, abs=0.005)
    # a copy no longer than a target is one tile; tiles asked for set
    # all three alike, heads a step too
    short = fa._plan((2, 32, 1024, 128), False, kv_heads=4,
                     block_diffusion=4)
    assert list(short) == [fa.Blocks(512, 512, 4)] * 3
    asked = fa._plan(shape, False, 1024, 1024, kv_heads=4, block_diffusion=4)
    assert list(asked) == [fa.Blocks(1024, 1024, 1)] * 3
    # without the mask the plan and its tags are what they were
    assert fa._plan_tags(fa._plan(shape, True, kv_heads=4), 16384,
                         True)['block_diffusion'] is None


def test_what_runs_where_and_what_is_refused():
    # short copies, blocks that no tile holds whole, a length that is no
    # power of two: the XLA path's
    assert not fa.preferred((2, 4, 256, 128), block_diffusion=4)
    assert fa.supports((2, 4, 256, 128), block_diffusion=4)
    assert not fa.supports((2, 4, 2 * 24, 128), block_diffusion=16)
    assert not fa.supports((2, 4, 1024, 128), block_diffusion=3)
    assert not fa.supports((2, 4, 1023, 128), block_diffusion=1)
    assert not fa.preferred((2, 4, 2 * 4160, 128), block_diffusion=4)
    q, k, v, _ = case(16, 2, 2, 16, jnp.float32)
    with pytest.raises(ValueError, match='third description'):
        fa.flash_attention(q, k, v, causal=True, block_diffusion=4)
    with pytest.raises(ValueError, match='third description'):
        fa.flash_attention(q, k, v, causal=False, window=(4, 0),
                           block_diffusion=4)
    with pytest.raises(ValueError, match='third description'):
        fa.check_window((4, 4), block_diffusion=4)
    with pytest.raises(ValueError, match='positive block length'):
        fa.flash_attention(q, k, v, causal=False, block_diffusion=0)
    with pytest.raises(ValueError, match='not supported'):
        fa.flash_attention(q, k, v, causal=False, block_diffusion=3)
    with pytest.raises(ValueError, match='square tiles'):
        fa.flash_attention(q, k, v, causal=False, block_q=16, block_k=8,
                           block_diffusion=4)
    with pytest.raises(ValueError, match='third description'):
        MultiHeadAttention(32, 2, causal=True, block_diffusion=4)
    with pytest.raises(ValueError, match='no window'):
        TransformerConfig.tiny(block_length=4, positions='rotary', window=4,
                               global_every=2)
    with pytest.raises(ValueError, match='no learned position table'):
        TransformerConfig.tiny(block_length=4)


def tiny_model(**kw):
    d = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
             head_dim=8, max_len=64, causal=True, tied_embeddings=False,
             positions='rotary', rope_theta=1e4, mlp_dim=48, gated_mlp=True,
             gelu='silu', norm='rms', mlp_bias=False, dtype=jnp.float32,
             qk_norm=True, block_length=4)
    d.update(kw)
    return TransformerLM(TransformerConfig(**d))


def stack_rows(model, params, rows):
    """The stack's output rows (before the final norm) for the ``2 L``
    ids ``rows`` of each sequence."""
    x = model._embedded(params, rows)
    tables = model._position_tables(x)
    block_fn = model._block_fn(tables=tables(model.block))
    for i in range(model.cfg.n_layers):
        x, _ = block_fn(jax.tree.map(lambda a, i=i: a[i], params['blocks']),
                        x)
    return x


def test_what_the_mask_means_for_a_models_rows():
    """The noised rows of block ``b`` do not change when clean tokens of
    blocks ``>= b`` or noised tokens of other blocks change; the clean
    rows equal a block-causal forward of ``x_0`` alone."""
    seq, block = 16, 4
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    clean = rng.randint(0, 63, (2, seq))
    noised = np.where(rng.rand(2, seq) < 0.6, 63, clean)
    rows = jnp.asarray(np.concatenate([noised, clean], 1))
    base = stack_rows(model, params, rows)
    b = 2
    mine = slice(b * block, (b + 1) * block)
    # clean tokens of blocks >= b
    other = np.array(rows)
    other[:, seq + b * block:] = rng.randint(0, 63, (2, seq - b * block))
    moved = stack_rows(model, params, jnp.asarray(other))
    np.testing.assert_allclose(moved[:, mine], base[:, mine], atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, (b + 1) * block:seq]
                                 - base[:, (b + 1) * block:seq]))) > 1e-3
    # noised tokens of the other blocks
    other = np.array(rows)
    other[:, :b * block] = rng.randint(0, 63, (2, b * block))
    other[:, (b + 1) * block:seq] = rng.randint(0, 63,
                                                (2, seq - (b + 1) * block))
    moved = stack_rows(model, params, jnp.asarray(other))
    np.testing.assert_allclose(moved[:, mine], base[:, mine], atol=1e-6)
    np.testing.assert_allclose(moved[:, seq:], base[:, seq:], atol=1e-6)
    # ... but its own noised tokens and an earlier clean block move them
    other = np.array(rows)
    other[:, b * block] = (other[:, b * block] + 1) % 63
    assert float(jnp.max(jnp.abs(stack_rows(
        model, params, jnp.asarray(other))[:, mine] - base[:, mine]))) > 1e-3
    other = np.array(rows)
    other[:, seq] = (other[:, seq] + 1) % 63
    assert float(jnp.max(jnp.abs(stack_rows(
        model, params, jnp.asarray(other))[:, mine] - base[:, mine]))) > 1e-3
    # the clean rows: a forward of x_0 alone under the block-causal mask
    # (the same weights in a model without the objective, its attention
    # masked by blocks)
    alone = tiny_model(block_length=None, causal=False)
    x = alone._embedded(params, jnp.asarray(clean))
    block_causal = (jnp.arange(seq)[None, :] // block
                    <= jnp.arange(seq)[:, None] // block)
    for i in range(2):
        layer = jax.tree.map(lambda a, i=i: a[i], params['blocks'])
        attn, blk = alone.block.attn, alone.block
        a = blk.ln1.apply(layer['ln1'], x)
        qkv = attn._qk_normed(layer['attn'], attn.wqkv.apply(
            layer['attn']['qkv'], a))
        q, k, v = (jnp.transpose(t.reshape(2, seq, -1, 8), (0, 2, 1, 3))
                   for t in jnp.split(qkv, attn._runs, axis=-1))
        pos = jnp.arange(seq)
        q, k = rotary(q, pos, 1e4), rotary(k, pos, 1e4)
        o = dense(q, k, v, block_causal)
        x = x + attn.wo.apply(layer['attn']['out'], jnp.transpose(
            o, (0, 2, 1, 3)).reshape(2, seq, -1))
        x = x + blk.mlp.apply(layer['mlp'], blk.ln2.apply(layer['ln2'], x))
    np.testing.assert_allclose(base[:, seq:], x, atol=2e-5)


def test_the_objective_scores_the_noised_rows_with_the_batchs_weights():
    seq = 16
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    clean = rng.randint(0, 63, (2, seq)).astype(np.int32)
    masked = rng.rand(2, seq) < 0.6
    batch = {'tokens': np.where(masked, 63, clean).astype(np.int32),
             'targets': clean,
             'mask': np.where(masked, 2.0, 0.0).astype(np.float32)}
    rows = jnp.asarray(np.concatenate([batch['tokens'], clean], 1))
    h = model.ln_f.apply(params['ln_f'],
                         stack_rows(model, params, rows)[:, :seq])
    logits = model.lm_head.apply(params['lm_head'], h).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.asarray(clean)[..., None], -1)[..., 0]
    want = jnp.sum(nll * batch['mask']) / jnp.sum(batch['mask'])
    np.testing.assert_allclose(model.loss(params, batch), want, rtol=1e-5)
    got_nll, _ = model.per_token_loss_with_aux(params, batch)
    assert got_nll.shape == (2, seq)
    assert model.apply(params, rows).shape == (2, seq, 64)
    # weights, not a 0 / 1 mask: doubling them all changes nothing,
    # doubling one position's does
    twice = dict(batch, mask=2 * batch['mask'])
    np.testing.assert_allclose(model.loss(params, twice), want, rtol=1e-5)
    uneven = np.array(batch['mask'])
    uneven[0, np.argmax(masked[0])] *= 3
    assert abs(float(model.loss(params, dict(batch, mask=uneven)))
               - float(want)) > 1e-5
    # the step's counter and the event
    from autodist_tpu.models.core import model_mode
    with model_mode() as mode:
        model.loss(params, batch)
    np.testing.assert_allclose(mode.counters['bd_mask_rows'],
                               masked.mean() / 2, rtol=1e-6)
    event = [r['tags'] for r in telemetry.get().loop_records()
             if r['name'] == 'transformer.layers'][-1]
    assert (event['objective'], event['block_length'],
            event['rows_per_token']) == ('block_diffusion', 4, 2)


def test_qk_norm_alone():
    """The q/k norm without the objective (a causal model), against the
    norm written out."""
    model = tiny_model(block_length=None)
    params = model.init(jax.random.PRNGKey(0))
    attn = model.block.attn
    layer = jax.tree.map(lambda a: a[0], params['blocks'])['attn']
    assert sorted(layer) == ['k_norm', 'out', 'q_norm', 'qkv']
    assert layer['q_norm']['scale'].shape == (8,)
    layer = dict(layer, q_norm={'scale': jnp.linspace(0.5, 1.5, 8)},
                 k_norm={'scale': jnp.linspace(2.0, 1.0, 8)})
    qkv = jax.random.normal(jax.random.PRNGKey(2), (2, 16, (4 + 4) * 8))
    got = attn._qk_normed(layer, qkv)
    heads = np.asarray(qkv).reshape(2, 16, 8, 8)
    want = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-6)
    want[:, :, :4] *= np.linspace(0.5, 1.5, 8)
    want[:, :, 4:6] *= np.linspace(2.0, 1.0, 8)
    want[:, :, 6:] = heads[:, :, 6:]
    np.testing.assert_allclose(got, want.reshape(2, 16, 64), rtol=1e-5,
                               atol=1e-6)
    # positions come from one place
    np.testing.assert_array_equal(attn.positions(6), np.arange(6))
    np.testing.assert_array_equal(
        tiny_model().block.attn.positions(8), [0, 1, 2, 3, 0, 1, 2, 3])


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_head_rms_norm_and_its_written_out_backward(dtype):
    """The per-head norm over runs of lanes against the norm over heads
    as a reshape would write it: the output, and the gradients that the
    custom rule gives for the input and for the lanes' weights; the
    lanes behind the normed heads pass through, with their cotangent."""
    from autodist_tpu.models.attention import head_rms_norm
    rng = np.random.RandomState(0)
    heads, d, rest = 5, 8, 16
    x = jnp.asarray(rng.randn(2, 6, heads * d + rest), dtype)
    scale = jnp.asarray(1 + 0.3 * rng.randn(heads * d), jnp.float32)
    w = jnp.asarray(rng.randn(*x.shape), jnp.float32)

    def plain(x, scale):
        x32 = x.astype(jnp.float32)
        h = x32[..., :heads * d].reshape(2, 6, heads, d)
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)
        y = jnp.concatenate([h.reshape(2, 6, -1) * scale,
                             x32[..., heads * d:]], -1)
        return y.astype(x.dtype)

    def of(f):
        return lambda x, scale: jnp.sum(f(x, scale).astype(jnp.float32) * w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    got = head_rms_norm(x, scale, d, 1e-6)
    assert got.dtype == x.dtype
    assert rel(got, plain(x, scale)) < tol
    np.testing.assert_array_equal(got[..., heads * d:], x[..., heads * d:])
    for g, want in zip(
            jax.grad(of(lambda x, s: head_rms_norm(x, s, d, 1e-6)),
                     (0, 1))(x, scale),
            jax.grad(of(plain), (0, 1))(x, scale)):
        assert g.shape == want.shape and rel(g, want) < tol
