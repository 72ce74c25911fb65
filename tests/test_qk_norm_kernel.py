"""The per-head q/k norm as a kernel pair (PR 46, ``kernels/qk_norm.py``):
the Pallas kernels in interpret mode, through
``MultiHeadAttention._qk_normed``, against ``head_rms_norm``'s
``jax.numpy`` form and against the plain reshape-into-heads ``RMSNorm``,
output and the gradients of the projection's output and of the two
weights, over several row blocks, normed lane steps and copied ones, f32
and bf16; what decides between the two forms;
a dp and a dp x tp mesh against one device; and the model-level cases of
``test_flash_block_diffusion.py`` on the kernel path."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu.models.attention as attn_mod
from autodist_tpu.api import Trainer
from autodist_tpu.kernels import qk_norm as qn
from autodist_tpu.models.attention import MultiHeadAttention
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec

EPS = 1e-6
ROWS = (2, 64)


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 32 at tiles of 256 lanes (64 at one head of 128) in
    passes of 16: 128 rows are four blocks of two passes each, heads of
    128 go two a tile where both counts are even."""
    monkeypatch.setattr(qn, 'ROWS', 32)
    monkeypatch.setattr(qn, 'SUB', 16)
    monkeypatch.setattr(qn, 'MAX_TILE', 256)


def layer(h, kv, d, seed=0):
    """An attention layer with the norm, its two weights away from one,
    and a projection's output for :data:`ROWS`."""
    attn = MultiHeadAttention(32, h, head_dim=d, num_kv_heads=kv,
                              qk_norm=True, norm_eps=EPS)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    weights = {name: {'scale': 1 + 0.5 * jax.random.normal(key, (d,))}
               for name, key in zip(('q_norm', 'k_norm'), k)}
    qkv = jax.random.normal(k[2], ROWS + ((h + 2 * kv) * d,))
    return attn, weights, qkv, k[3]


def plain(attn, weights, qkv):
    """The norm over heads as a reshape writes it, in f32."""
    h, kv, d = attn.num_heads, attn.num_kv_heads, attn.head_dim
    x = qkv.astype(jnp.float32).reshape(ROWS + (h + 2 * kv, d))
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    scale = jnp.concatenate([
        jnp.broadcast_to(weights['q_norm']['scale'], (h, d)),
        jnp.broadcast_to(weights['k_norm']['scale'], (kv, d))])
    out = jnp.concatenate([normed[..., :h + kv, :] * scale,
                           x[..., h + kv:, :]], axis=-2)
    return out.reshape(qkv.shape).astype(qkv.dtype)


def through(form, attn):
    """The norm as ``_qk_normed`` runs it: ``'pallas'`` (handed the
    shape the flash kernels would run on), ``'xla'`` (handed none) or
    :func:`plain`."""
    local = ROWS[:1] + (attn.num_heads, ROWS[1], attn.head_dim)
    if form == 'plain':
        return lambda weights, qkv: plain(attn, weights, qkv)
    return lambda weights, qkv: attn._qk_normed(
        weights, qkv, local if form == 'pallas' else None)


def value_and_grads(form, attn, weights, qkv, key):
    """The value and the gradients (weights, qkv) of ``sum(out *
    cotangent)``, the cotangent other numbers for every element."""
    ct = jax.random.normal(key, qkv.shape)

    def loss(weights, qkv):
        out = through(form, attn)(weights, qkv)
        assert out.dtype == qkv.dtype and out.shape == qkv.shape
        return jnp.sum(out.astype(jnp.float32) * ct)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(weights, qkv)


def distance(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (q heads, kv heads, head dim): (rows a block, lanes a tile, normed lane
# steps of all). SDAR's counts (v from the 19th step on); as many kv
# heads as q heads; three normed heads of four (one head a tile); heads
# of two lane blocks, one a tile
HEADS = {(32, 4, 128): (32, 256, 18, 20), (4, 4, 128): (32, 256, 4, 6),
         (2, 1, 128): (64, 128, 3, 4), (2, 1, 256): (32, 256, 3, 4),
         (4, 4, 256): (32, 256, 8, 12)}


@pytest.mark.parametrize('dtype,limit,plain_limit', [
    (jnp.float32, 2e-6, 2e-6), (jnp.bfloat16, 2e-4, 6e-3)],
    ids=['f32', 'bf16'])
@pytest.mark.parametrize('h,kv,d', sorted(HEADS),
                         ids=['%dq%dkv_of_%d' % case
                              for case in sorted(HEADS)])
def test_the_kernels_are_the_head_norm_and_its_gradients(
        small_blocks, events_of, h, kv, d, dtype, limit, plain_limit):
    """Output and the gradients of the projection's output, ``q_norm``
    and ``k_norm`` against ``head_rms_norm`` (both compute in f32 from
    the same numbers, so bf16 differs by the last bit of a rounded output
    or cotangent at most) and against the reshape into heads (which in
    bf16 rounds its cotangent on another path); v's lanes and v's
    cotangent come through bit for bit; the trace says which form it
    took."""
    attn, weights, qkv, key = layer(h, kv, d)
    qkv = qkv.astype(dtype)
    block, tile, normed_steps, steps = HEADS[h, kv, d]
    assert qn.plan(128, (h + 2 * kv) * d, h + kv, d) == qn.Plan(
        block, 16, steps, tile, d, normed_steps)
    with events_of('qk_norm.plan') as events:
        got = value_and_grads('pallas', attn, weights, qkv, key)
    assert [e['tags'] for e in events] == [dict(
        path='pallas', heads=h, kv_heads=kv, head_dim=d, block_rows=block,
        tile_lanes=tile, passes_v='copy')]
    for form, bound in (('xla', limit), ('plain', plain_limit)):
        want = value_and_grads(form, attn, weights, qkv, key)
        assert abs(float(got[0]) - float(want[0])) \
            <= 1e-4 * abs(float(want[0])), form
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                                jax.tree.leaves(want[1])):
            assert g.shape == w.shape and g.dtype == w.dtype, (form, path)
            assert distance(g, w) < bound, (form, path)
    v = (h + kv) * d
    out = through('pallas', attn)(weights, qkv)
    np.testing.assert_array_equal(out[..., v:], qkv[..., v:])
    ct = jax.random.normal(key, qkv.shape)
    np.testing.assert_array_equal(got[1][1][..., v:],
                                  ct.astype(dtype)[..., v:])


def test_the_output_is_the_jax_numpy_form_to_the_last_bit_in_bf16(
        small_blocks):
    """One head is one lane block and the sum over it one reduction on
    either side: at SDAR's head counts the rounded outputs are equal."""
    attn, weights, qkv, _ = layer(32, 4, 128)
    qkv = qkv.astype(jnp.bfloat16)
    got, want = (np.asarray(through(form, attn)(weights, qkv), np.float32)
                 for form in ('pallas', 'xla'))
    assert np.mean(got != want) < 2e-3
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize('pass_', ['forward', 'backward'])
def test_nothing_crosses_a_row_or_a_head(small_blocks, pass_):
    """An output (a gradient of the projection's output) turns on its own
    row's lanes of its own head and nothing else: moving one element of
    a normed head moves that head's 128 lanes of that row, one of v's
    moves itself in the forward and nothing in the backward."""
    h, kv, d = 2, 1, 128
    attn, weights, qkv, key = layer(h, kv, d)
    ct = jax.random.normal(key, qkv.shape)
    form = through('pallas', attn)

    def run(qkv):
        if pass_ == 'forward':
            return form(weights, qkv)
        return jax.vjp(lambda x: form(weights, x), qkv)[1](ct)[0]
    base = run(qkv)
    # (batch row, row, lane): rows at a pass's and a block's edges; the
    # last lane is v's
    for b, t, lane in ((0, 0, 0), (1, 15, 127), (0, 32, 128), (1, 63, 383),
                       (0, 31, 511)):
        moved = np.array(base != run(qkv.at[b, t, lane].add(1.0)))
        first = lane // d * d
        if lane < (h + kv) * d:
            assert moved[b, t, first:first + d].all()
            moved[b, t, first:first + d] = False
        elif pass_ == 'forward':
            assert moved[b, t, lane]
            moved[b, t, lane] = False
        assert not moved.any(), (b, t, lane)


# name: (rows, width, normed heads, head dim): why no kernels
UNSUPPORTED = {
    'heads_of_half_a_lane_block': (128, 8 * 64, 6, 64),
    'heads_of_a_block_and_a_half': (128, 4 * 192, 3, 192),
    'rows_that_are_no_whole_pass': (40, 512, 3, 128),
    'lanes_that_are_no_whole_heads': (128, 576, 3, 128),
    'more_normed_heads_than_heads': (128, 512, 5, 128),
}


@pytest.mark.parametrize('case', sorted(UNSUPPORTED))
def test_shapes_the_kernels_do_not_take(small_blocks, case):
    """``supports`` false, and ``head_norm`` raises: the caller keeps its
    own form (the layer's, below)."""
    rows, width, heads, d = UNSUPPORTED[case]
    assert not qn.supports(rows, width, heads, d)
    if heads * d > width:
        return
    with pytest.raises(ValueError, match='ask supports'):
        qn.head_norm(jnp.zeros((rows, width)), jnp.ones((heads * d,)), d,
                     EPS)


def test_supports_at_the_published_shape():
    """SDAR-30B-A3B's projection, ``[2, 16384, 5120]`` with 32 + 4 heads
    of 128 normed: ten lane steps of four heads (36 and 40 share no
    larger count), nine of them normed; rows of any multiple of 16 split
    into smaller blocks; a head wider than a tile is the tile, of fewer
    rows."""
    how = qn.plan(32768, 5120, 36, 128)
    assert (how.steps, how.tile, how.head_lanes, how.normed_steps) \
        == (10, 512, 128, 9)
    assert how.block_rows * how.tile == qn.ROWS * qn.MAX_TILE
    assert how.block_rows % how.sub_rows == 0
    assert qn.plan(3 * 16 * 5, 5120, 36, 128).block_rows == 16
    wide = qn.plan(32768, 4 * 2048, 3, 2048)
    assert (wide.tile, wide.steps, wide.normed_steps) == (2048, 4, 3)
    assert wide.block_rows * wide.tile == qn.ROWS * qn.MAX_TILE


# (head dim, sequences, rows of a sequence): what the layer takes and why
LAYERS = {
    'kernels': (128, 2, 32, 'pallas'),
    'heads_of_half_a_lane_block': (64, 2, 32, 'xla'),
    'rows_that_are_no_whole_pass': (128, 1, 24, 'xla'),
    'below_the_flash_kernels_crossover': (128, 2, 8, 'xla'),
}


@pytest.mark.parametrize('case', sorted(LAYERS))
def test_the_layer_says_which_norm_it_traced(monkeypatch, events_of, case):
    """The one ``qk_norm.plan`` point event a trace of a
    ``MultiHeadAttention`` with the norm: the kernels where attention
    takes the flash kernels and ``supports`` says yes, ``head_rms_norm``
    under XLA where either says no (heads of 64 lanes, 24 rows, a
    sequence under the crossover); the layer's output is the same
    function either way."""
    d, bsz, seq, taken = LAYERS[case]
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    attn = MultiHeadAttention(32, 4, head_dim=d, num_kv_heads=2,
                              qk_norm=True, rope_theta=1e4)
    params = attn.init(jax.random.PRNGKey(0))
    params['q_norm']['scale'] = jnp.linspace(0.5, 1.5, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (bsz, seq, 32))
    with events_of('qk_norm.plan') as events:
        out = jax.jit(attn.apply)(params, x)
    assert len(events) == 1
    tags = events[0]['tags']
    assert (tags['path'], tags['heads'], tags['kv_heads'],
            tags['head_dim']) == (taken, 4, 2, d)
    if taken == 'pallas':
        assert (tags['block_rows'], tags['tile_lanes'],
                tags['passes_v']) == (64, 256, 'copy')
    else:
        assert (tags['block_rows'], tags['tile_lanes'],
                tags['passes_v']) == (None, None, None)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(qn, 'plan', lambda *a: None)
        want = jax.jit(attn.apply)(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def normed_model(**kw):
    d = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
             head_dim=128, max_len=256, causal=True, tied_embeddings=False,
             positions='rotary', rope_theta=1e4, mlp_dim=48, gated_mlp=True,
             gelu='silu', norm='rms', mlp_bias=False, dtype=jnp.float32,
             qk_norm=True)
    d.update(kw)
    return TransformerLM(TransformerConfig(**d))


@pytest.mark.parametrize('spec_kw', [dict(dp=2), dict(dp=2, tp=2)],
                         ids=['dp2', 'dp2_tp2'])
def test_a_step_under_a_mesh_equals_one_device(monkeypatch, spec_kw):
    """The kernels on each device's rows in a manual region (under tp on
    a shard's q heads and its k heads, two calls): a step on the mesh
    moves every parameter as a step on one device, and the kernels ran
    on both."""
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    calls = []
    call = qn._backward_call
    monkeypatch.setattr(qn, '_backward_call',
                        lambda *a, **kw: calls.append(a[0].shape)
                        or call(*a, **kw))
    model = normed_model()
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 64, (4, 32)),
             'targets': rng.randint(0, 64, (4, 32))}
    after = {}
    for name, spec in (('one', ParallelSpec(dp=1)),
                       ('mesh', ParallelSpec(**spec_kw))):
        del calls[:]
        tr = Trainer(model, optax.sgd(0.1), spec=spec)
        state, metrics = tr.step(tr.init(jax.random.PRNGKey(0)), batch)
        after[name] = (float(metrics['loss']),
                       jax.tree.map(np.asarray, state.params))
        # rows of a device by the lanes of an operand it holds
        lanes = [8 * 128] if name == 'one' or 'tp' not in spec_kw \
            else [2 * 128, 128]
        rows = 4 * 32 if name == 'one' else 2 * 32
        assert sorted(set(calls), reverse=True) == [(rows, n) for n in lanes]
    np.testing.assert_allclose(after['mesh'][0], after['one'][0], rtol=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(after['mesh'][1]),
            jax.tree.leaves(after['one'][1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4,
                                   err_msg=str(path))


def test_qk_norm_alone_on_the_kernel_path(monkeypatch):
    """``test_flash_block_diffusion.test_qk_norm_alone`` with heads of
    128 lanes at a sequence the flash kernels take: the layer's norm
    through the kernels against the norm written out."""
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    model = normed_model()
    params = model.init(jax.random.PRNGKey(0))
    attn = model.block.attn
    weights = jax.tree.map(lambda a: a[0], params['blocks'])['attn']
    weights = dict(weights, q_norm={'scale': jnp.linspace(0.5, 1.5, 128)},
                   k_norm={'scale': jnp.linspace(2.0, 1.0, 128)})
    qkv = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 8 * 128))
    local = attn.kernel_shape((2, 4, 32, 128))
    assert local == (2, 4, 32, 128)
    got = attn._qk_normed(weights, qkv, local)
    heads = np.asarray(qkv).reshape(2, 32, 8, 128)
    want = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-6)
    want[:, :, :4] *= np.linspace(0.5, 1.5, 128)
    want[:, :, 4:6] *= np.linspace(2.0, 1.0, 128)
    want[:, :, 6:] = heads[:, :, 6:]
    np.testing.assert_allclose(got, want.reshape(2, 32, 1024), rtol=1e-5,
                               atol=1e-6)


def test_a_block_diffusion_models_gradient_through_the_kernels(
        monkeypatch, events_of):
    """Every leaf of the gradient of a block-diffusion model's loss (two
    scanned layers under the blocks' checkpoint, the flash kernels under
    the mask) with the norm through the kernels against
    ``head_rms_norm``: the forward is traced twice a layer and the
    backward once, either way."""
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    model = normed_model(block_length=4, remat=True)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    clean = rng.randint(0, 63, (2, 128))
    batch = {'tokens': jnp.asarray(np.where(rng.rand(2, 128) < 0.6, 63,
                                            clean)),
             'targets': jnp.asarray(clean),
             'mask': jnp.asarray(rng.rand(2, 128), jnp.float32)}

    def grads():
        def loss(p):
            out = model.loss(p, batch)
            return out[0] if isinstance(out, tuple) else out
        return jax.jit(jax.grad(loss))(params)
    with events_of('qk_norm.plan') as events:
        got = grads()
    assert {e['tags']['path'] for e in events} == {'pallas'}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(qn, 'plan', lambda *a: None)
        with events_of('qk_norm.plan') as events:
            want = grads()
    assert {e['tags']['path'] for e in events} == {'xla'}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6, err_msg=str(path))
