"""The tail of a state-space layer, ``GroupRMSNorm((y + D x) silu(z))
scale`` (PR 43, ``kernels/ssm_gate_norm.py``): the Pallas kernels in
interpret mode against ``GatedGroupRMSNorm`` on ``y + D x`` in
``jax.numpy``, output and every gradient (``y``, ``x``, ``z``, ``scale``,
``D``), over several row blocks, lane steps and groups a tile, z at a
column offset of a wider operand, f32 and bf16; and what decides between
the two forms in ``Mamba2Mixer``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import ssm_gate_norm as gn
from autodist_tpu.models.core import GatedGroupRMSNorm

EPS = 1e-5
HEAD_DIM = 64


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 32 in passes of 16, tiles of at most 256 lanes: a
    sequence of 128 is four blocks of two passes each."""
    monkeypatch.setattr(gn, 'ROWS', 32)
    monkeypatch.setattr(gn, 'SUB', 16)
    monkeypatch.setattr(gn, 'MAX_TILE', 256)


def plain(y, x, proj, d, scale, offset, groups):
    """The layer's ``jax.numpy`` form: ``GatedGroupRMSNorm`` on ``y + D
    x`` with z sliced out of the projection."""
    inner = y.shape[-1]
    norm = GatedGroupRMSNorm(inner, groups, eps=EPS, dtype=proj.dtype)
    t = (y.astype(jnp.float32)
         + jnp.repeat(d, HEAD_DIM) * x.astype(jnp.float32))
    return norm.apply({'scale': scale}, t, proj[..., offset:offset + inner])


def kernels(y, x, proj, d, scale, offset, groups):
    return gn.gate_norm(y, x, proj, jnp.repeat(d, HEAD_DIM), scale, offset,
                        groups, EPS)


def operands(dtype, inner, width, bsz=2, seq=128, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    y, x = (jax.random.normal(k[i], (bsz, seq, inner)).astype(dtype)
            for i in range(2))
    proj = jax.random.normal(k[2], (bsz, seq, width)).astype(dtype)
    d = 1 + 0.5 * jax.random.normal(k[3], (inner // HEAD_DIM,))
    scale = 1 + 0.5 * jax.random.normal(k[4], (inner,))
    return (y, x, proj, d, scale), k[5]


def value_and_grads(form, ins, offset, groups, key):
    """The value and the five gradients of ``sum(out * weights)``, the
    weights other numbers for every output element."""
    weights = jax.random.normal(key, ins[0].shape)

    def loss(*ins):
        out = form(*ins, offset, groups)
        assert out.dtype == ins[2].dtype and out.shape == ins[0].shape
        return jnp.sum(out.astype(jnp.float32) * weights)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*ins)


def distance(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# name: (inner, groups, z's offset, operand width, rows a block)
LAYOUTS = {
    # Mamba-2's z at column 0 of z | xBC | dt: two groups a tile
    'z_first_two_groups_a_tile': (512, 4, 0, 832, 32),
    # a group as wide as the widest tile, z behind other columns
    'z_from_an_offset_a_group_a_tile': (512, 2, 256, 768, 32),
    # one group wider than MAX_TILE: the tile is the group, of half the
    # rows
    'one_group_wider_than_a_tile': (384, 1, 0, 384, 16),
    'z_to_the_last_lane': (256, 2, 128, 384, 32),
}
NAMES = ('y', 'x', 'proj', 'D', 'scale')


@pytest.mark.parametrize('dtype,limit', [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-4)],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_the_kernels_are_the_gated_norm_and_its_gradients(small_blocks,
                                                          layout, dtype,
                                                          limit):
    """Forward and the gradients of y, x, z's columns, D and the scale
    against the ``jax.numpy`` form over four row blocks and two batch
    rows. Both sides compute in f32 from the same numbers, so bf16
    differs by the last bit of a rounded output or cotangent at most;
    outside z's columns the projection's gradient is zero."""
    inner, groups, offset, width, rows = LAYOUTS[layout]
    ins, key = operands(dtype, inner, width)
    how = gn.plan(128, width, offset, inner, groups)
    assert how is not None and (how.block_rows, how.sub_rows) == (rows, 16)
    assert how.group_lanes == inner // groups
    got = value_and_grads(kernels, ins, offset, groups, key)
    want = value_and_grads(plain, ins, offset, groups, key)
    assert abs(float(got[0]) - float(want[0])) <= 1e-4 * abs(float(want[0]))
    for name, g, w in zip(NAMES, got[1], want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert distance(g, w) < limit, name
    d_proj = np.asarray(got[1][2], np.float32)
    assert not d_proj[..., :offset].any()
    assert not d_proj[..., offset + inner:].any()
    assert d_proj[..., offset:offset + inner].all()


def test_the_output_is_the_plain_form_to_the_last_bit_in_bf16(small_blocks):
    inner, groups, offset, width, _ = LAYOUTS['z_first_two_groups_a_tile']
    ins, _ = operands(jnp.bfloat16, inner, width)
    got, want = (np.asarray(form(*ins, offset, groups), np.float32)
                 for form in (kernels, plain))
    # (a sum of squares added in another order may round a tie the
    # other way)
    assert np.mean(got != want) < 2e-3
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize('pass_', ['forward', 'backward'])
def test_nothing_crosses_a_batch_row_a_row_or_a_group(small_blocks, pass_):
    """An output (a gradient of y, x and z) at one row of one batch row
    and one group turns on that row's lanes of that group and nothing
    else: moving one element moves its group's 128 lanes of its row."""
    inner, groups, offset, width, _ = LAYOUTS['z_first_two_groups_a_tile']
    ins, key = operands(jnp.float32, inner, width)
    weights = jax.random.normal(key, ins[0].shape)

    def run(y, proj):
        def out(y, x, proj):
            return kernels(y, x, proj, *ins[3:], offset, groups)
        if pass_ == 'forward':
            return (out(y, ins[1], proj),)
        d_y, d_x, d_proj = jax.vjp(out, y, ins[1], proj)[1](weights)
        return d_y, d_x, d_proj[..., offset:offset + inner]
    base = run(ins[0], ins[2])
    lanes = inner // groups
    # (batch row, row, lane): rows at a pass's and a block's edges
    for b, t, lane in ((0, 0, 0), (1, 15, 127), (0, 32, 128), (1, 127, 511)):
        for which in ('y', 'z'):
            y, proj = ins[0], ins[2]
            if which == 'y':
                y = y.at[b, t, lane].add(1.0)
            else:
                proj = proj.at[b, t, offset + lane].add(1.0)
            for a, m in zip(base, run(y, proj)):
                moved = np.array(a != m)
                first = lane // lanes * lanes
                assert moved[b, t, first:first + lanes].all()
                moved[b, t, first:first + lanes] = False
                assert not moved.any(), (b, t, lane, which)


# name: (seq, operand width, z's offset, inner, groups): why no kernels
UNSUPPORTED = {
    'groups_not_whole_lane_blocks': (128, 384, 0, 384, 2),
    'inner_not_whole_lane_blocks': (128, 192, 0, 192, 1),
    'offset_not_a_whole_tile': (128, 512, 64, 256, 2),
    'sequence_not_whole_row_blocks': (48, 256, 0, 256, 2),
    'rows_not_whole_passes': (24, 256, 0, 256, 2),
    'lanes_do_not_divide_over_the_groups': (128, 384, 0, 384, 5),
    'columns_past_the_operand': (128, 384, 256, 256, 2),
}


@pytest.mark.parametrize('case', sorted(UNSUPPORTED))
def test_shapes_the_kernels_do_not_take(small_blocks, case):
    """``supports`` false, and ``gate_norm`` raises: the caller keeps its
    own form (the mixer's, below)."""
    seq, width, offset, inner, groups = UNSUPPORTED[case]
    assert not gn.supports(seq, width, offset, inner, groups)
    if offset + inner > width:
        return
    ins, _ = operands(jnp.float32, inner, width, seq=seq)
    with pytest.raises(ValueError, match='ask supports'):
        gn.gate_norm(*ins[:3], jnp.repeat(ins[3], HEAD_DIM), ins[4], offset,
                     groups, EPS)


def test_supports_at_the_published_shapes():
    """Nemotron-3-Nano's Mamba-2 layer: 4096 lanes in 8 groups of 512, z
    at column 0 of the projection's 10304: four lane steps of two groups,
    row blocks of 1024 in passes of 64. Mamba-2 2.7B's (one group of
    5120, z first of 10576): the tile is the group, the row block
    smaller by as much."""
    assert gn.plan(8192, 10304, 0, 4096, 8) == gn.Plan(1024, 64, 4, 1024,
                                                       512, 0)
    wide = gn.plan(8192, 10576, 0, 5120, 1)
    assert (wide.tile, wide.group_lanes, wide.steps) == (5120, 5120, 1)
    assert wide.block_rows * wide.tile <= gn.ROWS * gn.MAX_TILE
    assert 8192 % wide.block_rows == 0


def mixer(**kw):
    from autodist_tpu.models.ssm import Mamba2Mixer
    d = dict(dim=32, heads=4, head_dim=64, groups=2, state=128, conv=4)
    d.update(kw)
    return Mamba2Mixer(**d)


def with_the_gate_norm_in_jax_numpy(run):
    """``run()`` with every mixer on ``GatedGroupRMSNorm``."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(gn, 'supports', lambda *a: False)
        return run()


# heads of 64 in two groups: groups of 128 lanes, or of 192
@pytest.mark.parametrize('heads,taken', [(4, 'pallas'), (6, 'xla')])
def test_the_mixer_says_which_gate_norm_it_traced(heads, taken, events_of):
    """The one ``ssm.plan`` point event a trace of a ``Mamba2Mixer``
    carries the gate norm's three tags beside the conv's: the kernels
    where they take the shape, XLA on ``GatedGroupRMSNorm`` where they do
    not (groups of 192 lanes; three heads a group, which the scan's
    kernels do not take either); the layer's output is the same function
    either way."""
    layer = mixer(heads=heads)
    params = layer.init(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32))
    with events_of('ssm.plan') as events:
        out = jax.jit(layer.apply)(params, u)
    assert len(events) == 1
    tags = events[0]['tags']
    assert tags['gate_norm'] == taken
    assert tags['conv'] == 'pallas'
    if taken == 'pallas':
        assert tags['gate_norm_block_rows'] == 128
        assert tags['gate_norm_group_lanes'] == 128
    else:
        assert tags['gate_norm_block_rows'] is None
        assert tags['gate_norm_group_lanes'] is None
    want = with_the_gate_norm_in_jax_numpy(
        lambda: jax.jit(layer.apply)(params, u))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_the_mixers_gradient_through_the_kernels_is_the_plain_one():
    """Every leaf of a Mamba-2 layer's gradient, and its input's, with
    the gate, ``D x`` and the norm through the kernels against
    ``GatedGroupRMSNorm``: z's cotangent is laid into the projection's
    beside the conv's and dt's, x's added to the scan's, either way."""
    layer = mixer()
    params = layer.init(jax.random.PRNGKey(0))
    # (D away from its draw of ones, so that its gradient is told apart)
    params['d'] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (4,))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32))
    calls = []

    def grads():
        return jax.jit(jax.grad(
            lambda p, u: jnp.sum(jnp.sin(layer.apply(p, u))),
            argnums=(0, 1)))(params, u)
    with pytest.MonkeyPatch.context() as m:
        call = gn._backward_call
        m.setattr(gn, '_backward_call',
                  lambda *a, **kw: calls.append(1) or call(*a, **kw))
        got = grads()
    assert calls == [1]
    want = with_the_gate_norm_in_jax_numpy(grads)
    assert calls == [1]
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6, err_msg=str(path))
