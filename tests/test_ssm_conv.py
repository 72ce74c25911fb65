"""The short causal conv of a state-space layer with its SiLU (PR 42,
``kernels/ssm_conv.py``): the Pallas kernels in interpret mode against
the same function in ``jax.numpy``, output and all three gradients, at
several row blocks (the halo on both sides, forward and backward), two
batch rows, a column offset into a wider operand, f32 and bf16; and what
decides between the two forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import ssm_conv as sc


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 32 in passes of 16: a sequence of 128 is four
    blocks of two passes each."""
    monkeypatch.setattr(sc, 'ROWS', 32)
    monkeypatch.setattr(sc, 'SUB', 16)


def operands(dtype, bsz=2, seq=128, width=1088, channels=768, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    proj = jax.random.normal(k[0], (bsz, seq, width)).astype(dtype)
    taps = jax.random.normal(k[1], (4, channels)) * 12 ** -0.5
    bias = jax.random.normal(k[2], (channels,)) * 12 ** -0.5
    return proj, taps, bias, k[3]


def value_and_grads(form, proj, taps, bias, offset, widths, key):
    """The value and the three gradients of ``sum(out * weights)``, the
    weights other numbers for every output element."""
    weights = [jax.random.normal(jax.random.fold_in(key, p),
                                 proj.shape[:2] + (w,))
               for p, w in enumerate(widths)]

    def loss(proj, taps, bias):
        outs = form(proj, taps, bias, offset, widths)
        assert [o.shape[-1] for o in outs] == list(widths)
        assert all(o.dtype == proj.dtype for o in outs)
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(outs, weights))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        proj, taps, bias)


def worst(got, want):
    return max(
        float(np.linalg.norm(np.asarray(g, np.float64)
                             - np.asarray(w, np.float64))
              / np.linalg.norm(np.asarray(w, np.float64)))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# name: (offset, widths, operand width)
LAYOUTS = {
    # Mamba-2's x | B | C at a quarter of a lane step: tiles 256 | 128 | 128
    'x_b_c_from_an_offset': (256, (256, 128, 128), 832),
    # two lane steps of 512 | 128 | 128 lanes
    'two_lane_steps': (1024, (1024, 256, 256), 2624),
    'one_part_at_the_start': (0, (384,), 384),
    'one_part_to_the_last_lane': (128, (256,), 384),
}


@pytest.mark.parametrize('dtype,limit', [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2e-4)],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_the_kernels_are_the_conv_and_its_gradients(small_blocks, layout,
                                                    dtype, limit):
    """Forward and the gradients of the columns, the taps and the bias
    against ``reference`` over four row blocks and two batch rows. The
    operands are the same numbers on both sides and both compute in f32,
    so bf16 differs by the last bit of a rounded output or cotangent at
    most; outside the conv's columns the operand's gradient is zero."""
    offset, widths, width = LAYOUTS[layout]
    channels = sum(widths)
    proj, taps, bias, key = operands(dtype, width=width, channels=channels)
    how = sc.plan(proj.shape[1], width, offset, widths, 4)
    assert how is not None and how.block_rows == 32 and how.sub_rows == 16
    got = value_and_grads(sc.conv_silu, proj, taps, bias, offset, widths, key)
    want = value_and_grads(sc.reference, proj, taps, bias, offset, widths,
                           key)
    assert abs(float(got[0]) - float(want[0])) <= 1e-4 * abs(float(want[0]))
    assert worst(got[1], want[1]) < limit
    d_proj = np.asarray(got[1][0], np.float32)
    assert d_proj.shape == proj.shape
    assert not d_proj[..., :offset].any()
    assert not d_proj[..., offset + channels:].any()
    assert d_proj[..., offset:offset + channels].all()


def test_outputs_are_the_reference_to_the_last_bit_in_bf16(small_blocks):
    offset, widths, width = LAYOUTS['x_b_c_from_an_offset']
    proj, taps, bias, _ = operands(jnp.bfloat16, width=width,
                                   channels=sum(widths))
    got = sc.conv_silu(proj, taps, bias, offset, widths)
    want = sc.reference(proj, taps, bias, offset, widths)
    for g, w in zip(got, want):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        # (a sigmoid computed twice may round a tie the other way)
        assert np.mean(g != w) < 1e-3
        np.testing.assert_allclose(g, w, rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize('pass_', ['forward', 'backward'])
def test_nothing_crosses_a_batch_row_or_the_ends_of_the_sequence(
        small_blocks, pass_):
    """A batch row's outputs and gradients are those it has alone: the
    halo before its first row block and after its last is zeros, not the
    neighbouring batch row's rows."""
    offset, widths, width = LAYOUTS['x_b_c_from_an_offset']
    proj, taps, bias, key = operands(jnp.float32, width=width,
                                     channels=sum(widths))

    def run(proj):
        if pass_ == 'forward':
            return sc.conv_silu(proj, taps, bias, offset, widths)
        return jax.grad(lambda p: sum(
            jnp.sum(jnp.sin(o)) for o in sc.conv_silu(
                p, taps, bias, offset, widths)))(proj)
    both = run(proj)
    for row in range(2):
        alone = run(proj[row:row + 1])
        for b, a in zip(jax.tree.leaves(both), jax.tree.leaves(alone)):
            np.testing.assert_array_equal(np.asarray(b[row]),
                                          np.asarray(a[0]))


def test_a_row_block_reads_its_halo_and_nothing_further(small_blocks):
    """``out_t`` turns on ``x_{t-3} .. x_t`` and ``dx_t`` on ``g_t ..
    g_{t+3}``, across a row block's edge (rows 31 | 32) as inside one."""
    offset, widths, width = 0, (128,), 128
    proj, taps, bias, _ = operands(jnp.float32, bsz=1, width=width,
                                   channels=128)

    def out(proj):
        return sc.conv_silu(proj, taps, bias, offset, widths)[0]
    base = out(proj)
    for t in (0, 29, 31, 32, 63, 127):
        moved = np.asarray(out(proj.at[0, t].add(1.0)) - base)[0]
        rows = np.flatnonzero(np.abs(moved).max(axis=1) > 0)
        assert list(rows) == list(range(t, min(t + 4, 128))), (t, rows)
    # the backward: a cotangent at row t reaches dx of rows t-3 .. t
    for t in (0, 3, 32, 34, 96, 127):
        ct = jnp.zeros_like(base).at[0, t].set(1.0)
        dx = np.asarray(jax.vjp(out, proj)[1](ct)[0])[0]
        rows = np.flatnonzero(np.abs(dx).max(axis=1) > 0)
        assert list(rows) == list(range(max(t - 3, 0), t + 1)), (t, rows)


# name: (seq, operand width, offset, widths, taps): why the kernels pass
UNSUPPORTED = {
    'channels_not_whole_lane_blocks': (128, 512, 128, (192, 64), 4),
    'offset_not_a_whole_lane_block': (128, 512, 64, (128, 128), 4),
    'offset_no_multiple_of_the_tile': (128, 1024, 128, (256, 128, 128), 4),
    'sequence_not_whole_row_blocks': (48, 512, 128, (128, 128), 4),
    'three_taps': (128, 512, 128, (128, 128), 3),
    'columns_past_the_operand': (128, 384, 128, (128, 256), 4),
}


@pytest.mark.parametrize('case', sorted(UNSUPPORTED))
def test_shapes_the_kernels_do_not_take_run_jax_numpy(small_blocks, case,
                                                      monkeypatch):
    """``supports`` false: the ``jax.numpy`` form, same numbers as the
    conv written out a position at a time, and no kernel is called."""
    seq, width, offset, widths, k = UNSUPPORTED[case]
    assert not sc.supports(seq, width, offset, widths, k)
    if offset + sum(widths) > width:
        return
    monkeypatch.setattr(sc, '_forward_call', None)
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    proj = jax.random.normal(key[0], (2, seq, width))
    taps = jax.random.normal(key[1], (k, sum(widths)))
    bias = jax.random.normal(key[2], (sum(widths),))
    got = jnp.concatenate(sc.conv_silu(proj, taps, bias, offset, widths), -1)
    x = np.asarray(proj[..., offset:offset + sum(widths)], np.float64)
    pre = np.zeros_like(x) + np.asarray(bias)
    for i in range(k):
        shift = k - 1 - i
        pre[:, shift:] += np.asarray(taps[i]) * x[:, :seq - shift]
    np.testing.assert_allclose(np.asarray(got), pre / (1 + np.exp(-pre)),
                               rtol=2e-5, atol=2e-6)


def test_supports_at_the_published_shape():
    """Nemotron-3-Nano's Mamba-2 layer: 6144 channels from lane 4096 of
    the projection's 10304, x | B | C of 4096 | 1024 | 1024: four lane
    steps of 1024 | 256 | 256, row blocks of 1024 in passes of 64."""
    how = sc.plan(8192, 10304, 4096, (4096, 1024, 1024), 4)
    assert how == sc.Plan(1024, 64, 4, (1024, 256, 256))
    assert sc.plan(8192, 10304, 4096, (6144,), 4).tiles == (1024,)


def mixer(**kw):
    from autodist_tpu.models.ssm import Mamba2Mixer
    d = dict(dim=32, heads=2, head_dim=64, groups=1, state=128, conv=4)
    d.update(kw)
    return Mamba2Mixer(**d)


def with_the_conv_in_jax_numpy(run):
    """``run()`` with every ``conv_silu`` on the ``jax.numpy`` path."""
    def plain(proj, taps, bias, offset, widths, **_):
        return sc.reference(proj, taps, bias, offset, widths)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(sc, 'conv_silu', plain)
        return run()


@pytest.mark.parametrize('conv,taken', [(4, 'pallas'), (3, 'xla')])
def test_the_mixer_says_which_conv_it_traced(conv, taken, events_of):
    """One ``ssm.plan`` point event a trace of a ``Mamba2Mixer``: the
    kernels on the projection's own columns where they take the shape,
    XLA on a slice of it where they do not (three taps); the layer's
    output is the same function either way."""
    layer = mixer(conv=conv)
    params = layer.init(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32))
    with events_of('ssm.plan') as events:
        out = jax.jit(layer.apply)(params, u)
    assert len(events) == 1
    tags = events[0]['tags']
    assert tags['conv'] == taken
    assert (tags['channels'], tags['taps']) == (384, conv)
    assert tags['in_place'] == tags['split_outputs'] == (taken == 'pallas')
    if taken == 'pallas':
        assert tags['block_rows'] == 128
        assert tuple(tags['block_lanes']) == (128, 128, 128)

    # the same layer with the conv in jax.numpy on a slice
    want = with_the_conv_in_jax_numpy(
        lambda: jax.jit(layer.apply)(params, u))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_the_mixers_gradient_through_the_kernels_is_the_plain_one():
    """Every leaf of a Mamba-2 layer's gradient, and its input's, with
    the conv through the kernels against the conv in ``jax.numpy``: the
    cotangent of the projection is assembled from z's, the conv's three
    parts' and dt's columns either way."""
    layer = mixer()
    params = layer.init(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32))

    def grads():
        return jax.jit(jax.grad(
            lambda p, u: jnp.sum(jnp.sin(layer.apply(p, u))),
            argnums=(0, 1)))(params, u)
    got = grads()
    want = with_the_conv_in_jax_numpy(grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6, err_msg=str(path))
