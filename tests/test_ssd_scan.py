"""The chunked scan of a selective state-space layer (PR 41,
``kernels/ssd_scan.py``): the Pallas kernels in interpret mode and the
``jax.numpy`` chunked form against the recurrence a position at a time,
output and every gradient, at decays near 0 and near 1, one, two and
many chunks, f32 and bf16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.kernels import ssd_scan as ss


def operands(bsz, seq, heads, groups, dtype, dt_scale=1.0, a_range=(1.0, 16.0),
             head_dim=64, state=128, seed=0):
    """``(x, dt, a, b, c)`` and a cotangent: step sizes log-uniform in
    [1e-3, 1e-1] x ``dt_scale``, ``a = -U(a_range)`` in log space."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (bsz, seq, heads * head_dim)).astype(dtype)
    b = jax.random.normal(k[1], (bsz, seq, groups * state)).astype(dtype)
    c = jax.random.normal(k[2], (bsz, seq, groups * state)).astype(dtype)
    dt = dt_scale * jnp.exp(jax.random.uniform(
        k[3], (bsz, seq, heads), minval=np.log(1e-3), maxval=np.log(1e-1)))
    a = -jnp.exp(jax.random.uniform(k[4], (heads,),
                                    minval=np.log(a_range[0]),
                                    maxval=np.log(a_range[1])))
    dy = jax.random.normal(k[5], (bsz, seq, heads * head_dim))
    return (x, dt, a, b, c), dy


def value_and_grads(form, args, dy, heads, groups):
    def loss(*args):
        return jnp.sum(form(*args, heads, groups).astype(jnp.float32) * dy)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)


def worst(got, want):
    """Largest ``|got - want| / |want|`` (L2) over the two trees'
    leaves."""
    return max(
        float(np.linalg.norm(np.asarray(g, np.float64)
                             - np.asarray(w, np.float64))
              / np.linalg.norm(np.asarray(w, np.float64)))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# name: (batch, seq, heads, groups, dtype, dt_scale, a_range, limit)
CASES = {
    'one_chunk': (1, 128, 2, 1, jnp.float32, 1.0, (1.0, 16.0), 2e-5),
    'two_chunks_two_groups': (2, 256, 4, 2, jnp.float32, 1.0, (1.0, 16.0),
                              2e-5),
    'many_chunks': (1, 640, 2, 1, jnp.float32, 1.0, (1.0, 16.0), 2e-5),
    # exp(dt A) down to exp(-32) a step: nothing of a state survives a
    # chunk, and a factored exp(l_t) exp(-l_s) would overflow
    'decay_near_0': (1, 384, 4, 1, jnp.float32, 20.0, (8.0, 16.0), 3e-4),
    # dt A of -1e-6 to -1e-4 a step: the state carries over every chunk
    'decay_near_1': (1, 384, 2, 1, jnp.float32, 0.001, (1.0, 1.5), 2e-5),
    'bf16': (1, 256, 4, 2, jnp.bfloat16, 1.0, (1.0, 16.0), 1e-2),
}


@pytest.mark.parametrize('form', ['kernel', 'chunked'])
@pytest.mark.parametrize('case', sorted(CASES))
def test_chunked_forms_are_the_recurrence(case, form):
    """Output and the gradient of every operand (x, dt, A, B, C) against
    ``ssd_reference``, the recurrence in f32. In bf16 the operands are
    the same bf16 numbers on both sides; what differs is the rounding of
    the products' operands inside (the decay matrix times ``C B^T``, the
    states)."""
    bsz, seq, heads, groups, dtype, dt_scale, a_range, limit = CASES[case]
    assert ss.supports(seq, heads, groups, 64, 128)
    args, dy = operands(bsz, seq, heads, groups, dtype, dt_scale, a_range)
    f32 = tuple(t.astype(jnp.float32) for t in args)
    want = value_and_grads(ss.ssd_reference, f32, dy, heads, groups)
    got = value_and_grads(ss.ssd_scan if form == 'kernel' else ss.ssd_chunked,
                          args, dy, heads, groups)
    assert abs(float(got[0]) - float(want[0])) <= limit * max(
        1.0, float(jnp.sqrt(jnp.sum(jnp.square(dy)))))
    assert worst(got[1], want[1]) < limit
    y = (ss.ssd_scan if form == 'kernel' else ss.ssd_chunked)(
        *args, heads, groups)
    assert y.dtype == dtype
    assert worst(y, ss.ssd_reference(*f32, heads, groups)) < limit


def test_the_decays_really_reach_both_ends():
    """The cases' names are true: at one end a step keeps under 1e-6 of
    the state, at the other a whole sequence keeps over 98%."""
    (_, dt, a, _, _), _ = operands(*CASES['decay_near_0'][:5],
                                   *CASES['decay_near_0'][5:7])
    assert float(jnp.min(jnp.exp(dt * a))) < 1e-6
    (_, dt, a, _, _), _ = operands(*CASES['decay_near_1'][:5],
                                   *CASES['decay_near_1'][5:7])
    assert float(jnp.min(jnp.exp(jnp.sum(dt * a, axis=1)))) > 0.98


@pytest.mark.parametrize('shape,takes', [
    ((8192, 64, 8, 64, 128), True),         # the published layer
    ((128, 2, 1, 64, 128), True),
    ((128, 2, 2, 64, 128), False),          # one head a group: no pair
    ((128, 4, 1, 32, 128), False),          # heads of 32 lanes
    ((128, 4, 1, 64, 64), False),           # a state of 64
    ((192, 4, 1, 64, 128), False),          # no whole chunks
    ((128, 6, 4, 64, 128), False),          # heads that do not divide
])
def test_supports(shape, takes):
    assert ss.supports(*shape) is takes


@pytest.mark.parametrize('seq,heads,groups,head_dim,state', [
    (96, 4, 2, 16, 8),        # one chunk shorter than CHUNK
    (256, 3, 1, 32, 16),      # an odd number of heads
])
def test_other_shapes_take_the_jnp_form(seq, heads, groups, head_dim, state):
    """``ssd_scan`` on a shape the kernels do not take is the chunked
    form in ``jax.numpy`` (no Pallas call), and the recurrence."""
    args, dy = operands(2, seq, heads, groups, jnp.float32,
                        head_dim=head_dim, state=state)
    assert not ss.supports(seq, heads, groups, head_dim, state)
    text = jax.jit(lambda *a: ss.ssd_scan(*a, heads, groups)).lower(
        *args).as_text()
    assert 'ssd_fwd' not in text and 'pallas' not in text
    want = value_and_grads(ss.ssd_reference, args, dy, heads, groups)
    got = value_and_grads(ss.ssd_scan, args, dy, heads, groups)
    assert worst(got, want) < 2e-5


def test_the_kernels_are_named_and_called_once_each():
    """A gradient of a supported call lowers to one ``ssd_fwd`` and one
    ``ssd_bwd`` (the names a trace and ``benchmark/ssm_kinds.py`` read),
    with the running sums of ``log a`` outside them."""
    import re
    args, _ = operands(1, 256, 2, 1, jnp.float32)
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(ss.ssd_scan(*a, 2, 1)), argnums=(0, 1, 2, 3, 4))
    ).lower(*args).as_text(debug_info=True)
    assert re.search(r'ssd_fwd\W+pallas_call', text)
    assert re.search(r'ssd_bwd\W+pallas_call', text)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ss.ssd_scan(*a, 2, 1)), argnums=0))(*args))
    assert jaxpr.count('name=ssd_fwd') == 1
    assert jaxpr.count('name=ssd_bwd') == 1


def test_a_state_outlives_its_chunk():
    """One impulse in chunk 0 is read in chunk 2 through the carried
    state: ``y_t = dt_0 (x_0 . ) (B_0 . C_t) prod a``."""
    seq, heads, groups = 384, 2, 1
    x = jnp.zeros((1, seq, heads * 64)).at[0, 5, :].set(1.0)
    b = jnp.zeros((1, seq, 128)).at[0, 5, 3].set(2.0)
    c = jnp.zeros((1, seq, 128)).at[0, 300, 3].set(0.5)
    dt = jnp.full((1, seq, heads), 0.01)
    a = jnp.asarray([-1.0, -2.0])
    y = ss.ssd_scan(x, dt, a, b, c, heads, groups)
    want = 0.01 * 2.0 * 0.5 * np.exp(0.01 * np.asarray([-1.0, -2.0]) * 295)
    np.testing.assert_allclose(np.asarray(y[0, 300, ::64]), want, rtol=1e-5)
    assert not np.any(np.asarray(y[0, :300]))
