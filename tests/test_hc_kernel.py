"""The hyper-connections as two kernel pairs (PR 51,
``kernels/hyper_connections.py``): the four Pallas kernels in interpret
mode against ``HyperConnection``'s ``jax.numpy`` form, forward (what the
sublayer reads, what is written back, the held coefficients) and every
gradient leaf, over several row blocks (``d phi``'s block stays in VMEM
over them), four streams and two, f32 and bf16; logits at both ends of the
clamp; the gradient through all twenty rounds and not nineteen; what
``supports`` refuses; a data-parallel mesh against one device; and the
four calls compiled for a described v5e at Xing4.0's width."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.api import Trainer
from autodist_tpu.kernels import hyper_connections as hk
from autodist_tpu.models.hyper_connections import (HyperConnection,
                                                   _KernelHeld, sinkhorn)
from autodist_tpu.models.transformer import TransformerConfig, TransformerLM
from autodist_tpu.parallel.axes import ParallelSpec

DIM = 128
SHAPE = (3, 128)        # three row blocks of 128
EPS = 1e-6


def connection(n, dtype=jnp.float32, clamp=(-30.0, 30.0), seed=0,
               alpha=(1.5, -0.7, 2.0), spread=0.5):
    """A connection whose coefficients move with the token and lie away
    from the plain residual path, the streams, a sublayer's output and a
    cotangent for each of the two results."""
    hc = HyperConnection(DIM, n, iters=20, clamp=clamp, eps=EPS, dtype=dtype)
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    params = hc.init(k[0])
    params['alpha'] = jnp.asarray(alpha, jnp.float32)
    params['bias'] = params['bias'] + spread * jax.random.normal(
        k[1], params['bias'].shape)
    x = (2.0 * jax.random.normal(k[2], SHAPE + (n * DIM,))).astype(dtype)
    y = jax.random.normal(k[3], SHAPE + (DIM,)).astype(dtype)
    cts = (jax.random.normal(k[4], SHAPE + (DIM,)),
           jax.random.normal(k[5], SHAPE + (n * DIM,)))
    return hc, params, x, y, cts


def through(form, hc):
    """``(u, x', H_post, H_res)`` of ``(params, x, y)`` with the sublayer
    left out: by the kernels (``HyperConnection.enter`` / ``leave`` on a
    shape they take) or by the ``jax.numpy`` form."""
    n = hc.streams

    def kernels(params, x, y):
        u, held = hc.enter(params, x)
        assert isinstance(held, _KernelHeld)
        out, _ = hc.leave(x, y, held)
        return (u, out, held.coefficients[:n].reshape((n,) + SHAPE),
                held.coefficients[n:].reshape((n, n) + SHAPE))

    def numpy_form(params, x, y):
        pre, post, res = hc.coefficients(params, x)
        return hc.read(x, pre), hc.write(x, y, post, res), post, res
    return kernels if form == 'pallas' else numpy_form


def value_and_grads(form, hc, params, x, y, cts):
    def loss(params, x, y):
        u, out, post, res = through(form, hc)(params, x, y)
        assert u.dtype == x.dtype and out.dtype == x.dtype
        total = jnp.sum(u.astype(jnp.float32) * cts[0]) \
            + jnp.sum(out.astype(jnp.float32) * cts[1])
        return total, (u, out, post, res)
    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        params, x, y)


def off(got, want):
    """Largest ``|got - want|`` as a share of the largest ``|want|``."""
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 3e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('n', [4, 2], ids=['four', 'two'])
def test_kernels_are_the_numpy_form(n, dtype, tol):
    """Forward and every gradient leaf. In bf16 both forms round the
    streams' products and each output once; the f32 sums are added in
    another order."""
    hc, params, x, y, cts = connection(n, dtype)
    assert hc.kernel_plan(x.shape, x.dtype).block_rows == 128
    (_, got), got_grads = value_and_grads('pallas', hc, params, x, y, cts)
    (_, want), want_grads = value_and_grads('xla', hc, params, x, y, cts)
    for name, a, b in zip(('u', 'out', 'post', 'res'), got, want):
        assert off(a, b) < tol, (name, off(a, b))
    got_grads = dict(got_grads[0], x=got_grads[1], y=got_grads[2])
    want_grads = dict(want_grads[0], x=want_grads[1], y=want_grads[2])
    assert set(got_grads) == {'phi', 'alpha', 'bias', 'x', 'y'}
    for name in got_grads:
        assert got_grads[name].dtype == want_grads[name].dtype, name
        assert off(got_grads[name], want_grads[name]) < tol, (
            name, off(got_grads[name], want_grads[name]))


def test_both_ends_of_the_clamp_are_finite():
    """Logits of the stream mix far past -3 and +3 side by side, forward
    and through the twenty rounds' backward; a clamped logit has no
    gradient, as ``jnp.clip``'s."""
    hc, params, x, y, cts = connection(4, clamp=(-3.0, 3.0),
                                       alpha=(1.0, 1.0, 40.0), spread=20.0)
    (_, got), got_grads = value_and_grads('pallas', hc, params, x, y, cts)
    (_, want), want_grads = value_and_grads('xla', hc, params, x, y, cts)
    for a in jax.tree.leaves((got, got_grads)):
        assert bool(jnp.all(jnp.isfinite(a)))
    np.testing.assert_allclose(jnp.sum(got[3], axis=1), 1.0, atol=1e-5)
    for a, b in zip(jax.tree.leaves((got, got_grads)),
                    jax.tree.leaves((want, want_grads))):
        assert off(a, b) < 3e-4


def test_the_gradient_is_that_of_every_round():
    """With the gates at zero the stream mix is ``SK(bias)`` at every
    token: the kernels' gradient of the bias is ``jax.grad`` of
    ``hyper_connections.sinkhorn`` at twenty rounds and NOT at nineteen.
    The logits are an upper triangle (ones over ``exp(-12)``), whose
    rounds converge like ``1 / round``."""
    n = 4
    hc, params, x, y, _ = connection(n, alpha=(0.0, 0.0, 0.0))
    logits = jnp.where(jnp.arange(n)[None] >= jnp.arange(n)[:, None],
                       0.0, -12.0)
    params['bias'] = params['bias'].at[2 * n:].set(logits.ravel())
    weight = jax.random.normal(jax.random.PRNGKey(9), (n, n))

    def by_kernels(bias):
        _, held = hc.enter(dict(params, bias=bias), x)
        res = held.coefficients[n:].reshape((n, n) + SHAPE)
        return jnp.sum(res * weight[:, :, None, None])

    def by_rounds(iters):
        def total(bias):
            res = sinkhorn(bias[2 * n:].reshape(n, n, 1, 1), iters, EPS)
            return jnp.sum(res[..., 0, 0] * weight) * SHAPE[0] * SHAPE[1]
        return jax.grad(total)(params['bias'])
    got = jax.grad(by_kernels)(params['bias'])[2 * n:]
    assert off(got, by_rounds(20)[2 * n:]) < 3e-4
    assert off(got, by_rounds(19)[2 * n:]) > 3e-3


@pytest.mark.parametrize('rows,n,dim,dtype,why', [
    (256, 4, 96, jnp.float32, 'a stream of no whole lane blocks'),
    (256, 4, 32, jnp.float32, 'the tiny-width tests'),
    (200, 4, 128, jnp.float32, 'rows that do not tile'),
    (64, 4, 128, jnp.bfloat16, 'fewer rows than a block'),
    (256, 1, 128, jnp.float32, 'one stream'),
    (256, 4, 128, jnp.float16, 'a dtype the kernels were not written for'),
    (256, 4, 128 * 1024, jnp.float32, 'a block that does not fit VMEM'),
], ids=lambda v: v.replace(' ', '_') if isinstance(v, str) else None)
def test_supports_refuses(rows, n, dim, dtype, why):
    assert not hk.supports(rows, n, dim, dtype), why
    with pytest.raises(ValueError, match='ask supports'):
        hk.enter(jnp.zeros((rows, n * dim), dtype),
                 jnp.zeros((n * dim, n * (n + 2)), dtype), jnp.zeros((3,)),
                 jnp.zeros((n * (n + 2),)), n, 20, (-30.0, 30.0), EPS)


@pytest.mark.parametrize('rows,dim,dtype,block', [
    (8192, 3584, jnp.bfloat16, 256), (384, 128, jnp.float32, 128),
    (512, 256, jnp.bfloat16, 256), (8192, 3584, jnp.float32, 128)],
    ids=['xing4', 'three_blocks', 'small', 'xing4_f32'])
def test_supports_takes_and_plans(rows, dim, dtype, block):
    how = hk.plan(rows, 4, dim, dtype)
    assert hk.supports(rows, 4, dim, dtype)
    assert (how.block_rows, how.sub_rows, how.unroll) == (
        block, hk.SUB, hk.UNROLL)
    assert how.vmem_limit_bytes <= 100 << 20
    assert rows % how.block_rows == 0 and how.block_rows % how.sub_rows == 0


def test_a_data_parallel_mesh_runs_the_kernels_on_each_devices_rows():
    """Two devices of two sequences each against one device of four: the
    kernels run in a manual region over the batch, ``d phi`` and the other
    parameters' gradients are summed over the devices."""
    cfg = TransformerConfig.tiny(
        dim=DIM, n_heads=4, n_layers=1, positions='rotary', norm='rms',
        tied_embeddings=False, mlp_dim=64, hc_streams=2, scan_layers=False,
        max_len=64)
    batch = {k: np.random.RandomState(i).randint(0, 256, (4, 64)).astype(
        np.int32) for i, k in enumerate(('tokens', 'targets'))}
    losses = []
    for dp in (1, 2):
        model = TransformerLM(cfg)
        tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(dp=dp))
        state = tr.init(jax.random.PRNGKey(0))
        for _ in range(2):
            state, metrics = tr.step(state, batch)
        losses.append(float(metrics['loss']))
    assert abs(losses[0] - losses[1]) < 1e-4 * abs(losses[0])


# -- the four calls, compiled for the chip at the cell's width ---------------

@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_calls_compile_for_a_v5e_at_xing4s_width(one_chip):
    """``[8192, 14336]`` bf16, four streams, twenty rounds: Mosaic takes
    the four kernels (their slices, their transposes, their VMEM) and the
    compiled program calls each by name. A compile, not a run."""
    n, dim, rows = 4, 3584, 8192

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, y, phi, alpha, bias):
        u, held, x = hk.enter(x, phi, alpha, bias, n, 20, (-30.0, 30.0),
                              EPS, interpret=False)
        out = hk.leave(x, y + u, held, n, 20, interpret=False)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(held)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shaped((rows, n * dim), jnp.bfloat16),
        shaped((rows, dim), jnp.bfloat16),
        shaped((n * dim, n * (n + 2)), jnp.bfloat16),
        shaped((3,), jnp.float32), shaped((n * (n + 2),), jnp.float32),
    ).compile().as_text()
    for name in ('hc_enter_fwd', 'hc_leave_fwd', 'hc_leave_bwd',
                 'hc_enter_bwd'):
        assert name in text, name
