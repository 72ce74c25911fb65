"""Which attention a model's call takes: the flash kernels through
``MultiHeadAttention`` alone, under a tp mesh (nested manual region), a
dp=8 GSPMD mesh and a mesh with extra live axes; XLA where the heads
tile no lane block.
"""
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.kernels import flash_attention as fa


def test_heads_that_tile_no_lane_block_take_the_xla_path(monkeypatch):
    """Three heads of 64: ``supports`` is False, and the module never
    reaches the kernels, whatever the sequence."""
    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.models.attention import MultiHeadAttention

    def never(*a, **kw):
        raise AssertionError('kernel path taken')

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', never)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)
    mha = MultiHeadAttention(192, 3, causal=False)
    assert mha.kernel_shape((2, 3, 32, 64)) is None
    assert MultiHeadAttention(256, 4).kernel_shape((2, 4, 32, 64)) \
        == (2, 4, 32, 64)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 192), jnp.float32)
    assert mha.apply(mha.init(jax.random.PRNGKey(0)), x).shape == x.shape


def test_tp_mesh_dispatches_via_nested_manual(monkeypatch):
    """Under a dp/tp GSPMD mesh the module hops into a nested shard_map
    so the kernel runs on local shards — and the numbers still match the
    pure-DP run."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}

    def losses(spec):
        tr = Trainer(model, optax.adam(1e-2), spec=spec)
        state = tr.init(jax.random.PRNGKey(0))
        out = []
        for _ in range(2):
            state, m = tr.step(state, batch)
            out.append(float(m['loss']))
        return out

    tp_losses = losses(ParallelSpec(tp=2))
    assert calls['n'] > 0, 'nested-manual kernel path not taken'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10**9)
    dp_losses = losses(ParallelSpec())
    np.testing.assert_allclose(tp_losses, dp_losses, atol=3e-4)


def test_flash_parity_on_dp8_gspmd_mesh_long_seq(monkeypatch):
    """dp=8 GSPMD mesh at seq 2048 (the real crossover regime,
    MIN_KERNEL_SEQ untouched): the nested-manual flash path engages and
    matches the jnp attention path numerically (interpret mode)."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    cfg = TransformerConfig(vocab=64, dim=32, n_layers=1, n_heads=2,
                            max_len=2048, dtype=jnp.float32,
                            scan_layers=False)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 64, (8, 2048)),
             'targets': rng.randint(0, 64, (8, 2048))}

    def one_loss():
        tr = Trainer(model, optax.sgd(0.1), spec=ParallelSpec(dp=8))
        state = tr.init(jax.random.PRNGKey(0))
        _, m = tr.step(state, batch)
        return float(m['loss'])

    flash_loss = one_loss()
    assert calls['n'] > 0, 'nested-manual kernel path not taken'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10 ** 9)
    jnp_loss = one_loss()
    np.testing.assert_allclose(flash_loss, jnp_loss, rtol=2e-4)


def test_flash_dispatch_with_extra_live_mesh_axes(monkeypatch):
    """A live size>1 mesh axis beyond data/heads (here: expert) no
    longer drops long-seq attention to the jnp path (round-2 weak item):
    the nested-manual region runs over data+heads, leaves the extra axis
    untouched, and numbers match the pure-DP run."""
    import optax

    import autodist_tpu.models.attention as attn_mod
    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    calls = {'n': 0}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['n'] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    cfg = TransformerConfig.tiny(dtype=jnp.float32, n_layers=2)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (8, 32)),
             'targets': rng.randint(0, 256, (8, 32))}

    def losses(spec):
        tr = Trainer(model, optax.adam(1e-2), spec=spec)
        state = tr.init(jax.random.PRNGKey(0))
        out = []
        for _ in range(2):
            state, m = tr.step(state, batch)
            out.append(float(m['loss']))
        return out

    mixed = losses(ParallelSpec(dp=2, tp=2, ep=2))
    assert calls['n'] > 0, \
        'kernel path must engage despite the live expert axis'
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 10 ** 9)
    dp_losses = losses(ParallelSpec())
    np.testing.assert_allclose(mixed, dp_losses, atol=3e-4)


def test_module_dispatches_to_kernel(monkeypatch):
    """MultiHeadAttention routes to the kernel exactly when execution is
    device-local and the shape clears the crossover."""
    from autodist_tpu.models.attention import MultiHeadAttention

    calls = {}
    real = fa.flash_attention_merged

    def spy(*a, **kw):
        calls['hit'] = True
        return real(*a, **kw)

    import autodist_tpu.models.attention as attn_mod
    monkeypatch.setattr(attn_mod.fa, 'flash_attention_merged', spy)
    monkeypatch.setattr(attn_mod.fa, 'MIN_KERNEL_SEQ', 16)

    mha = MultiHeadAttention(32, 2)
    params = mha.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 32, 32), jnp.float32)
    out = mha.apply(params, x)
    assert out.shape == (2, 32, 32)
    assert calls.get('hit'), 'kernel path not taken for local execution'
