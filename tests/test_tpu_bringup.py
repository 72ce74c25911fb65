"""TPU bring-up invariants (PR 21), all checkable without a chip.

``chip_smoke.py`` stays runnable and honest: it refuses to run without
a TPU, its ``--cpu-dry-run`` drives every phase on the CPU, no phase's
failure can be swallowed, and the compile cache it (and every other
entry point) uses can be placed from outside. One process per chip: the
launcher parent holds no chips, and processes that share a TPU host
either get one chip each from the resource spec or are refused before
anything starts. And the steps that carry a Pallas kernel must lower for
the TPU, which cross-platform lowering from the CPU can check.

The subprocess tests here are the slow ones of this file's family, so
the file sorts late in the suite on purpose.
"""
import ast
import functools
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from conftest import free_port

from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runtime import coordinator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')


# -- Mosaic's lowering checks, without a chip ------------------------------

@pytest.mark.parametrize('spec_kw,seq', [
    (dict(dp=2, tp=2), 512),                      # nested-manual route
    (dict(dp=1, sp=2, sp_mode='ulysses'), 1024),  # Trainer's sp region
], ids=['dp2_tp2', 'sp2_ulysses'])
def test_kernel_regions_lower_for_tpu(monkeypatch, spec_kw, seq):
    """The step must LOWER for the TPU with the real Mosaic kernel in
    it, under GSPMD (dp x tp) and inside the Trainer's manual region.
    Interpret mode never reaches Mosaic's check that no mesh axis is
    left automatic around a kernel, so the CPU mesh ran these routes
    for twenty PRs while the first four-chip run refused them ("Mosaic
    kernels cannot be automatically partitioned"). Cross-platform
    lowering from the CPU runs that check without a chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    monkeypatch.setattr(fa, '_interpret_default', lambda: False)
    cfg = TransformerConfig.tiny(dtype=jnp.bfloat16, n_layers=1,
                                 max_len=seq)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(**spec_kw))
    state = tr.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {'tokens': rng.randint(0, 256, (4, seq), dtype=np.int32),
             'targets': rng.randint(0, 256, (4, seq), dtype=np.int32)}
    step = tr._ensure_step(tr._step_key(batch), state, batch)
    exported = jax.export.export(step, platforms=['tpu'])(
        state, tr.shard_batch(batch))
    assert 'tpu_custom_call' in exported.mlir_module()


@pytest.mark.parametrize('spec_kw', [
    dict(dp=2, ep=2, tp=2), dict(dp=4), dict(dp=1, ep=4)],
    ids=['dp2_ep2_tp2', 'dp4', 'ep4'])
def test_expert_layer_lowers_for_tpu_on_a_sharded_mesh(monkeypatch, spec_kw):
    """A step with the expert layer under a mesh that shards the
    tokens, the experts or their hidden units LOWERS for the TPU with
    the grouped kernels in it (PR 33): the layer runs them on each
    device's shard in a manual region, as attention does its own, and
    no path falls back to ``jax.lax.ragged_dot``."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import grouped_matmul as gm
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    monkeypatch.setattr(fa, '_interpret_default', lambda: False)
    monkeypatch.setattr(gm, '_interpret_default', lambda: False)
    cfg = TransformerConfig.tiny(
        dtype=jnp.bfloat16, n_layers=1, max_len=512, dim=256, n_heads=2,
        mlp_dim=256, gated_mlp=True, gelu='silu', moe_experts=8,
        moe_top_k=2, moe_held=4, moe_aux_coef=0.01)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(**spec_kw))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((4, 512), np.int32)
             for name in ('tokens', 'targets')}
    step = tr._ensure_step(tr._step_key(batch), state, batch)
    text = jax.export.export(step, platforms=['tpu'])(
        state, tr.shard_batch(batch)).mlir_module()
    assert {'moe_gmm', 'moe_gmm_dx', 'moe_gmm_dw', 'moe_combine'} <= set(
        re.findall(r'kernel_name = "(\w+)"', text))
    assert 'ragged_dot' not in text


@pytest.mark.parametrize('local_shape,causal,dp,window', [
    ((96, 16, 512, 64), False, 1, None),    # bert-large.s512.c1
    ((32, 16, 1024, 64), True, 1, None),    # gpt2-medium.s1024.c1
    ((96, 16, 512, 64), False, 4, None),    # bert-large.s512.dp4's shard_map
    # modernbert-large.s8192.c1: a global layer's calls (the multi-block
    # path), a window layer's (the band in its row form, PR 40), and the
    # band under dp=4
    ((4, 16, 8192, 64), False, 1, None),
    ((4, 16, 8192, 64), False, 1, (64, 64)),
    ((4, 16, 8192, 64), False, 4, (64, 64)),
], ids=['s512', 's1024_causal', 's512_dp4', 's8192_global', 's8192_band',
        's8192_band_dp4'])
def test_flash_step_is_three_named_kernels(local_shape, causal, dp, window):
    """The benchmark reads the kernels of a step by name
    (``benchmark/scope_reduce.py``, ``benchmark/flash_kinds.py``):
    forward and backward of ``flash_attention_merged`` at the cells'
    shapes are exactly three Mosaic calls, ``flash_fwd``, ``flash_dq``
    and ``flash_dkv``, with ``_band`` behind each name for a band call;
    and tracing leaves the static plan in the loop ring, with tile
    counts that a position-by-position count confirms. The operands are
    the model's (PR 29): the projection's ``[b, s, 3 * h * d]`` read
    where it lies, and for ModernBERT (PR 32) the rotary positions'
    ``cos`` and ``sin [s, 128]`` beside it, which every shard of a dp
    mesh sees whole; no operand or result of a call has a head's 64
    lanes as its minor dimension."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from autodist_tpu import telemetry
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.parallel.axes import shard_map

    b, h, s, d = local_shape
    rotary = s == 8192

    def attend(qkv, *tables):
        return fa.flash_attention_merged(
            qkv, h, causal=causal, interpret=False, window=window,
            rotary=tables or None)

    shape, sharding, whole = (b, s, 3 * h * d), None, None
    if dp > 1:
        mesh = Mesh(np.array(jax.devices()[:dp]), ('data',))
        attend = shard_map(attend, mesh, (P('data'),) + 2 * rotary * (P(),),
                           P('data'))
        shape = (dp * b,) + shape[1:]
        sharding, whole = (NamedSharding(mesh, spec)
                           for spec in (P('data'), P()))
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    tables = 2 * rotary * (
        jax.ShapeDtypeStruct((s, 128), jnp.float32, sharding=whole),)
    t_before = time.perf_counter()
    exported = jax.export.export(
        jax.jit(jax.value_and_grad(
            lambda qkv, *tables: jnp.sum(
                attend(qkv, *tables).astype(jnp.float32)))),
        platforms=['tpu'])(x, *tables)
    text = exported.mlir_module()
    assert text.count('@tpu_custom_call') == 3
    band = '_band' if window else ''
    assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == [
        'flash_dkv' + band, 'flash_dq' + band, 'flash_fwd' + band]
    calls = [line for line in text.splitlines() if '@tpu_custom_call' in line]
    shapes = {t for line in calls
              for t in re.findall(r'tensor<([0-9x]+)x(?:bf16|f32)>', line)}
    assert shapes == {
        '%dx%dx%d' % (b, s, 3 * h * d), '%dx%dx%d' % (b, s, h * d),
        '%dx%dx1x%d' % (b, h, s)} | ({'%dx128' % s} if rotary else set())
    # dq goes out as the first third of the array dk is then written
    # into in place: the cotangent of the projection's output is never
    # concatenated
    assert sum('output_operand_aliases' in line for line in calls) == 1

    plans = [r for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'flash.plan']
    assert plans and plans[-1]['dur'] is None
    tags = plans[-1]['tags']
    seq = tags['seq']
    assert (seq, tags['head_dim'], tags['causal'], tags['window']) == (
        local_shape[2], local_shape[3], causal,
        list(window) if window else None)
    assert (tags['layout'], tags['lane_block'],
            tags['heads_per_lane_block'], tags['rotary']) == (
                'bsd', 128, 2, rotary)
    allowed = np.tril(np.ones((seq, seq), bool)) if causal else \
        np.ones((seq, seq), bool)
    if window:
        ahead = np.arange(seq)[None, :] - np.arange(seq)[:, None]
        allowed = (ahead >= -window[0]) & (ahead <= window[1])
    for kernel in ('', 'dq_', 'dkv_'):
        bq, bk = tags[kernel + 'tile_q'], tags[kernel + 'tile_k']
        assert local_shape[1] % tags[kernel + 'heads_per_step'] == 0
        if window:
            # the row form (PR 40): no inner grid dimension; one tile
            # for each sub-block of the outer operand's rows, against
            # the run of the inner one's round it (a corner of 64 rows
            # each side), of 32 to 64 in a row of the square; the tiles
            # hold every pair of the band between them, and each is
            # crossed by its edges (or by the sequence's end)
            assert tags['band_form'] == 'row' and tags[kernel + 'one_pass']
            transposed = kernel == 'dkv_'
            size, span = (bk, bq) if transposed else (bq, bk)
            assert span == size + 2 * 64
            assert tags[kernel + 'block_' + 'kq'[transposed]] \
                == tags[kernel + 'block_' + 'qk'[transposed]] + 2 * 64
            assert tags[kernel + 'block_' + 'qk'[transposed]] % size == 0
            pairs = np.pad(allowed.T if transposed else allowed,
                           ((0, 0), (64, 64)))
            tiles = [pairs[i:i + size, i:i + span]
                     for i in range(0, seq, size)]
            assert sum(t.sum() for t in tiles) == allowed.sum()
        else:
            assert tags['band_form'] is None
            assert tags[kernel + 'one_pass'] == (
                tags[kernel + ('block_q' if kernel == 'dkv_' else 'block_k')]
                == seq)
            tiles = [allowed[i:i + bq, j:j + bk]
                     for i in range(0, seq, bq) for j in range(0, seq, bk)]
        assert tags[kernel + 'tiles'] == len(tiles)
        assert tags[kernel + 'live_tiles'] == sum(t.any() for t in tiles)
        assert tags[kernel + 'masked_tiles'] == sum(
            t.any() and not t.all() for t in tiles)


def test_mellum2_period_lowers_with_its_kernels_and_no_repeated_kv():
    """One period of Mellum2 at its published widths (three causal-window
    layers and the full causal YaRN layer, 32 query heads over 4 kv heads
    of 128, top-8 of 64 experts of width 896, two of them held to keep
    the test light) under remat=True with its gradient, as it lowers for
    the TPU (PR 33): the flash calls of both kinds, the three grouped
    products and the combine (PR 34) are there by name; every flash call
    reads the projection's ``[b, s, (32 + 4 + 4) x 128]`` where it lies,
    and no tensor of the step is k or v repeated to 32 heads (``[b, s, 3
    x 4096]``, or ``[b, 32, s, 128]`` beside q) or a one-hot dispatch
    tensor ``[b, s, experts, capacity]``. Of the rows buffer's length
    there is the one bf16 buffer a pass of the combine holds, 2304 wide,
    and the rows' sums are added up by no scatter."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import grouped_matmul as gm
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    b, s = 1, 8192
    cfg = TransformerConfig(
        vocab=256, dim=2304, n_layers=4, n_heads=32, n_kv_heads=4,
        head_dim=128, max_len=s, causal=True, tied_embeddings=False,
        dtype=jnp.bfloat16, remat=True, positions='rotary',
        rope_theta=500000.0, window=1023, global_every=4, global_at=3,
        rope_yarn=dict(factor=16, original_max_position_embeddings=8192,
                       beta_fast=32, beta_slow=1,
                       attention_factor=1.2772588722239782),
        mlp_dim=896, gated_mlp=True, gelu='silu', norm='rms',
        moe_experts=64, moe_top_k=8, moe_held=2, moe_aux_coef=0.0)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((b, s), np.int32)
             for name in ('tokens', 'targets')}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, '_interpret_default', lambda: False)
        patch.setattr(gm, '_interpret_default', lambda: False)
        step = tr._ensure_step(tr._step_key(batch), state, batch)
        shapes = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            batch, tr.batch_sharding(batch))
        text = jax.export.export(step, platforms=['tpu'])(
            state, shapes).mlir_module()
    names = set(re.findall(r'kernel_name = "(\w+)"', text))
    assert names == {'flash_fwd', 'flash_dq', 'flash_dkv', 'flash_fwd_band',
                     'flash_dq_band', 'flash_dkv_band', 'moe_gmm',
                     'moe_gmm_dx', 'moe_gmm_dw', 'moe_combine',
                     'moe_rows_buffer'}
    flash_calls = [line for line in text.splitlines()
                   if '@tpu_custom_call' in line and 'flash_' in line]
    assert flash_calls and all('%dx%dx5120xbf16' % (b, s) in line
                               for line in flash_calls)
    tensors = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32|i32|i1)>', text))
    assert '%dx%dx12288' % (b, s) not in tensors         # 3 x 32 heads
    assert not any(t.startswith('%dx32x%dx128' % (b, s)) for t in tensors)
    assert not any(t.startswith('%dx%dx64x' % (b, s)) for t in tensors)
    # the rows buffer's worst case, tokens x min(8, 2) + a tile an expert,
    # is walked a chunk at a time: of its length there is only what a
    # pass of the combine holds, the experts' outputs (forward) or the
    # gradients w.r.t. their inputs (backward) in bf16; nothing of the
    # experts' inner widths, nothing in f32
    from autodist_tpu.models import moe
    rows = moe.buffer_rows(8192, 8, 2)
    assert rows == 8192 * 2 + 4096 and moe.pass_chunks(rows) == rows // 4096
    long = set(re.findall(r'tensor<%dx([0-9]+)x(\w+)>' % rows, text))
    assert long == {('2304', 'bf16')}
    # and the sums onto the tokens are the kernel's: the scatters left are
    # the top-k's gradient and the embedding's; none is by row (the rows'
    # token and weight come by a sort of the pairs, PR 38)
    scatters = set(re.findall(
        r'"stablehlo.scatter"\(.*?\n\s*\}\) : \([^)]*\) -> '
        r'tensor<([0-9x]+)x\w+>', text, re.S))
    assert scatters == {'%dx64' % s, '256x2304'}


def test_xing4_stack_lowers_with_its_streams_side_by_side():
    """Xing4.0's dense layer and one expert layer at the published widths
    (3584 wide on four residual streams, latent attention with a q rank of
    768 under YaRN at 4096 keys, a dense MLP of 9216, sigmoid 4 of 64
    experts of width 1024, two of them held to keep the test light, a
    shared expert) under remat=True with its gradient, as it lowers for the
    TPU (PR 48): the three latent flash calls are there by name and read q
    from the up-projection's output where it lies; the layers carry the
    streams as ``[b, s, 4 * 3584]`` and no tensor of the step has a stream
    axis of 4 between the positions and the lanes; the connections are the
    four kernels of ``kernels/hyper_connections.py`` by name (PR 51), on
    the streams where they lie and with no f32 copy of them; what they
    hold between a sublayer's two halves is ``[n (n + 1), b s]``, tokens
    last, and nothing is ``[b, s, 4, 4]``."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import grouped_matmul as gm
    from autodist_tpu.kernels import hyper_connections as hk
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    b, s, n, d = 1, 4096, 4, 3584
    cfg = TransformerConfig(
        vocab=256, dim=d, n_layers=2, n_heads=32, max_len=s, causal=True,
        tied_embeddings=False, dtype=jnp.bfloat16, remat=True,
        positions='rotary', rope_theta=1e4, latent_rank=512,
        latent_q_rank=768, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_yarn=dict(factor=64, original_max_position_embeddings=4096,
                       beta_fast=32, beta_slow=1, attention_factor=1.0,
                       score_factor=2.00474),
        mlp_dim=1024, gated_mlp=True, gelu='silu', norm='rms',
        mlp_bias=False, moe_experts=64, moe_top_k=4, moe_held=2,
        moe_aux_coef=0.0, dense_lead=1, dense_mlp_dim=9216,
        moe_scoring='sigmoid', moe_scale=2.0, moe_shared_dim=1024,
        hc_streams=n)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((b, s), np.int32)
             for name in ('tokens', 'targets')}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, '_interpret_default', lambda: False)
        patch.setattr(gm, '_interpret_default', lambda: False)
        patch.setattr(hk, '_interpret_default', lambda: False)
        step = tr._ensure_step(tr._step_key(batch), state, batch)
        shapes = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            batch, tr.batch_sharding(batch))
        text = jax.export.export(step, platforms=['tpu'])(
            state, shapes).mlir_module()
    names = set(re.findall(r'kernel_name = "(\w+)"', text))
    assert {'flash_fwd_mla', 'flash_dq_mla', 'flash_dkv_mla'} <= names
    assert {'hc_enter_fwd', 'hc_leave_fwd', 'hc_leave_bwd',
            'hc_enter_bwd'} <= names
    assert not re.search(r'%dx%dxf32' % (b * s, n * d), text)
    flash_calls = [line for line in text.splitlines()
                   if '@tpu_custom_call' in line and 'flash_' in line]
    assert len(flash_calls) == 2 * 3
    for line in flash_calls:
        operands = line.split(' : (', 1)[1].split(') -> ')[0]
        assert operands.startswith(
            'tensor<%dx%dx6144xbf16>, tensor<%dx%dx8192xbf16>, '
            'tensor<%dx%dx8192xbf16>, tensor<%dx%dx576xbf16>'
            % ((b, s) * 4)), line
    tensors = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32|i32|i1)>', text))
    assert '%dx%dx%d' % (b, s, n * d) in tensors
    assert '%dx%d' % (n * (n + 1), b * s) in tensors    # H_post | H_res
    assert '%dx%dx%dx%d' % (n, n, b, s) not in tensors  # no round under XLA
    wrong = [t for t in tensors if re.search(
        r'x%dx(%d|%d)$|(^|x)%dx%dx%d$' % (n, d, n, s, n, n), t)]
    assert not wrong, wrong


def test_kanana2_stack_lowers_with_its_kernels_and_nothing_by_head():
    """kanana-2's dense layer and one expert layer at the published
    widths (latent attention: 32 heads of 128 + 64 on one rotary key, v
    heads of 128, a latent of 512; a dense MLP of 6144; sigmoid 6 of 128
    experts of width 768, two of them held to keep the test light, a
    shared expert of 1536) under remat=True with its gradient, as it
    lowers for the TPU (PR 39): the three latent flash calls, the grouped
    products and the combine are there by name; every flash call reads
    the projections' outputs where they lie (q ``[b, s, 6144]``, k_nope
    and v as ``[b, s, 8192]``, the rotary key inside ``[b, s, 576]``);
    and no tensor of the step is by head: no score square ``[., 32, s,
    s]``, no copy of the rotary key repeated to the 32 heads, no q, k or
    v padded to a common head width."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import grouped_matmul as gm
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    b, s = 1, 8192
    cfg = TransformerConfig(
        vocab=256, dim=2048, n_layers=2, n_heads=32, max_len=s, causal=True,
        tied_embeddings=False, dtype=jnp.bfloat16, remat=True,
        positions='rotary', rope_theta=1e6, latent_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, mlp_dim=768,
        gated_mlp=True, gelu='silu', norm='rms', mlp_bias=False,
        moe_experts=128, moe_top_k=6, moe_held=2, moe_aux_coef=0.0,
        dense_lead=1, dense_mlp_dim=6144, moe_scoring='sigmoid',
        moe_scale=2.448, moe_shared_dim=1536)
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((b, s), np.int32)
             for name in ('tokens', 'targets')}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, '_interpret_default', lambda: False)
        patch.setattr(gm, '_interpret_default', lambda: False)
        step = tr._ensure_step(tr._step_key(batch), state, batch)
        shapes = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            batch, tr.batch_sharding(batch))
        text = jax.export.export(step, platforms=['tpu'])(
            state, shapes).mlir_module()
    names = set(re.findall(r'kernel_name = "(\w+)"', text))
    assert names == {'flash_fwd_mla', 'flash_dq_mla', 'flash_dkv_mla',
                     'moe_gmm', 'moe_gmm_dx', 'moe_gmm_dw', 'moe_combine',
                     'moe_rows_buffer'}
    flash_calls = [line for line in text.splitlines()
                   if '@tpu_custom_call' in line and 'flash_' in line]
    # a forward call in each layer and again in neither's backward (the
    # checkpoint keeps its output), a backward pair each
    assert len(flash_calls) == 2 * 3
    for line in flash_calls:
        operands = line.split(' : (', 1)[1].split(') -> ')[0]
        assert operands.startswith(
            'tensor<%dx%dx6144xbf16>, tensor<%dx%dx8192xbf16>, '
            'tensor<%dx%dx8192xbf16>, tensor<%dx%dx576xbf16>'
            % ((b, s) * 4)), line
    tensors = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32|i32|i1)>', text))
    by_head = [t for t in tensors if re.search(
        r'(^|x)(%dx32x|32x%dx)(64|128|192|256|%d)$' % (s, s, s), t)]
    assert not by_head, by_head
    assert not any(t.endswith('%dx%d' % (s, s)) and t != '%dx%dx%d'
                   % (b, s, s) for t in tensors)


def test_ssd_scan_lowers_for_tpu_at_the_published_shape(monkeypatch):
    """The chunked scan's two kernels at Nemotron-3-Nano's shape (PR 41:
    2 x 8192 tokens, 64 heads of 64 in 8 groups, states of 128, bf16)
    lower for the TPU with their gradient: Mosaic's layout checks
    (blocks of ``[128, 8]`` and ``[8, 128]`` for what a head has one
    number of, products contracted over rows) run without a chip."""
    import re

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import ssd_scan as ss

    monkeypatch.setattr(ss, '_interpret_default', lambda: False)
    b, s, heads, groups = 2, 8192, 64, 8
    assert ss.supports(s, heads, groups, 64, 128)
    args = (jax.ShapeDtypeStruct((b, s, heads * 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, s, heads), jnp.float32),
            jax.ShapeDtypeStruct((heads,), jnp.float32),
            jax.ShapeDtypeStruct((b, s, groups * 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, s, groups * 128), jnp.bfloat16))
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(ss.ssd_scan(*a, heads, groups).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4))), platforms=['tpu'])(
                *args).mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(names) == ['ssd_bwd', 'ssd_fwd']
    calls = [line for line in text.splitlines() if '@tpu_custom_call' in line]
    forward = next(line for line in calls if 'ssd_fwd' in line)
    # x, B, C as the projection and the conv leave them; dt and l a
    # column a head and l a row a head; out: y and the entering states
    assert ('tensor<2x8192x4096xbf16>, tensor<2x8192x1024xbf16>, '
            'tensor<2x8192x1024xbf16>, tensor<2x8x8192x8xf32>, '
            'tensor<2x8x8192x8xf32>, tensor<2x8x8x8192xf32>) -> '
            '(tensor<2x8192x4096xbf16>, tensor<2x64x4096x128xf32>)') \
        in forward


def test_ssm_conv_lowers_for_tpu_at_the_published_shape(monkeypatch):
    """The conv's kernel pair at Nemotron-3-Nano's shape (PR 42: the
    in-projection's ``[2, 8192, 10304]`` in bf16, 6144 channels from lane
    4096 as x | B | C, four taps) lowers for the TPU with its gradient:
    both calls take the projection's output ITSELF (the forward twice:
    the tile and the halo before it; the backward three times) and
    nothing of ``[2, 8192, 6144]``; the outputs and the columns'
    cotangents are the three arrays the scan takes; Mosaic's checks of
    the sublane rotations and of the blocks at a column offset run
    without a chip."""
    import re

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import ssm_conv as sc

    monkeypatch.setattr(sc, '_interpret_default', lambda: False)
    widths = (4096, 1024, 1024)
    assert sc.supports(8192, 10304, 4096, widths, 4)
    args = (jax.ShapeDtypeStruct((2, 8192, 10304), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, 6144), jnp.float32),
            jax.ShapeDtypeStruct((6144,), jnp.float32))
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda *a: sum(jnp.sum(part.astype(jnp.float32))
                       for part in sc.conv_silu(*a, 4096, widths)),
        argnums=(0, 1, 2))), platforms=['tpu'])(*args).mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(names) == ['ssm_conv_bwd', 'ssm_conv_fwd']
    calls = {name: next(line for line in text.splitlines()
                        if '@tpu_custom_call' in line and name in line)
             for name in names}
    whole = 'tensor<2x8192x10304xbf16>'
    parts = ', '.join('tensor<2x8192x%dxbf16>' % w for w in widths)
    assert calls['ssm_conv_fwd'].count(whole) == 2 * 3
    assert '-> (%s)' % parts in calls['ssm_conv_fwd']
    assert calls['ssm_conv_bwd'].count(whole) == 3 * 3
    for w in widths:
        assert 'tensor<40x%dxf32>' % w in calls['ssm_conv_bwd']
    assert '8192x6144' not in text


def test_ssm_gate_norm_lowers_for_tpu_at_the_published_shape(monkeypatch):
    """The gate norm's kernel pair at Nemotron-3-Nano's shape (PR 43:
    ``y`` and ``x [2, 8192, 4096]`` and the in-projection's ``[2, 8192,
    10304]`` in bf16, z its first 4096 columns, 8 groups of 512 lanes)
    lowers for the TPU with its gradient: both calls take the
    projection's output ITSELF and nothing sliced from it; the forward
    returns one bf16 tensor, the backward ``dy``, ``dx``, ``dz`` and the
    row blocks' partial sums, nothing f32 of activation size; Mosaic's
    checks of the blocks at a column offset and of the lane
    slices inside a group's loop run without a chip."""
    import re

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import ssm_gate_norm as gn

    monkeypatch.setattr(gn, '_interpret_default', lambda: False)
    assert gn.supports(8192, 10304, 0, 4096, 8)
    part = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16)
    lanes = jax.ShapeDtypeStruct((4096,), jnp.float32)
    args = (part, part, jax.ShapeDtypeStruct((2, 8192, 10304), jnp.bfloat16),
            lanes, lanes)
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(gn.gate_norm(*a, 0, 8, 1e-5).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))), platforms=['tpu'])(*args).mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(names) == ['ssm_gate_norm_bwd', 'ssm_gate_norm_fwd']
    calls = {name: next(line for line in text.splitlines()
                        if '@tpu_custom_call' in line and name in line)
             for name in names}
    whole, tile = 'tensor<2x8192x10304xbf16>', 'tensor<2x8192x4096xbf16>'
    assert (': (%s, %s, %s, tensor<1x4096xf32>, tensor<1x4096xf32>) -> %s'
            % (tile, tile, whole, tile)) in calls['ssm_gate_norm_fwd']
    assert calls['ssm_gate_norm_bwd'].count(whole) == 1
    assert ('-> (%s, %s, %s, tensor<2x8x16x4096xf32>)' % (tile, tile, tile)
            in calls['ssm_gate_norm_bwd'])


def test_nemotron_h_stack_lowers_with_its_kernels(monkeypatch):
    """One layer of each kind of Nemotron-3-Nano's pattern at the
    published widths (a Mamba-2 layer of 64 heads of 64; relu2 experts
    of width 1856, 6 of 128 with two held to keep the test light, and a
    shared expert of 3712; attention of 32 query heads over 2 kv heads
    of 128 with no positions) under remat=True with its gradient, as it
    lowers for the TPU (PR 41): the scan's kernels, the conv's (PR 42),
    the gate norm's (PR 43), the flash kernels and the grouped products
    are there by name; the
    expert width 1856 =
    14.5 x 128 lowers as one block of the whole width; nothing is
    rotated and there is no position table."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import grouped_matmul as gm
    from autodist_tpu.kernels import ssd_scan as ss
    from autodist_tpu.kernels import ssm_conv as sc
    from autodist_tpu.kernels import ssm_gate_norm as gn
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    for module in (fa, gm, ss, sc, gn):
        monkeypatch.setattr(module, '_interpret_default', lambda: False)
    b, s = 1, 8192
    cfg = TransformerConfig(
        vocab=256, dim=2688, n_layers=3, mixers='ME*', n_heads=32,
        n_kv_heads=2, head_dim=128, max_len=s, causal=True,
        tied_embeddings=False, dtype=jnp.bfloat16, remat=True,
        positions='none', mlp_dim=1856, gelu='relu2', norm='rms',
        norm_eps=1e-5, mlp_bias=False, moe_experts=128, moe_top_k=6,
        moe_held=2, moe_aux_coef=0.0, moe_scoring='sigmoid', moe_scale=2.5,
        moe_shared_dim=3712,
        ssm=dict(heads=64, head_dim=64, groups=8, state=128, conv=4))
    assert gm._block(1856) == 1856
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=1))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((b, s), np.int32)
             for name in ('tokens', 'targets')}
    step = tr._ensure_step(tr._step_key(batch), state, batch)
    shapes = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        batch, tr.batch_sharding(batch))
    text = jax.export.export(step, platforms=['tpu'])(
        state, shapes).mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert set(names) == {'ssd_fwd', 'ssd_bwd', 'ssm_conv_fwd',
                          'ssm_conv_bwd', 'ssm_gate_norm_fwd',
                          'ssm_gate_norm_bwd', 'flash_fwd', 'flash_dq',
                          'flash_dkv', 'moe_gmm', 'moe_gmm_dx', 'moe_gmm_dw',
                          'moe_combine', 'moe_rows_buffer'}
    # the scan's forward, the conv's and the gate norm's run again under
    # the block's checkpoint (nothing of them is kept by name); the
    # flash forward does not
    assert (names.count('ssd_fwd'), names.count('ssd_bwd'),
            names.count('ssm_conv_fwd'), names.count('ssm_conv_bwd'),
            names.count('ssm_gate_norm_fwd'),
            names.count('ssm_gate_norm_bwd'),
            names.count('flash_fwd')) == (2, 1, 2, 1, 2, 1, 1)
    assert '8192x4096xf32' not in text
    assert 'rotary' not in text and 'pos_embed' not in str(
        jax.tree.map(lambda a: a.shape, state.params))


@pytest.mark.parametrize('carried', [False, True],
                         ids=['alone', 'onto_the_sum'])
def test_moe_combine_lowers_for_tpu_at_the_cells_shape(carried):
    """The combine of Mellum2's cell (PR 34) LOWERS for the TPU as one
    Mosaic call: a pass's buffer of 98,304 rows of 2304 in bf16, 32,768
    tokens over 16 held experts, with the weights by token; the layer's
    passes add onto the f32 sum in place."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import moe_combine as mc

    rows, tokens, held, dim = 98304, 32768, 16, 2304

    def call(buffer, row_of, weight, limit, total):
        return mc.combine(buffer, row_of, weight, limit=limit,
                          onto=total if carried else None, fresh=limit > 0,
                          out_dtype=jnp.float32, interpret=False)
    text = jax.export.export(jax.jit(call), platforms=['tpu'])(
        jax.ShapeDtypeStruct((rows, dim), jnp.bfloat16),
        jax.ShapeDtypeStruct((tokens, held), jnp.int32),
        jax.ShapeDtypeStruct((tokens, held), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((tokens, dim), jnp.float32)).mlir_module()
    calls = [line for line in text.splitlines()
             if '@tpu_custom_call' in line]
    assert len(calls) == 1 and 'kernel_name = "moe_combine"' in calls[0]
    assert ('output_operand_aliases = [#stablehlo.output_operand_alias'
            in calls[0]) == carried
    assert '-> tensor<%dx%dxf32>' % (tokens, dim) in calls[0]
    assert 'stablehlo.scatter' not in text


# One layer of each cell's model under remat=True with its gradient, as it
# lowers for the TPU: (config, batch a chip, seq, dp, kernel calls). Since
# PR 27 the block's checkpoint keeps the forward kernel's o and lse, so the
# gradient holds each forward kernel once, not twice.
_REMAT_BLOCKS = {
    's512': (dict(causal=False), 96, 512, 1,
             {'flash_fwd': 1, 'flash_dq': 1, 'flash_dkv': 1}),
    's1024_causal': (dict(causal=True), 32, 1024, 1,
                     {'flash_fwd': 1, 'flash_dq': 1, 'flash_dkv': 1}),
    's512_dp4': (dict(causal=False), 96, 512, 4,
                 {'flash_fwd': 1, 'flash_dq': 1, 'flash_dkv': 1}),
    # ModernBERT's layer 0 (global, unrolled) and one period of the scan
    # (window, window, global)
    's8192_global_and_band': (
        dict(causal=False, positions='rotary', window=64, global_every=3,
             rope_theta=160000.0, window_rope_theta=10000.0, n_layers=4,
             embed_norm=True), 4, 8192, 1,
        {'flash_fwd': 2, 'flash_dq': 2, 'flash_dkv': 2,
         'flash_fwd_band': 2, 'flash_dq_band': 2, 'flash_dkv_band': 2}),
}


@functools.lru_cache(maxsize=None)
def _export_remat_block(case):
    """``(StableHLO of the step for the TPU, its transformer.remat
    event, the model's config)`` of one of ``_REMAT_BLOCKS``; exported
    once for the tests that read it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import telemetry
    from autodist_tpu.api import Trainer
    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    kw, per_chip, seq, dp, _ = _REMAT_BLOCKS[case]
    cfg = TransformerConfig(**dict(dict(
        vocab=256, dim=1024, n_layers=1, n_heads=16, max_len=seq,
        dtype=jnp.bfloat16, remat=True), **kw))
    tr = Trainer(TransformerLM(cfg), optax.sgd(0.1),
                 spec=ParallelSpec(dp=dp))
    state = tr.init(jax.random.PRNGKey(0))
    batch = {name: np.zeros((dp * per_chip, seq), np.int32)
             for name in ('tokens', 'targets')}
    t_before = time.perf_counter()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, '_interpret_default', lambda: False)
        step = tr._ensure_step(tr._step_key(batch), state, batch)
        shapes = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            batch, tr.batch_sharding(batch))
        text = jax.export.export(step, platforms=['tpu'])(
            state, shapes).mlir_module()
    event = [r['tags'] for r in telemetry.get().loop_records()
             if r['t0'] >= t_before and r['name'] == 'transformer.remat'][0]
    return text, event, cfg


@pytest.mark.parametrize('case', sorted(_REMAT_BLOCKS))
def test_remat_block_lowers_with_one_forward_kernel(case):
    import collections
    import re

    _, per_chip, seq, _, want = _REMAT_BLOCKS[case]
    text, event, cfg = _export_remat_block(case)
    assert collections.Counter(
        re.findall(r'kernel_name = "(\w+)"', text)) == want
    # o in bf16 and lse in f32, of a chip's share of the batch
    assert event['layers'] == cfg.n_layers
    assert event['saved_bytes_per_layer'] == per_chip * seq * (
        1024 * 2 + 16 * 4)


@pytest.mark.parametrize('case', sorted(_REMAT_BLOCKS))
def test_remat_block_holds_no_tensor_by_head(case):
    """PR 29: the kernels take the projection's ``[b, s, 3 * h * d]`` and
    give the output projection its ``[b, s, h * d]``, so a block under
    remat, forward and backward, transposes no 4-D tensor (q, k, v, do
    into ``[b, h, s, d]``, o, dq, dk, dv out of it) and holds no tensor
    whose minor dimension is a head's 64 lanes, which HBM pads to 128;
    ``delta`` included, which ``flash_dq`` computes from the merged
    ``do`` and ``o``.

    PR 32: rotary positions (ModernBERT) are inside the kernels, so
    every cell's calls are alike: the projection's ``[b, s, 3 * h * d]``
    goes into the kernels as it is (no slice of q, k or v out of it)
    and its cotangent comes back as one array that ``flash_dkv`` writes
    in place (no concatenate of dq, dk, dv). What is left under the
    ``rotary`` scope is the making of the ``cos`` / ``sin`` tables
    ``[s, 128]``, outside the layers: no matmul (``rotary()`` turned
    q and k by a ``[1024, 1024]`` signed permutation, 168 times a step)
    and nothing ``[1024, 1024]``."""
    import re

    kw, per_chip, seq, dp, _ = _REMAT_BLOCKS[case]
    text, _, cfg = _export_remat_block(case)
    types = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32|i32|ui16|i1)>',
                           text))
    assert types and not [t for t in types if t.endswith('x64')]
    transposed = [re.search(r'-> tensor<([0-9x]+)x', line).group(1)
                  for line in text.splitlines()
                  if 'stablehlo.transpose' in line]
    assert not [t for t in transposed if t.count('x') >= 3], transposed

    qkv = '%dx%dx%d' % (dp * per_chip, seq, 3 * cfg.dim)
    lines = text.splitlines()
    assert not [line for line in lines if qkv in line and (
        'stablehlo.slice' in line or 'stablehlo.concatenate' in line)]
    calls = [line for line in lines if '@tpu_custom_call' in line]
    kernels = len(re.findall(r'kernel_name = "flash_dkv', text))
    assert sum('output_operand_aliases' in line for line in calls) == kernels
    # operations by the name their location gives them
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    rotary = [line for line in lines
              for ref in re.findall(r'loc\((#loc\d+)\)\s*$', line)
              if re.search(r'\brotary\b', named.get(ref, ''))]
    assert bool(rotary) == (kw.get('positions') == 'rotary')
    assert not [line for line in rotary if 'dot_general' in line
                or '1024x1024' in line]
    tables = [line for line in rotary if '-> tensor<%dx128xf32>' % seq in line]
    # one cos and one sin a rotary base, whatever the number of layers
    bases = {cfg.rope_theta, cfg.window_rope_theta or cfg.rope_theta}
    assert len([line for line in tables if 'concatenate' in line]) == (
        2 * len(bases) if rotary else 0)


# The Mosaic modules of the existing flash cells' kernels with their
# source locations stripped (the serialized module carries the file's
# path and line numbers, so the bytes differ from checkout to checkout).
# A PR that means to leave these cells' kernels alone (PR 26 added the
# band path beside them) leaves these as they are; one that changes the
# kernels replaces them, on purpose. jax 0.9.0's lowering.
# PR 29 replaced all nine: the kernels work on [b, s, heads * head_dim]
# now, two heads of 64 to a 128-lane block, flash_dq makes delta, and the
# two cells' calls are the model's (q, k, v read out of the projection's
# one [b, s, 3 * h * d], dk written into dq's array in place).
_KERNEL_MODULES = {
    's512': ((96, 16, 512, 64), False, {
        'flash_fwd': 'ce728f40448169bd', 'flash_dq': 'b47e95b84700c249',
        'flash_dkv': '3668fb4edb515cc6'}),
    's1024_causal': ((32, 16, 1024, 64), True, {
        'flash_fwd': '1e3f526c24fc74e6', 'flash_dq': '7a86f7845564c57f',
        'flash_dkv': '5db563625dce051b'}),
    # chip_smoke.py's shape and call (flash_attention on [b, h, s, d]):
    # the multi-block causal path
    's4096_causal': ((2, 12, 4096, 64), True, {
        'flash_fwd': '3ab6d97b0522dfbf', 'flash_dq': '2727da750cfd69fe',
        'flash_dkv': 'd0b2442aa14f6839'}),
    # Mellum2's window layers (PR 33): a causal window of 1024 keys over
    # 32 query heads on 4 kv heads of 128, rotary inside. Hashed on PR
    # 40's parent: the row form PR 40 gave a band of ModernBERT's reach
    # leaves a wide band's tiled walk op for op what it was.
    's8192_gqa_band': ((4, 32, 8192, 128), True, {
        'flash_fwd_band': 'f9c1099f1352f2f6',
        'flash_dq_band': 'b1420991828123a8',
        'flash_dkv_band': 'c878dd81d61e420a'},
        dict(window=(1023, 1023), kv_heads=4)),
}


@pytest.mark.parametrize('case', sorted(_KERNEL_MODULES))
def test_full_call_kernels_are_op_for_op_what_they_were(case):
    """Every call that does not take a form a PR adds lowers to the
    Mosaic module it lowered to before: the cells' full calls, and
    (since PR 40) Mellum2's band call."""
    import base64
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    from autodist_tpu.kernels import flash_attention as fa

    shape, causal, want, *band = _KERNEL_MODULES[case]
    b, h, s, d = shape
    tables = []
    if case == 's4096_causal':
        def attend(q, k, v):
            return fa.flash_attention(q, k, v, causal=causal,
                                      interpret=False)
        args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3
    elif band:
        def attend(qkv, *tables):
            return fa.flash_attention_merged(
                qkv, h, causal=causal, interpret=False, rotary=tables,
                **band[0])
        args = [jax.ShapeDtypeStruct(
            (b, s, (h + 2 * band[0]['kv_heads']) * d), jnp.bfloat16)]
        tables = [jax.ShapeDtypeStruct((s, d), jnp.float32)] * 2
    else:
        def attend(qkv):
            return fa.flash_attention_merged(qkv, h, causal=causal,
                                             interpret=False)
        args = [jax.ShapeDtypeStruct((b, s, 3 * h * d), jnp.bfloat16)]
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args))))), platforms=['tpu'])(
            *args, *tables).mlir_module()
    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    got = {}
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text):
        with context:
            module = ir.Module.parse(
                base64.b64decode(body + '=' * (-len(body) % 4)))
            asm = module.operation.get_asm(enable_debug_info=False)
        got[re.search(r'module @(\w+)', asm).group(1)] = hashlib.sha256(
            asm.encode()).hexdigest()[:16]
    assert got == want


# -- chip_smoke.py ---------------------------------------------------------

def _run_smoke(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=%d'
               % devices)
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=REPO)


def test_cpu_dry_run_drives_every_phase():
    """Four virtual devices, so the dp=4 / dp=2 x tp=2 legs and the
    four-device session legs are on the dry run's path too."""
    out = _run_smoke('--cpu-dry-run', devices=4)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith('CPU DRY RUN'), lines[0]
    assert json.loads(lines[-1]) == {'ok': True, 'cpu_dry_run': True}
    for leg in ('kernel flash', 'bert dp=1 tp=1',
                'bert dp=4 tp=1', 'bert dp=2 tp=2', 'session c0',
                'session dense (PartitionedPS', 'loose mode'):
        assert any(l.startswith(leg) for l in lines), (leg, out.stdout)
    # a dry run never prints a device figure
    assert 'observation' not in out.stdout


def test_refuses_to_run_without_a_tpu():
    out = _run_smoke()
    assert out.returncode != 0
    assert out.stdout.strip() == ''          # no result line
    assert "jax.default_backend() is 'cpu'" in out.stderr
    assert len(out.stderr.strip().splitlines()) == 1, out.stderr


def test_no_phase_failure_can_be_swallowed():
    """``main`` wraps no phase in ``try``; the handlers that exist
    elsewhere name one exception type and re-raise what they do not
    mean to step around."""
    tree = ast.parse(open(SMOKE).read())
    main = next(n for n in tree.body
                if isinstance(n, ast.FunctionDef) and n.name == 'main')
    tries = [n for n in ast.walk(main) if isinstance(n, ast.Try)]
    # the one try in main is the optional libtpu version import
    assert all(isinstance(h.type, ast.Name) and h.type.id == 'ImportError'
               for t in tries for h in t.handlers), ast.dump(tries[0])
    for handler in (n for n in ast.walk(tree)
                    if isinstance(n, ast.ExceptHandler)):
        assert handler.type is not None, 'bare except'
        name = ast.unparse(handler.type)
        assert name not in ('Exception', 'BaseException'), name
        assert name == 'ImportError' or any(
            isinstance(n, ast.Raise) for n in ast.walk(handler)), name


def test_compile_cache_dir_is_left_alone_when_placed(monkeypatch):
    import jax
    from autodist_tpu.utils import jax_env
    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda *a: calls.append(a))
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/some/dir')
    assert jax_env.setup_compile_cache() == '/some/dir'
    assert calls == []                       # JAX reads the variable


def test_compile_cache_default_is_fixed_inside_the_checkout(
        monkeypatch, tmp_path):
    import jax
    from autodist_tpu.utils import jax_env
    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda *a: calls.append(a))
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    expected = os.path.join(REPO, '.jax_cache')
    for cwd in (tmp_path, REPO):             # the same from any cwd
        monkeypatch.chdir(cwd)
        assert jax_env.setup_compile_cache() == expected
    assert calls == [('jax_compilation_cache_dir', expected)] * 2


# -- one process per chip ---------------------------------------------------

def _spec(*tpus):
    """Nodes that are all THIS host, one per entry of ``tpus``."""
    addresses = ['localhost', '127.0.0.1', '127.0.0.2', '127.0.0.3']
    return ResourceSpec(resource_info={'nodes': [
        dict({'address': a, 'chief': i == 0, 'network_bandwidth': 100},
             **({'tpus': t} if t is not None else {}))
        for i, (a, t) in enumerate(zip(addresses, tpus))]})


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(coordinator, 'children_take_chips', lambda: True)


def test_same_host_children_get_one_chip_each(on_tpu):
    spec = _spec([0], [1], [2], [3])
    envs = coordinator.same_host_chip_env(spec, list(spec.nodes))
    assert [envs[a]['TPU_VISIBLE_CHIPS'] for a in spec.nodes] == \
        ['0', '1', '2', '3']
    for env in envs.values():
        assert env['TPU_CHIPS_PER_PROCESS_BOUNDS'] == '1,1,1'
        assert env['TPU_PROCESS_BOUNDS'] == '1,1,1'
        assert env['ALLOW_MULTIPLE_LIBTPU_LOAD'] == '1'


@pytest.mark.parametrize('tpus,complaint', [
    ((None, None), 'declares tpus: no list'),
    (('auto', [1]), 'declares tpus: no list'),
    (([0], [0]), 'chip 0 is given to both'),
    # multi-chip blocks are not handed out: ICI neighbourhood differs
    # from host to host
    (([0, 1], [2, 3]), 'needs exactly one explicit chip'),
])
def test_same_host_children_without_own_chip_are_refused(
        on_tpu, tpus, complaint):
    spec = _spec(*tpus)
    with pytest.raises(ValueError, match=complaint) as exc:
        coordinator.same_host_chip_env(spec, list(spec.nodes))
    assert 'JAX_PLATFORMS=cpu' in str(exc.value)   # says what to change


def test_no_assignment_needed(on_tpu, monkeypatch):
    one_local = ResourceSpec(resource_info={'nodes': [
        {'address': 'localhost', 'chief': True, 'network_bandwidth': 100},
        {'address': '10.9.8.7', 'network_bandwidth': 100}]})
    assert coordinator.same_host_chip_env(
        one_local, list(one_local.nodes)) == {}
    # children pinned to the CPU by name own no chips
    monkeypatch.setattr(coordinator, 'children_take_chips', lambda: False)
    spec = _spec(None, None)
    assert coordinator.same_host_chip_env(spec, list(spec.nodes)) == {}


def test_coordinator_refuses_a_worker_on_the_chiefs_host(on_tpu):
    from autodist_tpu.strategy.base import Strategy
    spec = _spec([0], [1])
    c = coordinator.Coordinator(Strategy(), spec)
    with pytest.raises(RuntimeError, match="chief's own host"):
        c.launch_clients()
    assert not c.supervisors


def test_launcher_parent_initializes_no_backend(tmp_path):
    """``launch_cli`` reads a spec (even a ``tpus: auto`` one), starts
    its children and waits, without ever creating a JAX backend — on a
    TPU host that would take the chips the children need. And with the
    children not pinned to the CPU it refuses to start two of them on
    one host's chips."""
    script = tmp_path / 'child.py'
    script.write_text('print("child ran")\n')
    spec = tmp_path / 'spec.yml'
    spec.write_text(textwrap.dedent('''
        nodes:
          - address: localhost
            chief: true
            tpus: auto
            network_bandwidth: 100
          - address: 127.0.0.1
            tpus: auto
            network_bandwidth: 100
    '''))
    driver = textwrap.dedent('''
        import sys
        from autodist_tpu.runtime.coordinator import launch_cli
        rc = launch_cli(['--spec', %r, %r])
        from jax._src import xla_bridge
        assert not xla_bridge._backends, 'launcher touched a backend'
        sys.exit(rc)
    ''') % (str(spec), str(script))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS='cpu',
               AUTODIST_COORD_SERVICE_ADDR='127.0.0.1:%d' % free_port())
    ok = subprocess.run([sys.executable, '-c', driver], env=env,
                        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stderr[-2000:]
    assert ok.stdout.count('child ran') == 2
    env.pop('JAX_PLATFORMS')
    refused = subprocess.run([sys.executable, '-c', driver], env=env,
                             capture_output=True, text=True, timeout=120)
    assert refused.returncode == 2, refused.stderr[-2000:]
    assert 'declares tpus: no list' in refused.stderr
    assert 'child ran' not in refused.stdout


def test_block_diffusion_kernels_lower_for_tpu_at_the_published_shape(
        monkeypatch):
    """The three kernels under the block-diffusion mask at SDAR's shape
    (PR 45: 2 sequences of 8192, so ``[2, 16384, 5120]`` of q, k, v side
    by side in bf16, 32 query heads over 4 kv heads of 128, blocks of 4,
    rotary tables whose positions repeat) lower for the TPU with their
    gradient: the calls are ``flash_fwd_bd``, ``flash_dq_bd`` and
    ``flash_dkv_bd``, each reads the projection's output ITSELF three
    times and the tables four, the cotangent is one array of its shape,
    and nothing of the step is a score square or by head."""
    import re

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import flash_attention as fa

    monkeypatch.setattr(fa, '_interpret_default', lambda: False)
    b, seq, heads, kv, d = 2, 8192, 32, 4, 128
    rows = 2 * seq
    assert fa.preferred((b, heads, rows, d), kv_heads=kv, block_diffusion=4)

    def call(qkv):
        tables = fa.rotary_tables(jnp.arange(rows) % seq, 1e6, heads, d)
        return jnp.sum(fa.flash_attention_merged(
            qkv, heads, causal=False, rotary=tables, kv_heads=kv,
            block_diffusion=4).astype(jnp.float32))
    text = jax.export.export(jax.jit(jax.value_and_grad(call)),
                             platforms=['tpu'])(jax.ShapeDtypeStruct(
                                 (b, rows, (heads + 2 * kv) * d),
                                 jnp.bfloat16)).mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(names) == ['flash_dkv_bd', 'flash_dq_bd', 'flash_fwd_bd']
    calls = {name: next(line for line in text.splitlines()
                        if '@tpu_custom_call' in line and name in line)
             for name in names}
    whole, table = 'tensor<2x16384x5120xbf16>', 'tensor<16384x128xf32>'
    for name, line in calls.items():
        operands = line.split(' : (', 1)[1].split(') -> ')[0]
        assert operands.startswith(', '.join([whole] * 3)), name
        assert operands.count(table) == 4, name
    assert calls['flash_dq_bd'].split(') -> ')[1].startswith(
        '(%s, tensor<2x32x1x16384xf32>)' % whole)
    # dk goes into dq's array in place; dv is the kv heads' own width
    assert calls['flash_dkv_bd'].split(') -> ')[1].startswith(
        '(%s, tensor<2x16384x512xbf16>)' % whole)
    tensors = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32|i32|i1)>', text))
    assert not [t for t in tensors if t.endswith('16384x16384')]
    assert not [t for t in tensors
                if re.search(r'(^|x)(16384x32x|32x16384x)128$', t)]


def test_qk_norm_lowers_for_tpu_at_the_published_shape(monkeypatch):
    """The q/k norm's kernel pair at SDAR's shape (PR 46: the
    projection's ``[2, 16384, 5120]`` in bf16, 32 q heads and 4 k heads
    of 128 normed, v's 512 lanes behind them) lowers for the TPU with its
    gradient, alone and inside the attention layer: both calls take the
    projection's output ITSELF as ``[32768, 5120]`` and the scale's one
    row; the forward returns one bf16 tensor, the backward ``dx`` and
    the row blocks' partial sums; and the layer's step holds no f32
    tensor of the projection's size and no reshape of its lanes into
    heads."""
    import re

    import jax
    import jax.numpy as jnp

    from autodist_tpu.kernels import flash_attention as fa
    from autodist_tpu.kernels import qk_norm as qn
    from autodist_tpu.models.attention import MultiHeadAttention

    for module in (fa, qn):
        monkeypatch.setattr(module, '_interpret_default', lambda: False)
    b, rows, heads, kv, d = 2, 16384, 32, 4, 128
    width = (heads + 2 * kv) * d
    assert qn.supports(b * rows, width, heads + kv, d)

    def kernels_of(text):
        names = re.findall(r'kernel_name = "(\w+)"', text)
        return names, {name: next(
            line for line in text.splitlines()
            if '@tpu_custom_call' in line and name in line)
            for name in names if name.startswith('qk_norm')}
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda x, scale: jnp.sum(
            qn.head_norm(x, scale, d, 1e-6).astype(jnp.float32)),
        argnums=(0, 1))), platforms=['tpu'])(
            jax.ShapeDtypeStruct((b, rows, width), jnp.bfloat16),
            jax.ShapeDtypeStruct(((heads + kv) * d,),
                                 jnp.float32)).mlir_module()
    names, calls = kernels_of(text)
    assert sorted(names) == ['qk_norm_bwd', 'qk_norm_fwd']
    whole, row = 'tensor<32768x5120xbf16>', 'tensor<1x5120xf32>'
    assert ': (%s, %s) -> %s' % (whole, row, whole) in calls['qk_norm_fwd']
    assert (': (%s, %s, %s) -> (%s, tensor<8x8x5120xf32>)'
            % (whole, whole, row, whole)) in calls['qk_norm_bwd']

    attn = MultiHeadAttention(2048, heads, head_dim=d, num_kv_heads=kv,
                              causal=False, dtype=jnp.bfloat16,
                              rope_theta=1e6, qk_norm=True,
                              block_diffusion=4)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0))
    text = jax.export.export(jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(attn.apply(p, x).astype(jnp.float32)),
        argnums=(0, 1))), platforms=['tpu'])(
            params, jax.ShapeDtypeStruct((b, rows, 2048),
                                         jnp.bfloat16)).mlir_module()
    names, calls = kernels_of(text)
    assert sorted(names) == ['flash_dkv_bd', 'flash_dq_bd', 'flash_fwd_bd',
                             'qk_norm_bwd', 'qk_norm_fwd']
    assert calls['qk_norm_fwd'].count(whole) == 2
    assert calls['qk_norm_bwd'].count(whole) == 3
    tensors = set(re.findall(r'tensor<([0-9x]+)x(?:bf16|f32)>', text))
    assert not re.search(r'(16384|32768)x5120xf32', text)
    assert not [t for t in tensors
                if re.search(r'16384x(32|36|40)x128$', t)]
