"""The band kernels alone, on a TPU and nowhere else: ms a call of
``flash_fwd_band``, ``flash_dq_band`` and ``flash_dkv_band`` at ModernBERT's
window layers (``[4, 16, 8192, 64]`` bf16, 64 keys each side), with and
without rotary, in the row form and in the tiled walk of the same
checkout, whose o, lse, dq, dk and dv the row form's are compared with;
``--sweep`` walks the row form's targets (``flash_attention._ROW_TARGETS``
has the table), kernel by kernel.

    chiprun -- python3 tools/flash_band_bench.py --sweep

A time here is the host's clock round 20 calls in a row, not a trace:
it holds each call's dispatch, so it reads a few percent over what the
same kernels take inside a step (``flash_window_ms_per_step`` of a
``--trace 1`` run of ``modernbert-large.s8192.c1`` is the measure of
that). The last line of the output is one JSON object; the same goes to
``chiprun_out/flash_band_bench.json``.
"""
import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.kernels import flash_attention as fa

SHAPE = (4, 16, 8192, 64)
WINDOW = (64, 64)
_SWEEP = ((128, 256), (1, 2, 4, 8), (2, 4, 8, 16))   # sub, steps, heads


def _tiled_plan():
    """The plan the tiled walk has for this band (``_plan``'s other
    branch: the row form is what ``_plan`` itself answers here)."""
    _, h, s, d = SHAPE
    return fa.Plan(**{
        kernel: fa._blocks(h, d, s, targets, None, None)
        for kernel, targets in fa._block_targets(s, False, WINDOW).items()})


def _calls(tables, plan):
    """The three kernels as jitted calls on a packed qkv."""
    _, h, _, d = SHAPE
    scale = d ** -0.5

    def fwd(qkv):
        return fa._fwd((qkv,), tables, h, h, False, scale, plan.fwd, False,
                       WINDOW)

    def dq(qkv, do, o, lse):
        return fa._dq((qkv,), tables, do, o, lse, h, h, False, scale,
                      plan.dq, False, WINDOW)

    def dkv(qkv, do, lse, delta, dqkv):
        return fa._dkv((qkv,), tables, do, lse, delta, h, h, False, scale,
                       plan.dkv, False, WINDOW, dqkv=dqkv)
    # (dk goes into dq's array in place, as in the step: donated, or the
    # call would be timed with a copy of that array)
    return jax.jit(fwd), jax.jit(dq), jax.jit(dkv, donate_argnums=4)


def _ms(call, *args, repeats=20, into=None):
    """Best of three means over ``repeats`` calls in a row; ``into``:
    an array the call takes last, donated, and returns first."""
    def again(out):
        return call(*args) if into is None else call(*args, out[0])
    out = jax.block_until_ready(again((into,)))
    best = float('inf')
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = again(out)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / repeats)
    return 1e3 * best


def _run(tables, qkv, do, plan):
    """Results and ms a call of the three kernels."""
    fwd, dq, dkv = _calls(tables, plan)
    o, lse = fwd(qkv)
    dq_out, delta = dq(qkv, do, o, lse)
    spare = jnp.zeros_like(dq_out)
    dqkv, dv = dkv(qkv, do, lse, delta, dq_out)
    ms = {'fwd': _ms(fwd, qkv), 'dq': _ms(dq, qkv, do, o, lse),
          'dkv': _ms(dkv, qkv, do, lse, delta, into=spare)}
    # (the last third of dqkv is dv's, which the caller writes there)
    width = dv.shape[-1]
    return dict(o=o, lse=lse, dq=dqkv[..., :width],
                dk=dqkv[..., width:2 * width], dv=dv), ms


def _worst(got, want, rows):
    """Largest difference over ``rows`` of the sequence, as a share of
    the largest magnitude of ``want`` there."""
    axis = 3 if got.ndim == 4 else 1
    got, want = (np.asarray(jnp.take(x.astype(jnp.float32),
                                     jnp.arange(rows.start, rows.stop),
                                     axis=axis)) for x in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('flash_band_bench: times are a TPU\'s or nothing; found %r'
                 % device.platform)
    _, h, s, d = SHAPE
    out = {'device': device.device_kind, 'shape': SHAPE, 'window': WINDOW}
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(SHAPE[0], s, 3 * h * d), jnp.bfloat16)
    do = jnp.asarray(rng.randn(SHAPE[0], s, h * d), jnp.bfloat16)
    rotary = fa.rotary_tables(jnp.arange(s), 10000.0, h, d)
    row_plan = fa._plan(SHAPE, False, window=WINDOW)
    for key, tables in (('rotary', rotary), ('plain', None)):
        row, row_ms = _run(tables, qkv, do, row_plan)
        tiles, tiles_ms = _run(tables, qkv, do, _tiled_plan())
        # the whole sequence, and the first and last row blocks (where
        # the corners' index maps clamp)
        against = {name: {where: _worst(row[name], tiles[name], rows)
                          for where, rows in (('all', slice(0, s)),
                                              ('first', slice(0, 256)),
                                              ('last', slice(s - 256, s)))}
                   for name in row}
        out[key] = {'row_ms': row_ms, 'tiles_ms': tiles_ms,
                    'row_against_tiles': against}
        print(key, out[key], flush=True)
    if args.sweep:
        out['sweep'] = []
        for sub, steps, g in itertools.product(*_SWEEP):
            if sub * steps > s:
                continue
            rows = fa._rows(h, d, s, WINDOW, sub, steps, g * d)
            try:
                _, ms = _run(rotary, qkv, do, fa.Plan(rows, rows, rows))
            except Exception as e:   # a step too large for the chip's VMEM
                ms = {'refused': str(e)[-200:]}
            out['sweep'].append({'sub': sub, 'steps': steps, 'g': g, **ms})
            print('sweep', sub, steps, g, ms, flush=True)
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/flash_band_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
