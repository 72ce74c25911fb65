"""The q/k norm's kernels alone, on a TPU and nowhere else: ms a call of
``qk_norm_fwd`` and ``qk_norm_bwd`` at SDAR-30B-A3B's attention (the
projection's output ``[2, 16384, 5120]`` bf16: 32 q heads and 4 k heads of
128 lanes normed, v's 512 lanes behind them), beside XLA's own forward and
forward + backward of the same function (``models/attention.head_rms_norm``,
what the layer ran before), whose output and gradients the kernels' are
compared with; ``--sweep`` walks the rows a grid step holds, the rows a
pass of the body computes and the widest lane tile.

    chiprun -- python3 tools/qk_norm_bench.py --sweep

A time here is the DEVICE's, from a ``jax.profiler`` trace of five calls
in a row, as ``tools/ssm_conv_bench.py``'s (``device_ms``). The last line
of the output is one JSON object; the same goes to
``chiprun_out/qk_norm_bench.json``.
"""
import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.kernels import qk_norm as qn
from autodist_tpu.models.attention import head_rms_norm
from ssm_conv_bench import device_ms, kernel_ms, worst

SHAPE = (2, 16384, 5120)
HEADS = 36          # 32 q + 4 k
D = 128
EPS = 1e-6
_SWEEP = ((512, 1024, 2048), (32, 64, 128, 256),
          (256, 1024))  # ROWS, SUB, MAX_TILE


def _forms(norm):
    """``norm``'s forward, and its backward with the forward it runs
    again, as jitted functions of ``(x, scale[, dy])``."""
    def fwd(x, scale):
        return norm(x, scale, D, EPS)

    def bwd(x, scale, dy):
        return jax.vjp(fwd, x, scale)[1](dy)
    return jax.jit(fwd), jax.jit(bwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('qk_norm_bench: times are a TPU\'s or nothing; found %r'
                 % device.platform)
    rng = np.random.RandomState(0)
    x, dy = (jnp.asarray(rng.randn(*SHAPE), jnp.bfloat16) for _ in range(2))
    scale = jnp.asarray(1 + 0.1 * rng.randn(HEADS * D), jnp.float32)
    nbytes = x.size * x.dtype.itemsize
    out = {'device': device.device_kind, 'shape': SHAPE, 'heads': HEADS,
           'head_dim': D, 'fwd_gb': 2 * nbytes / 1e9,
           'bwd_gb': 3 * nbytes / 1e9}
    x_fwd, x_bwd = _forms(head_rms_norm)
    want, wants = x_fwd(x, scale), x_bwd(x, scale, dy)
    ops = {'fwd': device_ms(x_fwd, x, scale),
           'fwd_and_bwd': device_ms(x_bwd, x, scale, dy)}
    out['xla_ms'] = {name: sum(ms.values()) for name, ms in ops.items()}
    # (a head's selects are a hundred operations of 0.1 us each)
    out['xla_ops_ms'] = {name: {op: t for op, t in ms.items() if t > 0.01}
                         for name, ms in ops.items()}
    print('xla', out['xla_ms'], flush=True)

    def through_kernels(x, scale, d, eps):
        # (the module's constants are read when the call is traced: a
        # fresh jit per sweep step, no cache of an older plan)
        return qn.head_norm(x, scale, d, eps, interpret=False)

    def run():
        how = qn.plan(SHAPE[0] * SHAPE[1], SHAPE[2], HEADS, D)
        fwd, bwd = _forms(through_kernels)
        got, gots = fwd(x, scale), bwd(x, scale, dy)
        res = {'plan': how._asdict(),
               'fwd_ms': kernel_ms(fwd, x, scale, prefix='qk_norm_'),
               'bwd_ops_ms': device_ms(bwd, x, scale, dy)}
        res['bwd_ms'], = (ms for name, ms in res['bwd_ops_ms'].items()
                          if name.startswith('qk_norm_bwd'))
        res['fwd_gb_per_s'] = out['fwd_gb'] / res['fwd_ms'] * 1e3
        res['bwd_gb_per_s'] = out['bwd_gb'] / res['bwd_ms'] * 1e3
        res['against_xla'] = {
            'out': worst(got, want), 'd_x': worst(gots[0], wants[0]),
            'd_scale': worst(gots[1], wants[1])}
        res['out_bits_differ'] = float(np.mean(
            np.asarray(got, np.float32) != np.asarray(want, np.float32)))
        res['v_bits_differ'] = float(np.mean(np.asarray(
            got[..., HEADS * D:] != x[..., HEADS * D:])))
        return res

    out['kernels'] = run()
    print('kernels', out['kernels'], flush=True)
    if args.sweep:
        out['sweep'] = []
        committed = qn.ROWS, qn.SUB, qn.MAX_TILE
        for rows, sub, tile in itertools.product(*_SWEEP):
            qn.ROWS, qn.SUB, qn.MAX_TILE = rows, sub, tile
            try:
                res = run()
            except Exception as e:   # a step the chip's compiler refuses
                res = {'refused': str(e)[-300:]}
            res.pop('bwd_ops_ms', None)
            out['sweep'].append({'rows': rows, 'sub': sub, 'tile': tile,
                                 **res})
            print('sweep', out['sweep'][-1], flush=True)
        qn.ROWS, qn.SUB, qn.MAX_TILE = committed
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/qk_norm_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
