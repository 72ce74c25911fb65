"""The gate norm's kernels alone, on a TPU and nowhere else: ms a call
of ``ssm_gate_norm_fwd`` and ``ssm_gate_norm_bwd`` at Nemotron-3-Nano's
Mamba-2 layers (``y`` and ``x [2, 8192, 4096]`` bf16, z the first 4096
columns of the in-projection's ``[2, 8192, 10304]``, 8 groups of 512
lanes), beside XLA's own forward and forward + backward of the same
function (``GatedGroupRMSNorm`` on ``y + D x``, what the layer ran
before), whose outputs and gradients the kernels' are compared with;
``--sweep`` walks the rows a grid step holds, the rows a pass of the
body computes and the widest lane tile.

    chiprun -- python3 tools/ssm_gate_norm_bench.py --sweep

A time here is the DEVICE's, from a ``jax.profiler`` trace of five calls
in a row, as ``tools/ssm_conv_bench.py``'s (``device_ms``: the host's
clock round a call reads 1.0 ms too much, the copy of the projection
into the layout the call wants, left out here). The last line of the
output is one JSON object; the same goes to
``chiprun_out/ssm_gate_norm_bench.json``.
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

from autodist_tpu.kernels import ssm_gate_norm as gn
from autodist_tpu.models.core import GatedGroupRMSNorm
from ssm_conv_bench import device_ms, kernel_ms, worst

SHAPE = (2, 8192, 4096)
WIDTH = 10304
OFFSET = 0
GROUPS = 8
EPS = 1e-5
_SWEEP = ((512, 1024), (16, 32, 64),
          (512, 1024, 2048, 4096))  # ROWS, SUB, MAX_TILE
_NAMES = ('d_y', 'd_x', 'd_z', 'd_skip', 'd_scale')


def _kernels():
    """The two calls as jitted functions, planned by the module's
    constants as they stand."""
    how = gn.plan(SHAPE[1], WIDTH, OFFSET, SHAPE[2], GROUPS)
    if how is None:
        raise ValueError('no plan')

    def fwd(y, x, proj, skip, scale):
        return gn._forward_call(y, x, proj, skip, scale, EPS, how, False)

    def bwd(y, x, proj, skip, scale, ct):
        return gn._backward_call(y, x, proj, skip, scale, ct, EPS, how,
                                 False)
    return how, jax.jit(fwd), jax.jit(bwd)


def _xla():
    """XLA's forward and backward of the same function, the backward
    with the forward it runs again; the gradients in :data:`_NAMES`'
    order, z's as its own columns."""
    norm = GatedGroupRMSNorm(SHAPE[2], GROUPS, eps=EPS, dtype=jnp.bfloat16)

    def fwd(y, x, proj, skip, scale):
        t = y.astype(jnp.float32) + skip * x.astype(jnp.float32)
        return norm.apply({'scale': scale}, t,
                          proj[..., OFFSET:OFFSET + SHAPE[2]])

    def bwd(y, x, proj, skip, scale, ct):
        dy, dx, dproj, dskip, dscale = jax.vjp(fwd, y, x, proj, skip,
                                               scale)[1](ct)
        return (dy, dx, dproj[..., OFFSET:OFFSET + SHAPE[2]], dskip, dscale)
    return jax.jit(fwd), jax.jit(bwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('ssm_gate_norm_bench: times are a TPU\'s or nothing; '
                 'found %r' % device.platform)
    rng = np.random.RandomState(0)
    y, x, ct = (jnp.asarray(rng.randn(*SHAPE), jnp.bfloat16)
                for _ in range(3))
    proj = jnp.asarray(rng.randn(*SHAPE[:2], WIDTH), jnp.bfloat16)
    skip = jnp.asarray(1 + 0.1 * rng.randn(SHAPE[2]), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.randn(SHAPE[2]), jnp.float32)
    ins = (y, x, proj, skip, scale)
    out = {'device': device.device_kind, 'shape': SHAPE, 'width': WIDTH,
           'groups': GROUPS}
    x_fwd, x_bwd = _xla()
    want, wants = x_fwd(*ins), x_bwd(*ins, ct)
    out['xla_ops_ms'] = {'fwd': device_ms(x_fwd, *ins),
                         'fwd_and_bwd': device_ms(x_bwd, *ins, ct)}
    out['xla_ms'] = {name: sum(ops.values())
                     for name, ops in out['xla_ops_ms'].items()}
    print('xla', out['xla_ms'], out['xla_ops_ms'], flush=True)

    def run():
        how, fwd, bwd = _kernels()
        got, gots = fwd(*ins), bwd(*ins, ct)
        res = {'plan': how._asdict(),
               'fwd_ms': kernel_ms(fwd, *ins, prefix='ssm_gate_norm_'),
               'bwd_ms': kernel_ms(bwd, *ins, ct, prefix='ssm_gate_norm_')}
        res['against_xla'] = {'out': worst(got, want), **{
            name: worst(g, w) for name, g, w in zip(_NAMES, gots, wants)}}
        res['out_bits_differ'] = float(np.mean(
            np.asarray(got, np.float32) != np.asarray(want, np.float32)))
        return res
    out['kernels'] = run()
    print('kernels', out['kernels'], flush=True)
    if args.sweep:
        out['sweep'] = []
        committed = gn.ROWS, gn.SUB, gn.MAX_TILE
        for rows, sub, tile in itertools.product(*_SWEEP):
            gn.ROWS, gn.SUB, gn.MAX_TILE = rows, sub, tile
            try:
                res = run()
            except Exception as e:   # a step the chip's compiler refuses
                res = {'refused': str(e)[-300:]}
            out['sweep'].append({'rows': rows, 'sub': sub, 'tile': tile,
                                 **res})
            print('sweep', out['sweep'][-1], flush=True)
        gn.ROWS, gn.SUB, gn.MAX_TILE = committed
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/ssm_gate_norm_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
