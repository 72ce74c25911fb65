"""The connections' kernels alone, on a TPU and nowhere else: ms a call of
``hc_enter_fwd``, ``hc_leave_fwd``, ``hc_leave_bwd`` and ``hc_enter_bwd``
at Xing4.0-29B-A4B's residual path (the streams ``[2, 4096, 14336]``
bf16: four streams of 3584; 20 Sinkhorn-Knopp rounds) and the GB/s each
reaches against the chip's 819, beside XLA's own forward and forward +
backward of the same function
(``models/hyper_connections.HyperConnection`` in ``jax.numpy``, what the
layer ran before), whose outputs and gradients the kernels' are compared
with; ``--sweep`` walks the rows a grid step holds, the rows a pass of the
body computes and the steps unrolled at lowering.

    chiprun -- python3 tools/hc_bench.py --sweep

A connection here is ``(u, x') = (H_pre x, H_res x + H_post^T y)`` of
independent ``x`` and ``y`` (the sublayer between them is not run), its
backward the vjp at given ``du`` and ``dx'``: every one of the four
kernels runs once, and ``hc_enter_bwd`` adds the part of ``dx`` that
``hc_leave_bwd`` left. A time is the DEVICE's, from a ``jax.profiler``
trace of five calls in a row, as ``tools/ssm_conv_bench.py``'s
(``device_ms``). The last line of the output is one JSON object; the same
goes to ``chiprun_out/hc_bench.json``.
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

from autodist_tpu.kernels import hyper_connections as hk
from autodist_tpu.models.hyper_connections import HyperConnection
from ssm_conv_bench import device_ms, worst

BATCH, SEQ, STREAMS, DIM = 2, 4096, 4, 3584
ITERS, CLAMP, EPS = 20, (-30.0, 30.0), 1e-6
_SWEEP = ((128, 256), (64, 128), (7, 14, 28))  # ROWS, SUB, UNROLL
# the streams ([rows, n dim]) and the one-stream arrays ([rows, dim]) a
# kernel reads and writes
_PASSES = {'hc_enter_fwd': (1, 1), 'hc_leave_fwd': (2, 1),
           'hc_leave_bwd': (3, 2), 'hc_enter_bwd': (3, 1)}


def _xla(hc):
    def connection(x, y, params):
        pre, post, res = hc.coefficients(params, x)
        return hc.read(x, pre), hc.write(x, y, post, res)
    return connection


def _kernels(x, y, params):
    # (the module's constants are read when the call is traced: a fresh
    # jit per sweep step, no cache of an older plan)
    u, held, x = hk.enter(x, params['phi'], params['alpha'], params['bias'],
                          STREAMS, ITERS, CLAMP, EPS, interpret=False)
    return u, hk.leave(x, y, held, STREAMS, ITERS, interpret=False)


def _forms(connection):
    """``connection``'s forward, and its backward with the forward it runs
    again, as jitted functions."""
    def bwd(x, y, params, du, dout):
        return jax.vjp(connection, x, y, params)[1]((du, dout))
    return jax.jit(connection), jax.jit(bwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('hc_bench: times are a TPU\'s or nothing; found %r'
                 % device.platform)
    rng = np.random.RandomState(0)
    hc = HyperConnection(DIM, STREAMS, ITERS, CLAMP, EPS, jnp.bfloat16)
    params = hc.init(jax.random.PRNGKey(0))
    # away from the plain residual path: gates that let the token move
    # its coefficients, biases spread
    params['alpha'] = jnp.full((3,), 1.0, jnp.float32)
    params['bias'] = params['bias'] + jnp.asarray(
        0.5 * rng.randn(STREAMS * (STREAMS + 2)), jnp.float32)
    x, dout = (jnp.asarray(rng.randn(BATCH, SEQ, STREAMS * DIM),
                           jnp.bfloat16) for _ in range(2))
    y, du = (jnp.asarray(rng.randn(BATCH, SEQ, DIM), jnp.bfloat16)
             for _ in range(2))
    wide, one = (a.size * a.dtype.itemsize / 1e9 for a in (x, y))
    out = {'device': device.device_kind, 'rows': BATCH * SEQ,
           'streams': STREAMS, 'dim': DIM, 'iters': ITERS,
           'gb': {name: w * wide + o * one
                  for name, (w, o) in _PASSES.items()}}
    x_fwd, x_bwd = _forms(_xla(hc))
    want, wants = x_fwd(x, y, params), x_bwd(x, y, params, du, dout)
    ops = {'fwd': device_ms(x_fwd, x, y, params),
           'fwd_and_bwd': device_ms(x_bwd, x, y, params, du, dout)}
    out['xla_ms'] = {name: sum(ms.values()) for name, ms in ops.items()}
    out['xla_ops_ms'] = {name: {op: t for op, t in ms.items() if t > 0.05}
                         for name, ms in ops.items()}
    print('xla', out['xla_ms'], flush=True)

    def run():
        how = hk.plan(BATCH * SEQ, STREAMS, DIM, jnp.bfloat16, ITERS)
        fwd, bwd = _forms(_kernels)
        got, gots = fwd(x, y, params), bwd(x, y, params, du, dout)
        # (the backward's program runs no `hc_leave_fwd`: nothing reads
        # its x')
        ops = {**device_ms(fwd, x, y, params),
               **device_ms(bwd, x, y, params, du, dout)}
        res = {'plan': how._asdict(), 'ops_ms': ops, 'ms': {}, 'gb_per_s': {}}
        for name in _PASSES:
            res['ms'][name] = sum(ms for op, ms in ops.items()
                                  if op.startswith(name))
            res['gb_per_s'][name] = out['gb'][name] / res['ms'][name] * 1e3
        res['against_xla'] = {
            'u': worst(got[0], want[0]), 'out': worst(got[1], want[1]),
            'd_x': worst(gots[0], wants[0]), 'd_y': worst(gots[1], wants[1]),
            **{'d_' + k: worst(gots[2][k], wants[2][k]) for k in params}}
        return res

    out['kernels'] = run()
    print('kernels', out['kernels'], flush=True)
    if args.sweep:
        out['sweep'] = []
        committed = hk.ROWS, hk.SUB, hk.UNROLL
        for rows, sub, unroll in itertools.product(*_SWEEP):
            if sub > rows:
                continue
            hk.ROWS, hk.SUB, hk.UNROLL = rows, sub, unroll
            try:
                res = run()
            except Exception as e:   # a step the chip's compiler refuses
                res = {'refused': str(e)[-300:]}
            res.pop('ops_ms', None)
            out['sweep'].append({'rows': rows, 'sub': sub, 'unroll': unroll,
                                 **res})
            print('sweep', out['sweep'][-1], flush=True)
        hk.ROWS, hk.SUB, hk.UNROLL = committed
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/hc_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
