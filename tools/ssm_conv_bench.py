"""The conv kernels alone, on a TPU and nowhere else: ms a call of
``ssm_conv_fwd`` and ``ssm_conv_bwd`` at Nemotron-3-Nano's Mamba-2
layers (``[2, 8192, 10304]`` bf16 from the in-projection, 6144 channels
from lane 4096 as x | B | C of 4096 | 1024 | 1024, four taps), beside
XLA's own forward and backward of the same function
(``ssm_conv.reference``), whose outputs and gradients the kernels' are
compared with; ``--sweep`` walks the rows a grid step holds, the rows a
pass of the body computes and the widest lane tile.

    chiprun -- python3 tools/ssm_conv_bench.py --sweep

A time here is the DEVICE's, from a ``jax.profiler`` trace of five calls
in a row: the mean duration of the operations on the chip's ``XLA Ops``
line, by name. The host's clock round the same calls reads 1.0 ms more
for every form: a ``[2, 8192, 10304]`` array handed to a jitted function
is laid out columns-major on this chip and XLA copies it row-major first
(``%copy``, left out of every sum here); in the step the projection
writes the layout the kernels read. The last line of the output is one
JSON object; the same goes to ``chiprun_out/ssm_conv_bench.json``.
"""
import argparse
import glob
import itertools
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.kernels import ssm_conv as sc

SHAPE = (2, 8192, 10304)
OFFSET = 4096
WIDTHS = (4096, 1024, 1024)
_SWEEP = ((512, 1024), (32, 64, 128), (512, 1024, 2048))  # ROWS, SUB, MAX_TILE


def device_ms(call, *args, repeats=5):
    """Mean ms a call of every operation ``call`` runs on the chip, by
    the operation's name, without the copy of an operand into the
    layout the call wants."""
    from jax.profiler import ProfileData
    jax.block_until_ready(call(*args))
    trace_dir = tempfile.mkdtemp(prefix='ssm_bench.')
    jax.profiler.start_trace(trace_dir)
    for _ in range(repeats):
        out = call(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path, = glob.glob(trace_dir + '/plugins/profile/*/*.xplane.pb')
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != '/device:TPU:0':
            continue
        for line in plane.lines:
            if line.name != 'XLA Ops':
                continue
            for event in line.events:
                name = event.name.split(' = ')[0].lstrip('%')
                if not name.startswith('copy'):
                    ops[name] = ops.get(name, 0.0) \
                        + event.duration_ns / repeats / 1e6
    shutil.rmtree(trace_dir, ignore_errors=True)
    return ops


def kernel_ms(call, *args, prefix='ssm_conv_'):
    """The ms of the one Pallas call named ``prefix``... among what
    ``call`` runs."""
    ms, = (ms for name, ms in device_ms(call, *args).items()
           if name.startswith(prefix))
    return ms


def worst(got, want):
    """Largest ``|got - want|`` as a share of the largest ``|want|``, and
    the L2 distance as a share of ``want``'s norm."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return (float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
            float(np.linalg.norm(got - want) / np.linalg.norm(want)))


def _kernels(widths):
    """The two calls as jitted functions, planned by the module's
    constants as they stand."""
    how = sc.plan(SHAPE[1], SHAPE[2], OFFSET, widths, sc.TAPS)
    if how is None:
        raise ValueError('no plan')

    def fwd(proj, taps, bias):
        return sc._forward_call(proj, taps, bias, OFFSET, widths, how, False)

    def bwd(proj, taps, bias, cts):
        return sc._backward_call(proj, taps, bias, cts, OFFSET, widths, how,
                                 False)
    return how, jax.jit(fwd), jax.jit(bwd)


def _xla():
    """XLA's forward and backward of the same function, the backward
    with the forward it runs again (what the parent's step does)."""
    def fwd(proj, taps, bias):
        return sc.reference(proj, taps, bias, OFFSET, WIDTHS)

    def bwd(proj, taps, bias, cts):
        _, pull = jax.vjp(lambda p, t, b: fwd(p, t, b), proj, taps, bias)
        return pull(tuple(cts))
    return jax.jit(fwd), jax.jit(bwd)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('ssm_conv_bench: times are a TPU\'s or nothing; found %r'
                 % device.platform)
    rng = np.random.RandomState(0)
    channels = sum(WIDTHS)
    proj = jnp.asarray(rng.randn(*SHAPE), jnp.bfloat16)
    taps = jnp.asarray(rng.randn(sc.TAPS, channels) * 12 ** -0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(channels) * 12 ** -0.5, jnp.float32)
    cts = [jnp.asarray(rng.randn(SHAPE[0], SHAPE[1], w), jnp.bfloat16)
           for w in WIDTHS]
    out = {'device': device.device_kind, 'shape': SHAPE, 'offset': OFFSET,
           'widths': WIDTHS}
    x_fwd, x_bwd = _xla()
    want = x_fwd(proj, taps, bias)
    want_proj, want_taps, want_bias = x_bwd(proj, taps, bias, cts)
    out['xla_ops_ms'] = {'fwd': device_ms(x_fwd, proj, taps, bias),
                         'fwd_and_bwd': device_ms(x_bwd, proj, taps, bias,
                                                   cts)}
    out['xla_ms'] = {name: sum(ops.values())
                     for name, ops in out['xla_ops_ms'].items()}
    print('xla', out['xla_ms'], out['xla_ops_ms'], flush=True)

    def run(widths, compare):
        how, fwd, bwd = _kernels(widths)
        got = fwd(proj, taps, bias)
        parts = cts if compare else [jnp.concatenate(cts, axis=-1)]
        d_cols, d_taps, d_bias = bwd(proj, taps, bias, parts)
        res = {'plan': how._asdict(),
               'fwd_ms': kernel_ms(fwd, proj, taps, bias),
               'bwd_ms': kernel_ms(bwd, proj, taps, bias, parts)}
        got = jnp.concatenate(got, axis=-1)
        d_cols = jnp.concatenate(d_cols, axis=-1)
        res['against_xla'] = {
            'out': worst(got, jnp.concatenate(want, axis=-1)),
            'd_cols': worst(d_cols,
                             want_proj[..., OFFSET:OFFSET + channels]),
            'd_taps': worst(d_taps, want_taps),
            'd_bias': worst(d_bias, want_bias)}
        return res
    out['kernels'] = run(WIDTHS, True)
    print('kernels', out['kernels'], flush=True)
    out['one_output'] = run((channels,), False)
    print('one_output', out['one_output'], flush=True)
    if args.sweep:
        out['sweep'] = []
        committed = sc.ROWS, sc.SUB, sc.MAX_TILE
        for rows, sub, tile in itertools.product(*_SWEEP):
            sc.ROWS, sc.SUB, sc.MAX_TILE = rows, sub, tile
            try:
                res = run(WIDTHS, True)
            except Exception as e:   # a step the chip's compiler refuses
                res = {'refused': str(e)[-300:]}
            out['sweep'].append({'rows': rows, 'sub': sub, 'tile': tile,
                                 **res})
            print('sweep', out['sweep'][-1], flush=True)
        sc.ROWS, sc.SUB, sc.MAX_TILE = committed
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/ssm_conv_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
