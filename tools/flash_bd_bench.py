"""The block-diffusion kernels alone, on a TPU and nowhere else: ms a call
of ``flash_fwd_bd``, ``flash_dq_bd`` and ``flash_dkv_bd`` at SDAR-30B-A3B's
attention (``[2, 32 over 4, 2 x 8192, 128]`` bf16 as the projection's
``[2, 16384, 5120]``, blocks of 4, rotary tables of positions that
repeat). The forward at square tiles of 512 (four heads a step: what
all three kernels had until PR 47) and of 1024 at one, two and four
heads a step, each form's o and lse compared with those of the first;
the backward pair as the plan has it. ``--sweep`` adds the forward at
256 and 2048 and the backward pair at 256 and 1024.

    chiprun -- python3 tools/flash_bd_bench.py --sweep

A time here is the DEVICE's, from a ``jax.profiler`` trace of five calls
in a row, as ``tools/ssm_conv_bench.py``'s (``device_ms``). ``ps_per_
element`` is that time over the score elements the form multiplies
(``live_tiles x tile_q x tile_k`` of its ``flash.plan``, a batch and
head); ``roofline_pct`` is the mask's need (``L^2 + L B`` pairs, two
products of ``2 d`` operations a pair in the forward, three in dq, four
in dkv: ``benchmark/bd_kinds.py``) at the chip's 197 TFLOP/s over it,
what ``flash_bd_*_roofline_pct`` of a ``--trace 1`` run of
``sdar-30b-a3b-chat.s8192.c1`` reads inside the step. The last line of
the output is one JSON object; the same goes to
``chiprun_out/flash_bd_bench.json``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.kernels import flash_attention as fa
from ssm_conv_bench import kernel_ms, worst

BATCH, HEADS, KV_HEADS, SEQ, D = 2, 32, 4, 8192, 128
BLOCK = 4
THETA = 1e6
PEAK_FLOPS = 197e12     # one v5e chip, bf16 (benchmark/peaks.json)
PRODUCTS = {'fwd': 2, 'dq': 3, 'dkv': 4}   # benchmark/bd_kinds.NEEDS
_FORMS = ((512, 4), (1024, 1), (1024, 2), (1024, 4))  # tile, heads a step
_SWEEP_FWD = ((256, 8), (2048, 1))
_SWEEP_BWD = (256, 1024)


def _blocks(tile, heads=None):
    """Square tiles of ``tile`` at ``heads`` a step (None: what the plan
    gives a backward kernel at that tile)."""
    blocks = fa._blocks(HEADS, D, SEQ, (tile, tile), None, None,
                        HEADS // KV_HEADS)
    return blocks._replace(heads_per_step=heads or blocks.heads_per_step)


def _priced(kernel, blocks, ms):
    """``ms`` a call beside the ``flash.plan`` tags of ``kernel`` at
    ``blocks``, an element it multiplies and the mask's need."""
    prefix = {'fwd': '', 'dq': 'dq_', 'dkv': 'dkv_'}[kernel]
    tags = fa._plan_tags(fa.Plan(blocks, blocks, blocks), 2 * SEQ, False,
                         None, BLOCK)
    tags = {key: tags[prefix + key] for key in (
        'block_q', 'heads_per_step', 'tiles', 'live_tiles', 'masked_tiles')}
    elements = BATCH * HEADS * tags['live_tiles'] * tags['block_q'] ** 2
    need_s = PRODUCTS[kernel] * 2 * D * BATCH * HEADS * (
        SEQ * SEQ + SEQ * BLOCK) / PEAK_FLOPS
    return {'ms': ms, 'ps_per_element': 1e9 * ms / elements,
            'roofline_pct': 100 * need_s / (1e-3 * ms), **tags}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--sweep', action='store_true')
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != 'tpu':
        sys.exit('flash_bd_bench: times are a TPU\'s or nothing; found %r'
                 % device.platform)
    rows, scale = 2 * SEQ, D ** -0.5
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(BATCH, rows, (HEADS + 2 * KV_HEADS) * D),
                      jnp.bfloat16)
    do = jnp.asarray(rng.randn(BATCH, rows, HEADS * D), jnp.bfloat16)
    tables = fa.rotary_tables(jnp.arange(rows) % SEQ, THETA, HEADS, D)
    out = {'device': device.device_kind,
           'shape': (BATCH, HEADS, KV_HEADS, rows, D), 'block': BLOCK}

    def forward(tile, heads):
        blocks = _blocks(tile, heads)
        call = jax.jit(lambda qkv: fa._fwd(
            (qkv,), tables, HEADS, KV_HEADS, False, scale, blocks, False,
            None, BLOCK))
        try:
            o, lse = call(qkv)
            res = _priced('fwd', blocks, kernel_ms(call, qkv,
                                                   prefix='flash_fwd_bd'))
        except Exception as e:   # a step the chip's compiler refuses
            o = lse = None
            res = {'tile': tile, 'heads': heads, 'refused': str(e)[-300:]}
        return o, lse, res

    def backward(tile, o, lse):
        blocks = _blocks(tile)
        dq = jax.jit(lambda qkv, do, o, lse: fa._dq(
            (qkv,), tables, do, o, lse, HEADS, KV_HEADS, False, scale,
            blocks, False, None, BLOCK))
        # (dk goes into dq's array in place, as in the step: donated)
        dkv = jax.jit(lambda qkv, do, lse, delta, dqkv: fa._dkv(
            (qkv,), tables, do, lse, delta, HEADS, KV_HEADS, False, scale,
            blocks, False, None, dqkv=dqkv, bd=BLOCK), donate_argnums=4)
        try:
            dq_out, delta = dq(qkv, do, o, lse)
            res = {'dq': _priced('dq', blocks, kernel_ms(
                dq, qkv, do, o, lse, prefix='flash_dq_bd'))}
            # (kernel_ms calls six times: an array to write into each)
            spares = iter([jnp.zeros_like(dq_out) for _ in range(6)])
            res['dkv'] = _priced('dkv', blocks, kernel_ms(
                lambda *a: dkv(*a, next(spares)), qkv, do, lse, delta,
                prefix='flash_dkv_bd'))
        except Exception as e:
            res = {'tile': tile, 'refused': str(e)[-300:]}
        return res

    want = None
    out['fwd'] = []
    for tile, heads in _FORMS + (_SWEEP_FWD if args.sweep else ()):
        o, lse, res = forward(tile, heads)
        if want is None:
            want = o, lse
        elif o is not None:
            res['against_512'] = {'o': worst(o, want[0]),
                                  'lse': worst(lse, want[1])}
        out['fwd'].append(res)
        print('fwd', res, flush=True)
    plan = fa._plan((BATCH, HEADS, rows, D), False, kv_heads=KV_HEADS,
                    block_diffusion=BLOCK)
    out['plan'] = {kernel: list(blocks)
                   for kernel, blocks in plan._asdict().items()}
    out['bwd'] = []
    for tile in (plan.dq.block_q,) + (_SWEEP_BWD if args.sweep else ()):
        out['bwd'].append(backward(tile, *want))
        print('bwd', out['bwd'][-1], flush=True)
    os.makedirs('chiprun_out', exist_ok=True)
    with open('chiprun_out/flash_bd_bench.json', 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == '__main__':
    main()
