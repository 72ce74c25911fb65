"""BERT-large pre-training benchmark (reference examples/benchmark/bert.py
role) on the functional Trainer: masked-LM-style training of the
TransformerLM in bfloat16 with LAMB/AdamW, multi-axis parallelism via
ParallelSpec (dp/tp/sp/pp/zero).

    python examples/bert.py --config bert_large --batch 128 --steps 20
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/bert.py --config tiny --tp 2 --steps 3
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--config', default='tiny',
                   choices=['tiny', 'gpt_small', 'bert_large'])
    p.add_argument('--batch', type=int, default=8,
                   help='per-chip batch. Measured v5e optima for '
                        'bert_large: 224 at seq 128 (phase 1), 96 at '
                        'seq 512 (phase 2); non-monotonic landscape '
                        '(BASELINE.md round-5)')
    p.add_argument('--seq', type=int, default=None)
    p.add_argument('--steps', type=int, default=10)
    p.add_argument('--lr', type=float, default=1e-4)
    p.add_argument('--optimizer', default='adamw',
                   choices=['adamw', 'lamb'])
    p.add_argument('--dp', type=int, default=None)
    p.add_argument('--tp', type=int, default=1)
    p.add_argument('--pp', type=int, default=1)
    p.add_argument('--sp', type=int, default=1)
    p.add_argument('--sp-mode', default='ring',
                   choices=['ring', 'ulysses'])
    p.add_argument('--zero', type=int, default=1)
    p.add_argument('--microbatches', type=int, default=1)
    p.add_argument('--pp-schedule', default='gpipe',
                   choices=['gpipe', '1f1b'],
                   help="'1f1b': custom-vjp interleaved schedule — live "
                        'activations bounded by the pipe depth '
                        '(embed/head folded into the first/last stages)')
    p.add_argument('--pp-variant', default='auto',
                   choices=['auto', 'remat', 'stash'],
                   help='1f1b backward: remat (pp-bounded memory, ~3 '
                        'fwd passes) | stash (per-microbatch boundary '
                        'stash, ~2 fwd) | auto (stash while it fits)')
    p.add_argument('--grad-accum', type=int, default=1)
    p.add_argument('--fp32', action='store_true')
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.models.transformer import (TransformerConfig,
                                                 TransformerLM)
    from autodist_tpu.parallel.axes import ParallelSpec

    dtype = jnp.float32 if (args.fp32 or args.config == 'tiny') \
        else jnp.bfloat16
    cfg = getattr(TransformerConfig, args.config)(
        dtype=dtype, remat=(args.config == 'bert_large'))
    seq = args.seq or (512 if args.config == 'bert_large' else 64)
    model = TransformerLM(cfg)
    opt = (optax.lamb if args.optimizer == 'lamb' else optax.adamw)(args.lr)
    spec = ParallelSpec(dp=args.dp, tp=args.tp, pp=args.pp, sp=args.sp,
                        sp_mode=args.sp_mode, zero=args.zero,
                        microbatches=args.microbatches,
                        pp_schedule=args.pp_schedule,
                        pp_variant=args.pp_variant,
                        grad_accum=args.grad_accum)
    trainer = Trainer(model, opt, spec=spec)
    state = trainer.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    batch = {
        'tokens': rng.randint(0, cfg.vocab, (args.batch, seq),
                              dtype=np.int32),
        'targets': rng.randint(0, cfg.vocab, (args.batch, seq),
                               dtype=np.int32)}

    state, loss, dt = _common.timed_steps(trainer, state, batch, args.steps)
    n = len(jax.devices())
    tps = args.steps * args.batch * seq / dt
    print('%s (%s): %.0f tokens/s (%.0f tokens/s/chip), loss=%.4f' %
          (args.config, dict(trainer.mesh.shape), tps, tps / n, loss))


if __name__ == '__main__':
    main()
