"""LM1B language model (reference examples/lm1b role): a multi-layer LSTM
LM with a large vocabulary — the reference pairs it with PartitionedPS
(sparse embedding push/pull, BASELINE.json configs). Text comes from
``SYS_DATA_PATH``/``--data`` (token .npy) or a synthetic stream.

    python examples/lm1b.py --vocab 100000 --steps 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm1b.py --tiny --steps 3
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)
import os

import numpy as np


def load_tokens(args):
    data = args.data or os.environ.get('SYS_DATA_PATH') or ''
    path = os.path.join(data, 'tokens.npy') if data else ''
    if path and os.path.exists(path):
        toks = np.load(path).astype(np.int32)
        need = args.batch * (args.seq + 1)
        toks = np.resize(toks, (need,))
    else:
        rng = np.random.RandomState(0)
        toks = rng.randint(0, args.vocab,
                           (args.batch * (args.seq + 1),), dtype=np.int32)
    toks = toks.reshape(args.batch, args.seq + 1)
    return {'tokens': toks[:, :-1], 'targets': toks[:, 1:]}


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--vocab', type=int, default=100000)
    p.add_argument('--dim', type=int, default=512)
    p.add_argument('--hidden', type=int, default=1024)
    p.add_argument('--layers', type=int, default=2)
    p.add_argument('--batch', type=int, default=128)
    p.add_argument('--seq', type=int, default=32)
    p.add_argument('--steps', type=int, default=10)
    p.add_argument('--lr', type=float, default=1e-3)
    p.add_argument('--tiny', action='store_true')
    p.add_argument('--strategy', default='PartitionedPS')
    p.add_argument('--data', default=None)
    args = p.parse_args()
    if args.tiny:
        args.vocab, args.dim, args.hidden = 1000, 32, 64
        args.batch, args.seq = 16, 16

    import jax
    import optax

    from autodist_tpu import strategy as strategies
    from autodist_tpu.models.rnn import LSTMLM
    from autodist_tpu.strategy.adapter import trainer_from_strategy

    model = LSTMLM(vocab=args.vocab, dim=args.dim, hidden=args.hidden,
                   n_layers=args.layers)
    builder = getattr(strategies, args.strategy)()
    trainer = trainer_from_strategy(model, optax.adam(args.lr), builder)
    state = trainer.init(jax.random.PRNGKey(0))
    batch = load_tokens(args)

    state, loss, dt = _common.timed_steps(trainer, state, batch, args.steps)
    n = len(jax.devices())
    tps = args.steps * args.batch * args.seq / dt
    print('lm1b-lstm [%s]: %.0f tokens/s (%.0f /chip), ppl=%.2f' %
          (args.strategy, tps, tps / n, float(np.exp(min(loss, 20)))))


if __name__ == '__main__':
    main()
