"""Neural Collaborative Filtering benchmark (reference
examples/benchmark/ncf.py role): GMF+MLP towers over user/item embedding
tables — the canonical sparse-variable workload. The default strategy is
the reference's pairing: PSLoadBalancing with partitioned embeddings
(BASELINE.json configs), via the strategy -> pytree adapter.

    python examples/ncf.py --users 100000 --items 50000 --steps 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ncf.py --tiny --steps 3
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--users', type=int, default=138493)   # ml-20m scale
    p.add_argument('--items', type=int, default=26744)
    p.add_argument('--batch', type=int, default=4096)
    p.add_argument('--steps', type=int, default=10)
    p.add_argument('--lr', type=float, default=1e-3)
    p.add_argument('--tiny', action='store_true')
    p.add_argument('--strategy', default='PSLoadBalancing')
    args = p.parse_args()
    if args.tiny:
        args.users, args.items, args.batch = 1000, 500, 256

    import jax
    import optax

    from autodist_tpu import strategy as strategies
    from autodist_tpu.models.ncf import NCF
    from autodist_tpu.strategy.adapter import trainer_from_strategy

    model = NCF(args.users, args.items,
                mf_dim=8 if args.tiny else 64,
                mlp_dims=(16, 8) if args.tiny else (256, 128, 64))
    builder = getattr(strategies, args.strategy)()
    trainer = trainer_from_strategy(model, optax.adam(args.lr), builder)
    state = trainer.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(0)
    batch = {
        'users': rng.randint(0, args.users, (args.batch,), dtype=np.int32),
        'items': rng.randint(0, args.items, (args.batch,), dtype=np.int32),
        'labels': rng.randint(0, 2, (args.batch,), dtype=np.int32)}

    state, loss, dt = _common.timed_steps(trainer, state, batch, args.steps)
    n = len(jax.devices())
    ex = args.steps * args.batch / dt
    print('ncf [%s]: %.0f examples/s (%.0f /chip), loss=%.4f' %
          (args.strategy, ex, ex / n, loss))


if __name__ == '__main__':
    main()
