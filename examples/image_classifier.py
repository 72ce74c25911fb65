"""Minimal image classifier on the zero-touch functional adapter.

Counterpart of ``/root/reference/examples/image_classifier.py`` (a
keras Sequential CNN trained under ``autodist.scope()``): here the
*unmodified user code* is a plain flax module — its own ``init`` and
``apply``, nothing framework-specific — wrapped in
:class:`FunctionalModel` so any reference-style strategy builder
distributes it (the reference achieves the same zero-touch property by
monkey-patching TF internals, ``autodist/patch.py:96-197``).

The reference example downloads Fashion-MNIST; this image has no
network egress, so the demo trains on a synthetic stand-in with the
same shapes (28x28x1, 10 classes). Swap in a real data iterator for
real work.

    python examples/image_classifier.py
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/image_classifier.py --strategy PartitionedPS
"""
import argparse

import _common  # noqa: F401  (path + compile-cache bootstrap)
import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from autodist_tpu import strategy as strategies
from autodist_tpu.strategy.adapter import (FunctionalModel,
                                           trainer_from_strategy)

BATCH_SIZE = 64


class CNN(nn.Module):
    """The reference example's keras Sequential, as a flax module."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(32, (3, 3))(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(10)(x)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--strategy', default='PS',
                   choices=sorted(s for s in dir(strategies)
                                  if s[:1].isupper()))
    p.add_argument('--steps', type=int, default=15)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    # separable synthetic classes: class k is noise around brightness
    # k/10 — stands in for Fashion-MNIST's (28, 28, 1) x 10 classes
    labels = rng.randint(0, 10, size=(512,))
    images = (labels[:, None, None, None] / 10.0 +
              0.1 * rng.rand(512, 28, 28, 1)).astype(np.float32) - 0.5

    mod = CNN()
    example = jnp.zeros((1, 28, 28, 1), jnp.float32)

    def init_fn(key):
        return mod.init(key, example)['params']

    def loss_fn(params, batch):
        logits = mod.apply({'params': params}, batch['image'])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch['label']).mean()

    model = FunctionalModel(init_fn, loss_fn, apply_fn=mod.apply)
    trainer = trainer_from_strategy(
        model, optax.adam(2e-3), getattr(strategies, args.strategy)())
    state = trainer.init(jax.random.PRNGKey(0))

    for step in range(args.steps):
        lo = (step * BATCH_SIZE) % (512 - BATCH_SIZE)
        batch = {'image': images[lo:lo + BATCH_SIZE],
                 'label': labels[lo:lo + BATCH_SIZE]}
        state, metrics = trainer.step(state, batch)
        print('train_loss: %.4f' % float(metrics['loss']))


if __name__ == '__main__':
    main()
