"""Minimal sentiment classifier through the reference-shaped DSL API.

Counterpart of ``/root/reference/examples/sentiment_classifier.py``: an
embedding-bag + 2-layer MLP under ``autodist.scope()`` with
``PartitionedPS`` — the embedding table is the interesting variable
(sparse gradient, partitioned over PS destinations,
``partitioned_ps_strategy.py:89-96``), which here lowers to a sharded
(ids, rows) wire over the mesh.

The reference example downloads IMDB; this image has no network egress,
so the demo trains on synthetic token sequences whose label is planted
on a few indicator words — enough signal for the loss to fall. Swap in
a real tokenized dataset for real work.

    python examples/sentiment_classifier.py
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sentiment_classifier.py --strategy Parallax
"""
import argparse
import time

import _common  # noqa: F401  (path + compile-cache bootstrap)
import numpy as np

import autodist_tpu as ad
from autodist_tpu import strategy as strategies

VOCAB, EMBED, HIDDEN, SEQ = 10000, 16, 16, 256


def synthetic_reviews(n, rng):
    """Token sequences with a planted sentiment signal: ids < 50 are
    'positive' words, 50..99 'negative'; the label is which side
    dominates."""
    tokens = rng.randint(100, VOCAB, size=(n, SEQ))
    pos = rng.randint(0, 8, size=n)
    neg = rng.randint(0, 8, size=n)
    for i in range(n):
        tokens[i, :pos[i]] = rng.randint(0, 50, size=pos[i])
        tokens[i, pos[i]:pos[i] + neg[i]] = \
            rng.randint(50, 100, size=neg[i])
    return tokens.astype(np.int32), \
        (pos > neg).astype(np.float32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--strategy', default='PartitionedPS')
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--log-frequency', type=int, default=10)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    tokens, labels = synthetic_reviews(4096, rng)

    autodist = ad.AutoDist(
        strategy_builder=getattr(strategies, args.strategy)())
    with autodist.scope():
        x = ad.placeholder(shape=[None, SEQ], dtype=np.int32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        emb = ad.Variable(
            rng.rand(VOCAB, EMBED).astype(np.float32), name='emb')
        w1 = ad.Variable(
            rng.rand(EMBED, HIDDEN).astype(np.float32), name='w1')
        b1 = ad.Variable(np.zeros(HIDDEN, np.float32), name='b1')
        w2 = ad.Variable(
            rng.rand(HIDDEN, 1).astype(np.float32), name='w2')
        b2 = ad.Variable(np.zeros(1, np.float32), name='b2')

        h = ad.ops.reduce_mean(ad.ops.embedding_lookup(emb, x), axis=1)
        h = ad.ops.relu(ad.ops.matmul(h, w1) + b1)
        logits = ad.ops.squeeze(ad.ops.matmul(h, w2) + b2, axis=-1)
        loss = ad.ops.reduce_mean(
            ad.ops.sigmoid_cross_entropy_with_logits(labels=y,
                                                     logits=logits))
        train_op = ad.optimizers.Adam(0.02).minimize(loss)

        sess = autodist.create_distributed_session()
        prev = time.time()
        for step in range(args.steps):
            lo = (step * args.batch_size) % (4096 - args.batch_size)
            lv, _ = sess.run(
                [loss, train_op],
                {x: tokens[lo:lo + args.batch_size],
                 y: labels[lo:lo + args.batch_size]})
            if step % args.log_frequency == 0:
                now = time.time()
                wps = args.batch_size * args.log_frequency / (now - prev)
                print('Iteration %d, time = %.2fs, wps = %.0f, '
                      'train loss = %.4f'
                      % (step, now - prev, wps, float(lv)))
                prev = now
        emb_val, = sess.run([emb])
        print('emb table: shape %s, norm %.4f'
              % (emb_val.shape, np.linalg.norm(emb_val)))


if __name__ == '__main__':
    main()
