"""Train, export a servable bundle, reload it, and serve.

The reference's SavedModel flow (examples used `SavedModelBuilder` to
hand a trained model to TF Serving); here the bundle is a StableHLO
artifact (`jax.export`) + logical-layout weights that any process with
jax + numpy can serve — no framework import needed at serving time.

    python examples/serving.py --export-dir /tmp/served-model
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/serving.py
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)

import numpy as np

import autodist_tpu as ad
from autodist_tpu.checkpoint.export import load_servable
from autodist_tpu.checkpoint.saver import SavedModelBuilder
from autodist_tpu.strategy import PSLoadBalancing


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--export-dir', default='/tmp/autodist-tpu-serve')
    parser.add_argument('--epochs', type=int, default=20)
    ns = parser.parse_args()

    np.random.seed(0)
    xs = np.random.randn(256, 4).astype(np.float32)
    true_w = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    ys = xs @ true_w + 0.01 * np.random.randn(256, 1).astype(np.float32)

    autodist = ad.AutoDist(strategy_builder=PSLoadBalancing())
    with autodist.scope():
        x = ad.placeholder(shape=[None, 4], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None, 1], dtype=np.float32, name='y')
        W = ad.Variable(np.zeros((4, 1), np.float32), name='W')
        b = ad.Variable(np.zeros((1,), np.float32), name='b')
        pred = x @ W + b
        loss = ad.ops.reduce_mean(ad.ops.square(pred - y))
        train_op = ad.optimizers.SGD(0.1).minimize(loss)
        sess = autodist.create_distributed_session()
        for epoch in range(ns.epochs):
            lv, _ = sess.run([loss, train_op], {x: xs, y: ys})
        print('final training loss: %.5f' % float(lv))

        # export: the forward subgraph + weights become a bundle
        builder = SavedModelBuilder(ns.export_dir)
        builder.add_meta_graph_and_variables(
            sess, tags=['serve'],
            signature_def_map={'serving_default': (pred, [x])})
        builder.save()
    sess.close()

    # reload and serve — load_servable is a convenience; serving with
    # raw jax.export.deserialize works identically (see the docs)
    serve = load_servable(ns.export_dir)
    queries = np.random.randn(3, 4).astype(np.float32)
    out = np.asarray(serve(queries)[0])
    want = queries @ true_w
    print('served predictions vs ground truth:')
    for got, expect in zip(out[:, 0], want[:, 0]):
        print('  %8.4f  (true %8.4f)' % (got, expect))
    err = float(np.abs(out - want).max())
    assert err < 0.1, 'served model diverges from ground truth: %f' % err
    print('export dir: %s (servable with jax + numpy only)'
          % ns.export_dir)


if __name__ == '__main__':
    main()
