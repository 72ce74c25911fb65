"""Linear regression through the reference-shaped DSL API.

Port of /root/reference/examples/linear_regression.py: build the model
under ``autodist.scope()``, create a distributed session, feed numpy
batches. Runs on 1 chip or any local device mesh:

    python examples/linear_regression.py --strategy PS --epochs 10
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/linear_regression.py --strategy PartitionedPS
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)

import numpy as np

import autodist_tpu as ad
from autodist_tpu import strategy as strategies

STRATEGIES = {
    'PS': lambda: strategies.PS(),
    'PSLoadBalancing': lambda: strategies.PSLoadBalancing(),
    'PartitionedPS': lambda: strategies.PartitionedPS(),
    'UnevenPartitionedPS': lambda: strategies.UnevenPartitionedPS(),
    'AllReduce': lambda: strategies.AllReduce(chunk_size=128),
    'PartitionedAR': lambda: strategies.PartitionedAR(),
    'RandomAxisPartitionAR': lambda: strategies.RandomAxisPartitionAR(),
    'Parallax': lambda: strategies.Parallax(),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--strategy', default='AllReduce',
                   choices=sorted(STRATEGIES))
    p.add_argument('--resource-spec', default=None,
                   help='resource spec YAML (default: all local devices)')
    p.add_argument('--epochs', type=int, default=10)
    p.add_argument('--lr', type=float, default=0.01)
    args = p.parse_args()

    TRUE_W, TRUE_b, NUM_EXAMPLES = 3.0, 2.0, 1000
    np.random.seed(123)
    inputs = np.random.randn(NUM_EXAMPLES).astype(np.float32)
    noises = np.random.randn(NUM_EXAMPLES).astype(np.float32)
    outputs = inputs * TRUE_W + TRUE_b + noises

    autodist = ad.AutoDist(resource_spec_file=args.resource_spec,
                           strategy_builder=STRATEGIES[args.strategy]())

    with autodist.scope():
        x = ad.placeholder(shape=[None], dtype=np.float32, name='x')
        y = ad.placeholder(shape=[None], dtype=np.float32, name='y')
        W = ad.Variable(5.0, name='W')
        b = ad.Variable(0.0, name='b')
        loss = ad.ops.reduce_mean(ad.ops.square(W * x + b - y))
        train_op = ad.optimizers.SGD(args.lr).minimize(loss, [W, b])
        sess = autodist.create_distributed_session()
        for epoch in range(args.epochs):
            lv, _ = sess.run([loss, train_op], {x: inputs, y: outputs})
            print('epoch %d: loss=%.5f' % (epoch, float(lv)))
        W_val, b_val = sess.run([W, b])
        print('W=%.5f (true %.1f)  b=%.5f (true %.1f)' %
              (float(np.ravel(W_val)[0]), TRUE_W,
               float(np.ravel(b_val)[0]), TRUE_b))


if __name__ == '__main__':
    main()
