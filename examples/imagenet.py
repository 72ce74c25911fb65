"""ImageNet CNN training benchmark (reference examples/benchmark/imagenet.py
role): ResNet-50/101/152, VGG16, DenseNet121, InceptionV3 through the
functional Trainer, with an optional reference-style strategy builder
steering the state shardings (strategy -> pytree adapter).

Data: synthetic by default (benchmark semantics, like the reference's
synthetic mode); point ``SYS_DATA_PATH`` or ``--data`` at a directory of
``.npy`` shards {images, labels} for real data.

    python examples/imagenet.py --model resnet101 --batch 64 --steps 20
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/imagenet.py --model resnet50 --tiny --steps 3
"""
import argparse
import _common  # noqa: F401  (path + compile-cache bootstrap)
import os

import numpy as np


def build_model(name, tiny, dtype):
    from autodist_tpu.models import vision
    if tiny:   # CPU-smoke configs: small stacks, 32x32, 10 classes
        builders = {
            'resnet50': lambda: vision.ResNet((1, 1), num_classes=10,
                                              dtype=dtype),
            'resnet101': lambda: vision.ResNet((1, 2), num_classes=10,
                                               dtype=dtype),
            'resnet152': lambda: vision.ResNet((2, 2), num_classes=10,
                                               dtype=dtype),
            'vgg16': lambda: vision.VGG(
                (16, 'M', 32, 'M'), num_classes=10, dtype=dtype,
                fc_spatial=8),
            'densenet121': lambda: vision.DenseNet(
                (2, 2), num_classes=10, dtype=dtype),
            'inception': lambda: vision.InceptionV3(num_classes=10,
                                                    dtype=dtype),
        }
        # inception's grid reductions need >= 75px even in tiny mode
        return builders[name](), (80 if name == 'inception' else 32)
    builders = {
        'resnet50': vision.ResNet.resnet50,
        'resnet101': vision.ResNet.resnet101,
        'resnet152': vision.ResNet.resnet152,
        'vgg16': vision.VGG.vgg16,
        'densenet121': vision.DenseNet.densenet121,
        'inception': vision.InceptionV3,
    }
    hw = 299 if name == 'inception' else 224
    return builders[name](dtype=dtype), hw


def record_stream(args, hw):
    """Native-DataLoader streaming when --data holds ADTR1 record files
    (images.records + labels.records, written with
    autodist_tpu.data.loader.write_records). The C++ reader thread
    prefetches so host IO overlaps device steps; shuffle stays off to
    keep the two files aligned record-for-record."""
    data_dir = args.data or os.environ.get('SYS_DATA_PATH') or ''
    img = os.path.join(data_dir, 'images.records') if data_dir else ''
    lab = os.path.join(data_dir, 'labels.records') if data_dir else ''
    if not (img and os.path.exists(img) and os.path.exists(lab)):
        return None
    from autodist_tpu.data.loader import DataLoader
    images = DataLoader([img], args.batch, (hw, hw, 3), 'float32',
                        shuffle=False)
    labels = DataLoader([lab], args.batch, (), 'int32', shuffle=False)

    def gen():
        while True:
            yield {'images': images.next_batch(),
                   'labels': labels.next_batch()}
    return gen()


def load_batch(args, hw, num_classes):
    data_dir = args.data or os.environ.get('SYS_DATA_PATH') or ''
    if data_dir and os.path.isdir(data_dir) and \
            os.path.exists(os.path.join(data_dir, 'images.npy')):
        images = np.load(os.path.join(data_dir, 'images.npy'))
        labels = np.load(os.path.join(data_dir, 'labels.npy'))
        images = images[:args.batch].astype('f4')
        labels = labels[:args.batch].astype(np.int32)
        return {'images': images, 'labels': labels}
    rng = np.random.RandomState(0)
    return {'images': rng.rand(args.batch, hw, hw, 3).astype('f4'),
            'labels': rng.randint(0, num_classes, (args.batch,),
                                  dtype=np.int32)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--model', default='resnet101',
                   choices=['resnet50', 'resnet101', 'resnet152', 'vgg16',
                            'densenet121', 'inception'])
    p.add_argument('--batch', type=int, default=64,
                   help='per-chip batch. Measured v5e optima: 256 for '
                        'resnet101/densenet121/vgg16/inception; the '
                        'landscape is NON-monotonic (BASELINE.md '
                        'round-5) — sweep down as well as up')
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--lr', type=float, default=0.1)
    p.add_argument('--tiny', action='store_true',
                   help='small config for CPU smoke runs')
    p.add_argument('--fp32', action='store_true')
    p.add_argument('--strategy', default=None,
                   help='optional reference strategy builder '
                        '(PS, PSLoadBalancing, PartitionedPS, AllReduce, '
                        'Parallax, ...) steering state shardings')
    p.add_argument('--data', default=None)
    p.add_argument('--eval', action='store_true',
                   help='after training, evaluate loss/accuracy in eval '
                        'mode (BatchNorm running statistics)')
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.api import Trainer
    from autodist_tpu.parallel.axes import ParallelSpec

    dtype = jnp.float32 if (args.fp32 or args.tiny) else jnp.bfloat16
    model, hw = build_model(args.model, args.tiny, dtype)
    num_classes = 10 if args.tiny else 1000
    opt = optax.sgd(args.lr, momentum=0.9)

    if args.strategy:
        from autodist_tpu import strategy as strategies
        from autodist_tpu.strategy.adapter import trainer_from_strategy
        builder = getattr(strategies, args.strategy)()
        trainer = trainer_from_strategy(model, opt, builder)
    else:
        trainer = Trainer(model, opt, spec=ParallelSpec())

    state = trainer.init(jax.random.PRNGKey(0))
    stream = record_stream(args, hw)
    if stream is not None:   # real data: stream fresh batches per step
        import time
        state, m = trainer.step(state, next(stream))   # compile+warmup
        float(m['loss'])
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, m = trainer.step(state, next(stream))
        loss = float(m['loss'])
        dt = time.perf_counter() - t0
    else:
        batch = load_batch(args, hw, num_classes)
        state, loss, dt = _common.timed_steps(trainer, state, batch,
                                              args.steps)
    n = len(jax.devices())
    print('%s: %.1f img/s (%.1f img/s/chip), loss=%.4f' %
          (args.model, args.steps * args.batch / dt,
           args.steps * args.batch / dt / n, loss))
    if args.eval:
        # eval mode: BatchNorm normalizes with the running statistics
        # accumulated during the steps above (tf.layers moving averages)
        def accuracy(params, b):
            logits = model.apply(params, b['images'])
            return {'acc': (logits.argmax(-1) == b['labels']).mean()}
        eval_batch = batch if stream is None else next(stream)
        metrics = trainer.evaluate(state, [eval_batch],
                                   metrics_fn=accuracy)
        print('eval (running stats): loss=%.4f acc=%.3f'
              % (metrics['loss'], metrics['acc']))


if __name__ == '__main__':
    main()
