"""CNN-family per-chip batch sweep (round 5: the batch landscape is
non-monotonic — sweep DOWN as well as up)."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bench as B


def main():
    from autodist_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()

    import jax.numpy as jnp
    import optax

    from autodist_tpu.models import vision

    name = sys.argv[1]
    batches = [int(b) for b in sys.argv[2:]]
    builders = {
        'resnet101': (lambda: vision.ResNet.resnet101(dtype=jnp.bfloat16),
                      224),
        'densenet121': (lambda: vision.DenseNet.densenet121(
            dtype=jnp.bfloat16), 224),
        'inceptionv3': (lambda: vision.InceptionV3(dtype=jnp.bfloat16),
                        299),
        'vgg16': (lambda: vision.VGG.vgg16(dtype=jnp.bfloat16), 224),
    }
    fn, hw = builders[name]
    lr = 0.001 if name == 'vgg16' else 0.1   # no-BN net: keep SGD cool
    rng = np.random.RandomState(0)
    steps = 10
    for bs in batches:
        batch = {'images': rng.rand(bs, hw, hw, 3).astype('f4'),
                 'labels': rng.randint(0, 10, (bs,), dtype=np.int32)}
        try:
            stats = {}
            dt, _ = B.run_workload(fn(), batch, steps,
                                   optimizer=optax.sgd(lr, momentum=0.9),
                                   stats_out=stats)
            print('%s_B%d' % (name, bs), json.dumps(
                {'img_per_s': round(bs * steps / dt, 1),
                 'step_ms': round(1000 * dt / steps, 2),
                 'dispersion_pct': stats['dispersion_pct']}), flush=True)
        except Exception as e:   # noqa: BLE001 - OOM rows recorded
            print('%s_B%d' % (name, bs),
                  json.dumps({'error': str(e)[:120]}), flush=True)


if __name__ == '__main__':
    main()
