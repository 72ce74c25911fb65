"""A/B the space-to-depth stem transform on the CNN family (TPU).

Measure AUTODIST_S2D_STEM=0 vs 1 train steps for
ResNet-101 / DenseNet-121 / InceptionV3 at their bench batch sizes.
Uses bench.run_workload (median of 3 fenced blocks).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bench as B


def run(name, steps=10):
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models import vision

    builders = {
        'resnet101': (lambda: vision.ResNet.resnet101(dtype=jnp.bfloat16),
                      256, 224),
        'densenet121': (lambda: vision.DenseNet.densenet121(
            dtype=jnp.bfloat16), 128, 224),
        'inceptionv3': (lambda: vision.InceptionV3(dtype=jnp.bfloat16),
                        128, 299),
    }
    fn, batch_size, hw = builders[name]
    rng = np.random.RandomState(0)
    batch = {'images': rng.rand(batch_size, hw, hw, 3).astype('f4'),
             'labels': rng.randint(0, 10, (batch_size,), dtype=np.int32)}
    out = {}
    for flag in ('0', '1'):
        os.environ['AUTODIST_S2D_STEM'] = flag
        stats = {}
        dt, _ = B.run_workload(fn(), batch, steps,
                               optimizer=optax.sgd(0.1, momentum=0.9),
                               stats_out=stats)
        out['s2d_%s' % flag] = {
            'step_ms': round(1000 * dt / steps, 2),
            'img_per_s': round(batch_size * steps / dt, 1),
            'dispersion_pct': stats['dispersion_pct']}
    return out


def main():
    from autodist_tpu.utils.jax_env import setup_compile_cache
    setup_compile_cache()
    names = sys.argv[1:] or ['resnet101', 'densenet121', 'inceptionv3']
    for name in names:
        print(name, json.dumps(run(name)), flush=True)


if __name__ == '__main__':
    main()
