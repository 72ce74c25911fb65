"""ImageNet CNN family: ResNet, VGG, DenseNet, Inception.

The reference's benchmark suite (examples/benchmark/imagenet.py;
BASELINE.md rows ResNet101/DenseNet121/InceptionV3/VGG16) rebuilt on the
functional module system. TPU-first choices: NHWC layout (native for TPU
convolutions), bfloat16 compute with float32 master weights and float32
batch-norm statistics, channels padded by construction to MXU-friendly
multiples in the standard configs.

BatchNorm note: training mode normalizes with batch statistics; running
mean/variance EMAs are carried as non-trainable state leaves in the
params tree and advance through the Trainer's state-update channel
(tf.layers ``moving_mean``/``moving_variance`` parity). Eval mode
(``Trainer.evaluate`` / ``model_mode(training=False)``) normalizes with
the running statistics. Plain forwards outside any mode context keep
batch-stat semantics (what the throughput benchmarks exercise).
"""
import jax
import jax.numpy as jnp

from autodist_tpu.models.core import Dense, Module, ParamDef


class Conv(Module):
    """NHWC conv, HWIO kernel."""

    def __init__(self, in_ch, out_ch, kernel=3, stride=1, padding='SAME',
                 use_bias=False, dtype=jnp.float32):
        self.in_ch, self.out_ch = in_ch, out_ch
        self.kernel = (kernel, kernel) if isinstance(kernel, int) \
            else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) \
            else tuple(stride)
        self.padding = padding
        self.use_bias = use_bias
        self.dtype = dtype

    def param_defs(self):
        d = {'kernel': ParamDef(self.kernel + (self.in_ch, self.out_ch),
                                (None, None, None, None), 'fan_in')}
        if self.use_bias:
            d['bias'] = ParamDef((self.out_ch,), (None,), 'zeros')
        return d

    def apply(self, params, x):
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype),
            params['kernel'].astype(self.dtype),
            window_strides=self.stride, padding=self.padding,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        if self.use_bias:
            y = y + params['bias'].astype(self.dtype)
        return y


class BatchNorm(Module):
    """Batch normalization with running statistics.

    Training mode (the default outside any ``model_mode`` context —
    benchmark semantics) normalizes with batch statistics and, when a
    state collector is active, records EMA updates of mean/var into the
    non-trainable ``ema_mean``/``ema_var`` leaves (tf.layers
    ``moving_mean``/``moving_variance`` parity). Eval mode
    (``model_mode(training=False)``, used by ``Trainer.evaluate``)
    normalizes with the running statistics."""

    def __init__(self, ch, eps=1e-5, momentum=0.9, dtype=jnp.float32):
        self.ch, self.eps, self.dtype = ch, eps, dtype
        self.momentum = momentum

    def param_defs(self):
        return {'scale': ParamDef((self.ch,), (None,), 'ones'),
                'bias': ParamDef((self.ch,), (None,), 'zeros'),
                'ema_mean': ParamDef((self.ch,), (None,), 'zeros',
                                     trainable=False),
                'ema_var': ParamDef((self.ch,), (None,), 'ones',
                                    trainable=False)}

    def coeffs(self, params, x):
        """(a, b) such that the normalized output is ``x*a + b``; in
        training mode also records the EMA state updates."""
        from autodist_tpu.models.core import (is_training,
                                              record_state_update)
        if is_training():
            # one pass of f32-ACCUMULATED moments (E[x], E[x^2]): the f32
            # convert fuses into the reduces, and XLA already emits them
            # as one multi-output reduce fusion, so no kernel is here.
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 1, 2))
            m2 = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
            var = jnp.maximum(m2 - jnp.square(mean), 0.0)
            m = self.momentum
            record_state_update(
                self, 'ema_mean', m * params['ema_mean'] + (1 - m) * mean)
            record_state_update(
                self, 'ema_var', m * params['ema_var'] + (1 - m) * var)
        else:
            mean = params['ema_mean']
            var = params['ema_var']
        a = params['scale'] * jax.lax.rsqrt(var + self.eps)
        b = params['bias'] - mean * a
        return a, b

    def apply(self, params, x):
        # normalize+affine folded to one per-channel multiply-add: the
        # [C]-vector coefficients are computed in f32, the elementwise
        # pass over the activations reads and writes the model dtype
        # (bf16 on TPU). XLA fuses this form's backward into one pass
        # (dbeta, dgamma and dx) and a custom_vjp boundary blocks that
        # fusion, so no hand-scheduled BN is here.
        a, b = self.coeffs(params, x)
        y = x.astype(self.dtype) * a.astype(self.dtype) + \
            b.astype(self.dtype)
        return y


def max_pool(x, window=3, stride=2, padding='SAME'):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), padding)


def avg_pool(x, window, stride=1, padding='VALID'):
    s = jax.lax.reduce_window(
        x, 0., jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), padding)
    return s / (window * window)


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


class ConvBn(Module):
    """conv + BN + optional relu — the CNN workhorse."""

    def __init__(self, in_ch, out_ch, kernel=3, stride=1, relu=True,
                 padding='SAME', dtype=jnp.float32):
        self.conv = Conv(in_ch, out_ch, kernel, stride, padding,
                         dtype=dtype)
        self.bn = BatchNorm(out_ch, dtype=dtype)
        self.relu = relu

    def param_defs(self):
        return {'conv': self.conv, 'bn': self.bn}

    def apply(self, params, x):
        y = self.bn.apply(params['bn'],
                          self.conv.apply(params['conv'], x))
        return jax.nn.relu(y) if self.relu else y


# ---------------------------------------------------------------------------
# ResNet (v1.5 bottleneck; resnet50/101/152)
# ---------------------------------------------------------------------------

class Bottleneck(Module):
    expansion = 4

    def __init__(self, in_ch, width, stride=1, dtype=jnp.float32):
        out_ch = width * self.expansion
        self.a = ConvBn(in_ch, width, 1, 1, dtype=dtype)
        self.b = ConvBn(width, width, 3, stride, dtype=dtype)
        self.c = ConvBn(width, out_ch, 1, 1, relu=False, dtype=dtype)
        self.proj = None
        if stride != 1 or in_ch != out_ch:
            self.proj = ConvBn(in_ch, out_ch, 1, stride, relu=False,
                               dtype=dtype)
        self.out_ch = out_ch

    def param_defs(self):
        d = {'a': self.a, 'b': self.b, 'c': self.c}
        if self.proj is not None:
            d['proj'] = self.proj
        return d

    def apply(self, params, x):
        sc = x if self.proj is None else self.proj.apply(params['proj'], x)
        y = self.a.apply(params['a'], x)
        y = self.b.apply(params['b'], y)
        y = self.c.apply(params['c'], y)
        return jax.nn.relu(y + sc)


class ResNet(Module):
    """ResNet-v1.5; stage_sizes (3,4,23,3) = ResNet-101."""

    def __init__(self, stage_sizes, num_classes=1000, dtype=jnp.float32):
        self.stem = ConvBn(3, 64, 7, 2, dtype=dtype)
        self.blocks = []
        in_ch = 64
        for stage, n in enumerate(stage_sizes):
            width = 64 * (2 ** stage)
            for i in range(n):
                stride = 2 if (i == 0 and stage > 0) else 1
                blk = Bottleneck(in_ch, width, stride, dtype=dtype)
                self.blocks.append(blk)
                in_ch = blk.out_ch
        self.head = Dense(in_ch, num_classes, 'embed', 'classes',
                          dtype=dtype)

    @classmethod
    def resnet50(cls, **kw):
        return cls((3, 4, 6, 3), **kw)

    @classmethod
    def resnet101(cls, **kw):
        return cls((3, 4, 23, 3), **kw)

    @classmethod
    def resnet152(cls, **kw):
        return cls((3, 8, 36, 3), **kw)

    def param_defs(self):
        d = {'stem': self.stem, 'head': self.head}
        for i, b in enumerate(self.blocks):
            d['block_%03d' % i] = b
        return d

    def apply(self, params, x):
        y = self.stem.apply(params['stem'], x)
        y = max_pool(y, 3, 2)
        for i, b in enumerate(self.blocks):
            y = b.apply(params['block_%03d' % i], y)
        y = global_avg_pool(y)
        return self.head.apply(params['head'], y).astype(jnp.float32)

    def loss(self, params, batch):
        logits = self.apply(params, batch['images'])
        return _softmax_xent(logits, batch['labels'])


# ---------------------------------------------------------------------------
# VGG16
# ---------------------------------------------------------------------------

class VGG(Module):
    CFG16 = (64, 64, 'M', 128, 128, 'M', 256, 256, 256, 'M',
             512, 512, 512, 'M', 512, 512, 512, 'M')

    def __init__(self, cfg=CFG16, num_classes=1000, dtype=jnp.float32,
                 fc_spatial=7):
        """``fc_spatial`` is the spatial size after the conv stack
        (7 for CFG16 at 224px); the classic fixed-size fc head is sized
        from it, so custom cfgs/resolutions must pass theirs."""
        self.cfg = cfg
        self.fc_spatial = fc_spatial
        self.convs = []
        in_ch = 3
        for v in cfg:
            if v == 'M':
                continue
            self.convs.append(Conv(in_ch, v, 3, 1, use_bias=True,
                                   dtype=dtype))
            in_ch = v
        self.fc1 = Dense(in_ch * fc_spatial * fc_spatial, 4096,
                         'embed', 'mlp', dtype=dtype)
        self.fc2 = Dense(4096, 4096, 'mlp', 'mlp', dtype=dtype)
        self.head = Dense(4096, num_classes, 'mlp', 'classes',
                          dtype=dtype)

    @classmethod
    def vgg16(cls, **kw):
        return cls(cls.CFG16, **kw)

    def param_defs(self):
        d = {'fc1': self.fc1, 'fc2': self.fc2, 'head': self.head}
        for i, c in enumerate(self.convs):
            d['conv_%02d' % i] = c
        return d

    def apply(self, params, x):
        ci = 0
        y = x
        for v in self.cfg:
            if v == 'M':
                y = max_pool(y, 2, 2)
            else:
                y = jax.nn.relu(
                    self.convs[ci].apply(params['conv_%02d' % ci], y))
                ci += 1
        if y.shape[1] != self.fc_spatial:
            raise ValueError(
                'VGG conv stack produced %dx%d spatial but the fc head '
                'was sized for %dx%d; pass fc_spatial=%d for this '
                'cfg/resolution' % (y.shape[1], y.shape[2],
                                    self.fc_spatial, self.fc_spatial,
                                    y.shape[1]))
        y = y.reshape(y.shape[0], -1)
        y = jax.nn.relu(self.fc1.apply(params['fc1'], y))
        y = jax.nn.relu(self.fc2.apply(params['fc2'], y))
        return self.head.apply(params['head'], y).astype(jnp.float32)

    def loss(self, params, batch):
        logits = self.apply(params, batch['images'])
        return _softmax_xent(logits, batch['labels'])


# ---------------------------------------------------------------------------
# DenseNet121
# ---------------------------------------------------------------------------

class DenseLayer(Module):
    def __init__(self, in_ch, growth, dtype=jnp.float32):
        self.bn1 = BatchNorm(in_ch, dtype=dtype)
        self.conv1 = Conv(in_ch, 4 * growth, 1, dtype=dtype)
        self.bn2 = BatchNorm(4 * growth, dtype=dtype)
        self.conv2 = Conv(4 * growth, growth, 3, dtype=dtype)

    def param_defs(self):
        return {'bn1': self.bn1, 'conv1': self.conv1,
                'bn2': self.bn2, 'conv2': self.conv2}

    def apply(self, params, x):
        y = self.conv1.apply(params['conv1'], jax.nn.relu(
            self.bn1.apply(params['bn1'], x)))
        y = self.conv2.apply(params['conv2'], jax.nn.relu(
            self.bn2.apply(params['bn2'], y)))
        return jnp.concatenate([x, y], axis=-1)


class DenseNet(Module):
    """DenseNet-BC; block config (6,12,24,16) = DenseNet-121."""

    def __init__(self, block_cfg=(6, 12, 24, 16), growth=32,
                 num_classes=1000, dtype=jnp.float32):
        self.stem = ConvBn(3, 2 * growth, 7, 2, dtype=dtype)
        ch = 2 * growth
        self.layers = []   # list of ('dense', layer) / ('trans', conv)
        for bi, n in enumerate(block_cfg):
            for _ in range(n):
                self.layers.append(('dense', DenseLayer(ch, growth,
                                                        dtype=dtype)))
                ch += growth
            if bi != len(block_cfg) - 1:
                self.layers.append(
                    ('trans', ConvBn(ch, ch // 2, 1, dtype=dtype)))
                ch //= 2
        self.bn_f = BatchNorm(ch, dtype=dtype)
        self.head = Dense(ch, num_classes, 'embed', 'classes',
                          dtype=dtype)

    @classmethod
    def densenet121(cls, **kw):
        return cls((6, 12, 24, 16), **kw)

    def param_defs(self):
        d = {'stem': self.stem, 'bn_f': self.bn_f, 'head': self.head}
        for i, (_, m) in enumerate(self.layers):
            d['layer_%03d' % i] = m
        return d

    def apply(self, params, x):
        y = self.stem.apply(params['stem'], x)
        y = max_pool(y, 3, 2)
        for i, (kind, m) in enumerate(self.layers):
            y = m.apply(params['layer_%03d' % i], y)
            if kind == 'trans':
                y = avg_pool(y, 2, 2, 'VALID')
        y = jax.nn.relu(self.bn_f.apply(params['bn_f'], y))
        y = global_avg_pool(y)
        return self.head.apply(params['head'], y).astype(jnp.float32)

    def loss(self, params, batch):
        logits = self.apply(params, batch['images'])
        return _softmax_xent(logits, batch['labels'])


# ---------------------------------------------------------------------------
# InceptionV3 (faithful block structure, standard 299x299 stem)
# ---------------------------------------------------------------------------

class InceptionBlock(Module):
    """Generic inception block: parallel towers concatenated on channels.

    Each tower is a list of ConvBn specs (out_ch, kernel, stride,
    padding); ``pool`` adds an avg-pool+1x1 tower.
    """

    def __init__(self, in_ch, towers, pool_ch=0, dtype=jnp.float32):
        self.towers = []
        for tower in towers:
            mods, ch = [], in_ch
            for (out_ch, kernel, stride, padding) in tower:
                mods.append(ConvBn(ch, out_ch, kernel, stride,
                                   padding=padding, dtype=dtype))
                ch = out_ch
            self.towers.append(mods)
        self.pool_proj = ConvBn(in_ch, pool_ch, 1, dtype=dtype) \
            if pool_ch else None
        self.out_ch = sum(t[-1][0] for t in towers) + pool_ch

    def param_defs(self):
        d = {}
        for ti, mods in enumerate(self.towers):
            for mi, m in enumerate(mods):
                d['t%d_%d' % (ti, mi)] = m
        if self.pool_proj is not None:
            d['pool'] = self.pool_proj
        return d

    def apply(self, params, x):
        outs = []
        for ti, mods in enumerate(self.towers):
            y = x
            for mi, m in enumerate(mods):
                y = m.apply(params['t%d_%d' % (ti, mi)], y)
            outs.append(y)
        if self.pool_proj is not None:
            p = avg_pool(x, 3, 1, 'SAME')
            outs.append(self.pool_proj.apply(params['pool'], p))
        return jnp.concatenate(outs, axis=-1)


def _c(out, k=1, s=1, p='SAME'):
    return (out, k, s, p)


class InceptionV3(Module):
    def __init__(self, num_classes=1000, dtype=jnp.float32):
        d = dtype
        self.stem = [ConvBn(3, 32, 3, 2, padding='VALID', dtype=d),
                     ConvBn(32, 32, 3, 1, padding='VALID', dtype=d),
                     ConvBn(32, 64, 3, 1, dtype=d),
                     ConvBn(64, 80, 1, 1, padding='VALID', dtype=d),
                     ConvBn(80, 192, 3, 1, padding='VALID', dtype=d)]
        blocks = []
        ch = 192
        for pool_ch in (32, 64, 64):  # 3x inception-A
            b = InceptionBlock(ch, [[_c(64)],
                                    [_c(48), _c(64, 5)],
                                    [_c(64), _c(96, 3), _c(96, 3)]],
                               pool_ch, dtype=d)
            blocks.append(('b', b))
            ch = b.out_ch
        grid = InceptionBlock(ch, [[_c(384, 3, 2, 'VALID')],
                                   [_c(64), _c(96, 3),
                                    _c(96, 3, 2, 'VALID')]], 0, dtype=d)
        blocks.append(('g', grid))
        ch = grid.out_ch + ch  # pool branch concat keeps input channels
        for mid in (128, 160, 160, 192):  # 4x inception-B (7x1/1x7)
            b = InceptionBlock(
                ch, [[_c(192)],
                     [_c(mid), _c(mid, (1, 7)), _c(192, (7, 1))],
                     [_c(mid), _c(mid, (7, 1)), _c(mid, (1, 7)),
                      _c(mid, (7, 1)), _c(192, (1, 7))]],
                192, dtype=d)
            blocks.append(('b', b))
            ch = b.out_ch
        grid2 = InceptionBlock(ch, [[_c(192), _c(320, 3, 2, 'VALID')],
                                    [_c(192), _c(192, (1, 7)),
                                     _c(192, (7, 1)),
                                     _c(192, 3, 2, 'VALID')]], 0, dtype=d)
        blocks.append(('g', grid2))
        ch = grid2.out_ch + ch
        for _ in range(2):  # 2x inception-C
            b = InceptionBlock(ch, [[_c(320)],
                                    [_c(384), _c(384, (1, 3))],
                                    [_c(448), _c(384, 3), _c(384, (3, 1))]],
                               192, dtype=d)
            blocks.append(('b', b))
            ch = b.out_ch
        self.blocks = blocks
        self.head = Dense(ch, num_classes, 'embed', 'classes', dtype=d)

    def param_defs(self):
        d = {'head': self.head}
        for i, m in enumerate(self.stem):
            d['stem_%d' % i] = m
        for i, (_, m) in enumerate(self.blocks):
            d['inc_%02d' % i] = m
        return d

    def apply(self, params, x):
        if x.shape[1] < 75 or x.shape[2] < 75:
            # below this the grid reductions hit zero spatial size and
            # reductions over empty windows would silently produce NaN
            raise ValueError('InceptionV3 needs inputs >= 75x75, got '
                             '%dx%d' % (x.shape[1], x.shape[2]))
        y = x
        for i, m in enumerate(self.stem):
            y = m.apply(params['stem_%d' % i], y)
            if i == 2:
                y = max_pool(y, 3, 2, 'VALID')
        y = max_pool(y, 3, 2, 'VALID')
        for i, (kind, m) in enumerate(self.blocks):
            if kind == 'g':
                pooled = max_pool(y, 3, 2, 'VALID')
                y = jnp.concatenate([m.apply(params['inc_%02d' % i], y),
                                     pooled], axis=-1)
            else:
                y = m.apply(params['inc_%02d' % i], y)
        y = global_avg_pool(y)
        return self.head.apply(params['head'], y).astype(jnp.float32)

    def loss(self, params, batch):
        logits = self.apply(params, batch['images'])
        return _softmax_xent(logits, batch['labels'])


def _softmax_xent(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.sum(logits * jax.nn.one_hot(labels, logits.shape[-1],
                                           dtype=logits.dtype), axis=-1)
    return jnp.mean(logz - gold)
