"""Mamba-2's mixer: a selective state-space layer on the training path.

For ``u [batch, seq, dim]`` (``heads`` heads of ``head_dim`` lanes,
``inner = heads x head_dim``; ``groups`` groups of ``state`` lanes share
their B and C among ``heads / groups`` heads; no projection has a
bias)::

    [z | xBC | dt] = u W_in           widths inner | inner + 2 groups state | heads
    xBC <- silu(conv(xBC) + b_conv)   depthwise, causal, `conv` taps: xBC_t from
                                      t - conv + 1 .. t, zeros before the sequence
    x, B, C = split(xBC)              inner | groups state | groups state
    dt_t,h = softplus(dt_t,h + dt_bias_h)      A_h = -exp(A_log_h)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T    y_t = H_t C_t + D_h x_t
    y <- GroupRMSNorm(y * silu(z))    the gate INSIDE the norm, the norm over each
                                      of `groups` groups of inner / groups lanes
    out = y W_out

The recurrence is ``kernels/ssd_scan.py``'s (the chunked form, Pallas
kernels forward and backward where the shape allows, ``jax.numpy``
otherwise); everything else here is XLA's, under the scope
``ssm_mixer``, so that a trace tells the projections, the conv and the
gate norm from the kernels (``docs/design/observability.md``).

The draw: ``dt_bias`` is the inverse softplus of a step size drawn
log-uniform in ``[dt_min, dt_max]`` and floored at ``dt_floor``;
``A_log = log U(a_range)``; ``D = 1``; the conv's taps and bias N(0, 1 /
12), the variance of PyTorch's U(-1/2, 1/2) for a depthwise conv of four
taps.

The heads carry the logical axis ``heads``. Under a mesh that shards the
batch the kernels run on each device's batch in a manual region (as
attention's do); under one that shards the heads, or the sequence, the
layer raises: a head's state runs the whole sequence, the groups' B and
C would have to follow their heads' shard, and neither path exists.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from autodist_tpu.const import AXIS_DATA, AXIS_SEQUENCE
from autodist_tpu.kernels import ssd_scan
from autodist_tpu.models.core import (Dense, GatedGroupRMSNorm, Module,
                                      ParamDef)
from autodist_tpu.parallel.axes import (active_manual_axes, current_mesh,
                                        manual_axis, shard_map,
                                        unsharded_execution)


@jax.custom_vjp
def causal_conv(x, taps, bias):
    """The depthwise causal conv of ``x [b, s, c]`` over ``taps [k, c]``
    as ``k`` shifted products, plus ``bias [c]``, in f32: ``out_t =
    sum_i taps_i x_{t - (k - 1) + i}``, zeros before the sequence.

    The backward pass is written out: ``dx_t = sum_i taps_i g_{t + (k -
    1) - i}`` is the same sum of shifted products over the cotangent
    (zeros after the sequence). Left to autodiff it is ``k`` padded f32
    copies of the cotangent written and read again, 2.4 GB a layer at
    16,384 tokens x 6144 channels, in a fusion that carries no scope's
    name."""
    return _conv_forward(x, taps, bias)


def _shifted_sum(x, taps, offsets, pad):
    s = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), pad, (0, 0)))
    return sum(padded[:, o:o + s] * taps[i] for i, o in enumerate(offsets))


def _conv_forward(x, taps, bias):
    k = taps.shape[0]
    return _shifted_sum(x, taps, range(k), (k - 1, 0)) + bias


def _conv_fwd(x, taps, bias):
    return _conv_forward(x, taps, bias), (x, taps)


def _conv_bwd(res, g):
    x, taps = res
    k, s = taps.shape[0], x.shape[1]
    dx = _shifted_sum(g, taps, range(k - 1, -1, -1), (0, k - 1))
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    d_taps = jnp.stack([jnp.sum(g * padded[:, i:i + s], axis=(0, 1))
                        for i in range(k)])
    return dx.astype(x.dtype), d_taps, jnp.sum(g, axis=(0, 1))


causal_conv.defvjp(_conv_fwd, _conv_bwd)


class Mamba2Mixer(Module):
    """[batch, seq, dim] in and out (module docstring)."""

    def __init__(self, dim, heads, head_dim, groups, state, conv=4,
                 dtype=jnp.float32, norm_eps=1e-5, dt_min=0.001, dt_max=0.1,
                 dt_floor=1e-4, a_range=(1.0, 16.0)):
        if heads % groups:
            raise ValueError('%d heads do not divide over %d groups'
                             % (heads, groups))
        self.dim, self.heads, self.head_dim = dim, heads, head_dim
        self.groups, self.state, self.conv = groups, state, conv
        self.inner = heads * head_dim
        self.conv_dim = self.inner + 2 * groups * state
        self.dtype = dtype
        self.dt_range = (dt_min, dt_max, dt_floor)
        self.a_range = tuple(a_range)
        self.w_in = Dense(dim, self.inner + self.conv_dim + heads, 'embed',
                          'heads', use_bias=False, dtype=dtype)
        self.norm = GatedGroupRMSNorm(self.inner, groups, eps=norm_eps,
                                      dtype=dtype)
        self.w_out = Dense(self.inner, dim, 'heads', 'embed', use_bias=False,
                           dtype=dtype)

    def param_defs(self):
        return {
            'in': self.w_in,
            'conv': ParamDef((self.conv, self.conv_dim), (None, 'heads'),
                             'normal', 12 ** -0.5),
            'conv_bias': ParamDef((self.conv_dim,), ('heads',), 'normal',
                                  12 ** -0.5),
            # (drawn in `init`)
            'dt_bias': ParamDef((self.heads,), ('heads',), 'zeros'),
            'a_log': ParamDef((self.heads,), ('heads',), 'zeros'),
            'd': ParamDef((self.heads,), ('heads',), 'ones'),
            'norm': self.norm,
            'out': self.w_out,
        }

    def init(self, rng):
        params = super().init(rng)
        k_dt, k_a = jax.random.split(jax.random.fold_in(rng, 0x55d))
        lo, hi, floor = self.dt_range
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            k_dt, (self.heads,), jnp.float32, jnp.log(lo), jnp.log(hi))),
            floor)
        # softplus(dt_bias) = step
        params['dt_bias'] = step + jnp.log(-jnp.expm1(-step))
        params['a_log'] = jnp.log(jax.random.uniform(
            k_a, (self.heads,), jnp.float32, *self.a_range))
        return params

    def apply(self, params, u):
        self._check_layout()
        inner, heads = self.inner, self.heads
        with jax.named_scope('ssm_mixer'):
            zxbcdt = self.w_in.apply(params['in'], u)
            z = zxbcdt[..., :inner]
            xbc = self._conv(params, zxbcdt[..., inner:inner + self.conv_dim])
            x = xbc[..., :inner]
            b, c = jnp.split(xbc[..., inner:], 2, axis=-1)
            dt = jax.nn.softplus(
                zxbcdt[..., inner + self.conv_dim:].astype(jnp.float32)
                + params['dt_bias'])
            a = -jnp.exp(params['a_log'])
        y = self._scan(x, dt, a, b, c)
        with jax.named_scope('ssm_mixer'):
            skip = jnp.repeat(params['d'], self.head_dim)
            y = y.astype(jnp.float32) + skip * x.astype(jnp.float32)
            y = self.norm.apply(params['norm'], y, z)
            return self.w_out.apply(params['out'], y)

    def _conv(self, params, xbc):
        """``silu(conv(xBC) + b_conv)``, in f32."""
        out = causal_conv(xbc, params['conv'], params['conv_bias'])
        return jax.nn.silu(out).astype(self.dtype)

    def _scan(self, x, dt, a, b, c):
        """The scan on device-local data; under a mesh that shards the
        batch, on each device's batch in a manual region (GSPMD cannot
        partition the kernels' opaque calls)."""
        def scan(x, dt, a, b, c):
            return ssd_scan.ssd_scan(x, dt, a, b, c, self.heads, self.groups)
        if unsharded_execution():
            return scan(x, dt, a, b, c)
        rows = P(AXIS_DATA, None, None)
        return shard_map(scan, current_mesh(), (rows, rows, P(), rows, rows),
                         rows)(x, dt, a, b, c)

    def _check_layout(self):
        if manual_axis(AXIS_SEQUENCE) is not None:
            raise ValueError(
                'Mamba2Mixer under sequence parallelism: a head\'s state '
                'runs the whole sequence and no scan across a split '
                'sequence exists; use sp=1')
        if unsharded_execution():
            return
        mesh = current_mesh()
        others = [axis for axis, n in mesh.shape.items()
                  if n > 1 and axis != AXIS_DATA]
        if others or active_manual_axes():
            raise ValueError(
                'Mamba2Mixer under a mesh %s that shards more than the '
                'batch: the scan kernels take all of a group\'s heads with '
                'their B and C, and no path runs them on a shard of the '
                'heads; use tp=1, ep=1, pp=1' % (dict(mesh.shape),))
