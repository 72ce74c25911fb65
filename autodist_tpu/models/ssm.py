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
otherwise). The conv, its SiLU and the split are
``kernels/ssm_conv.py``'s: a kernel pair that reads xBC where the
projection left it (by a column offset: nothing is sliced out, nothing
f32 of activation size is written) and writes x, B and C as the three
arrays the scan takes; ``jax.numpy`` on a slice of the projection where
the kernels do not take the shape (``ssm_conv.supports``: channels and
an offset of whole lane blocks, whole row blocks, four taps). ``D x``,
the gate and the group norm are ``kernels/ssm_gate_norm.py``'s: a kernel
pair that reads ``y``, ``x`` and z's columns of the projection in place
and hands the out-projection its operand in the model's dtype, nothing
f32 of activation size between them; ``GatedGroupRMSNorm`` in
``jax.numpy`` where the kernels do not take the shape
(``ssm_gate_norm.supports``: groups of whole lane blocks, whole row
blocks). What is left to XLA here is the two projections and the step
sizes. All of it but the scan's kernels runs under the scope
``ssm_mixer``, the other two pairs of kernels too, so that a trace tells
what the layer costs round its scan (``docs/design/observability.md``);
each trace of the layer leaves its plan as the point event ``ssm.plan``.

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

from autodist_tpu import telemetry
from autodist_tpu.const import AXIS_DATA, AXIS_SEQUENCE
from autodist_tpu.kernels import ssd_scan, ssm_conv, ssm_gate_norm
from autodist_tpu.kernels.ssm_conv import causal_conv  # noqa: F401
from autodist_tpu.models.core import (Dense, GatedGroupRMSNorm, Module,
                                      ParamDef)
from autodist_tpu.parallel.axes import (active_manual_axes, current_mesh,
                                        manual_axis, shard_map,
                                        unsharded_execution)


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
        inner = self.inner
        with jax.named_scope('ssm_mixer'):
            zxbcdt = self.w_in.apply(params['in'], u)
            self._record_plan(*zxbcdt.shape[1:])
            x, b, c = self._conv(params, zxbcdt)
            dt = jax.nn.softplus(
                zxbcdt[..., inner + self.conv_dim:].astype(jnp.float32)
                + params['dt_bias'])
            a = -jnp.exp(params['a_log'])
        y = self._scan(x, dt, a, b, c)
        with jax.named_scope('ssm_mixer'):
            y = self._gate_norm(params, y, x, zxbcdt)
            return self.w_out.apply(params['out'], y)

    def _record_plan(self, seq, width):
        """One ``ssm.plan`` point event a trace of the layer: how the
        conv's and the gate norm's kernels walk the projection's output
        ``[., seq, width]``, or that ``jax.numpy`` does."""
        states = self.groups * self.state
        conv = ssm_conv.plan(seq, width, self.inner,
                             (self.inner, states, states), self.conv)
        gate = ssm_gate_norm.plan(seq, width, 0, self.inner, self.groups)
        telemetry.get().loop_event(
            'ssm.plan', conv='pallas' if conv else 'xla',
            channels=self.conv_dim, taps=self.conv,
            block_rows=conv.block_rows if conv else None,
            block_lanes=conv.tiles if conv else None,
            in_place=conv is not None, split_outputs=conv is not None,
            gate_norm='pallas' if gate else 'xla',
            gate_norm_block_rows=gate.block_rows if gate else None,
            gate_norm_group_lanes=gate.group_lanes if gate else None)

    def _conv(self, params, zxbcdt):
        """``x, B, C = split(silu(conv(xBC) + b_conv))`` from the
        projection's output as it is: ``kernels/ssm_conv.py``'s kernels on
        its own columns where they take the shape, ``jax.numpy`` on a
        slice of it otherwise; f32 inside, the model's dtype out. Under a
        mesh that shards the batch, on each device's batch as
        :meth:`_scan`."""
        states = self.groups * self.state
        widths = (self.inner, states, states)

        def conv(zxbcdt, taps, bias):
            return ssm_conv.conv_silu(zxbcdt, taps, bias, self.inner, widths)
        if unsharded_execution():
            return conv(zxbcdt, params['conv'], params['conv_bias'])
        rows = P(AXIS_DATA, None, None)
        return shard_map(conv, current_mesh(), (rows, P(), P()),
                         (rows, rows, rows))(
                             zxbcdt, params['conv'], params['conv_bias'])

    def _gate_norm(self, params, y, x, zxbcdt):
        """``GroupRMSNorm((y + D x) silu(z)) scale`` in the model's dtype,
        z the projection's first ``inner`` columns:
        ``kernels/ssm_gate_norm.py``'s kernels on ``y``, ``x`` and those
        columns in place where they take the shape, else
        ``GatedGroupRMSNorm`` on a slice; f32 inside either way. Under a
        mesh that shards the batch, on each device's batch as
        :meth:`_scan`."""
        skip = jnp.repeat(params['d'], self.head_dim)
        scale = params['norm']['scale']
        if not ssm_gate_norm.supports(*zxbcdt.shape[1:], 0, self.inner,
                                      self.groups):
            y = y.astype(jnp.float32) + skip * x.astype(jnp.float32)
            return self.norm.apply(params['norm'], y,
                                   zxbcdt[..., :self.inner])

        def gate_norm(y, x, zxbcdt, skip, scale):
            return ssm_gate_norm.gate_norm(y, x, zxbcdt, skip, scale, 0,
                                           self.groups, self.norm.eps)
        if unsharded_execution():
            return gate_norm(y, x, zxbcdt, skip, scale)
        rows = P(AXIS_DATA, None, None)
        return shard_map(gate_norm, current_mesh(),
                         (rows, rows, rows, P(), P()), rows)(
                             y, x, zxbcdt, skip, scale)

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
