"""The residual path as several streams: manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, after Hyper-Connections,
arXiv:2409.19606), one :class:`HyperConnection` a sublayer.

A token's residual state is ``n`` streams of ``dim`` lanes, ``x [n,
dim]``. A sublayer ``F`` (attention or the MLP, with its own pre-norm)
reads ONE mix of them and writes back to ALL of them, and the streams
themselves are mixed, by three per-token sets of coefficients made from
the streams:

* ``v = RMSNorm(vec(x))`` over the ``n dim`` numbers (``eps``, no weight);
* ``P = alpha_pre (v phi_pre) + b_pre [n]``, ``Q = alpha_post (v phi_post)
  + b_post [n]``, ``R = alpha_res mat(v phi_res) + b_res [n, n]``;
* ``H_pre = sigmoid(P)``, ``H_post = 2 sigmoid(Q)``, ``H_res =
  SK(clip(R, clamp))``: Sinkhorn-Knopp from ``M = exp(.)``, ``iters``
  rounds of "every column divided by its sum + eps, then every row by its
  sum + eps", which leaves ``H_res`` (nearly) doubly stochastic: mixing the
  streams neither grows nor shrinks their sum;
* ``u = H_pre x [dim]``, ``y = F(u)``, ``x' = H_res x + H_post^T y``.

The coefficients are f32 and differentiated through every round.

Layout. The streams lie side by side on the lanes, ``x [b, s, n * dim]``
with stream ``i`` the lanes ``i dim .. (i + 1) dim``: the bytes of a
row-major ``[b, s, n, dim]``, as an array whose lanes the TPU tiles whole
(a 4-D array with a stream axis of 4 between ``s`` and ``dim`` the compiler
laid out three ways with copies between them: 5.5 GB of temporaries against
2.1 in a five-layer probe compiled for a v5e). The per-token ``n x n`` work
is laid out with the TOKENS on the lanes: the coefficients are ``[n (n +
2), b, s]``, a Sinkhorn round adds and divides ``[n, n, b, s]`` along its
leading axes, elementwise over whole ``[b, s]`` tiles, and no ``[tokens, n,
n]`` array (16 numbers on a 128-lane tile) exists.

Parameters: ``phi [n dim, n (n + 2)]`` (the columns ``pre | post | res``,
``res`` row-major ``(i, j)``), ``alpha [3]`` (the three gates), ``bias [n
(n + 2)]``. ``init`` draws the connection NEAR the plain residual path and
not at it: gates 0.01, ``H_pre`` 1 / n each (the sublayer reads the
streams' mean), ``H_post`` 1, ``H_res`` within ``exp(-RES_INIT)`` of the
identity; ``phi`` N(0, 1 / (n dim)).

Paths. Where ``kernels/hyper_connections.supports`` takes a device's
rows (``dim`` whole 128-lane blocks, rows whole blocks of 128, a block
that fits VMEM: :meth:`HyperConnection.kernel_plan`), a connection is four
Pallas kernels, each one pass over the streams where they lie:
``hc_enter_fwd`` / ``hc_enter_bwd`` (the coefficients AND the read, one
``jax.custom_vjp``) and ``hc_leave_fwd`` / ``hc_leave_bwd`` (the write-back
and the stream mix); under a data-parallel mesh on each device's rows, in
a manual region. Every other shape (tiny test widths, a mesh that shards
anything but the batch) keeps the ``jax.numpy`` form below, which is also
what the kernels are held to in the tests. The choice is by shape alone.

Scopes: ``hc`` around everything here (inside ``block``, beside
``attention`` and ``mlp``, not around them), ``hc_coeff`` (the norm, the
``phi`` product, Sinkhorn; on the kernels' path ``hc_enter_*``, which also
hold the read) and ``hc_mix`` (the three mixes; on the kernels' path
``hc_leave_*``: the write-back and the stream mix alone) inside it; the
model leaves one point event ``hc.plan`` a trace (``TransformerLM``,
docs/design/observability.md).
"""
import collections
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from autodist_tpu.const import AXIS_DATA, AXIS_SEQUENCE
from autodist_tpu.kernels import hyper_connections as hc_kernels
from autodist_tpu.models.core import Module, ParamDef
from autodist_tpu.parallel.axes import (active_manual_axes, current_mesh,
                                        manual_axis, shard_map,
                                        unsharded_execution)

ALPHA_INIT = 0.01
RES_INIT = 8.0       # H_res's diagonal over its off-diagonal, as a logit


def sinkhorn(logits, iters, eps):
    """``logits [n, n, ...]`` to ``H_res``: ``exp``, then ``iters`` rounds
    of columns (over axis 0: ``sum_i H[i, j]``) and then rows (over axis
    1) divided by their sums + ``eps``. Rows sum to 1 within ``eps``
    after any round; columns as far as the rounds have converged."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def split_streams(x, n):
    """The ``n`` streams of ``x [b, s, n dim]`` in f32, each the lanes
    where it lies (no stream axis is made: the module's docstring)."""
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(n)]


# What the kernels' `enter` holds for `leave`: ``H_post | H_res`` as ``[n (n
# + 1), rows]`` f32 (the tokens on the lanes) and the streams as `enter`
# read them. `leave` reads THESE streams, so that the two cotangents of
# ``x`` meet in ``hc_enter_bwd`` and are added on its block.
_KernelHeld = collections.namedtuple('_KernelHeld', 'coefficients streams')
# where a device's rows are, under a data-parallel mesh: the leading axis
# of the streams, the trailing one of the held coefficients
_ROWS, _HELD = P(AXIS_DATA), P(None, AXIS_DATA)


class HyperConnection(Module):
    """One sublayer's connection over ``streams`` streams of ``dim``
    (the module's docstring), in two parts around the sublayer, which
    runs under a scope of its own between them and not inside ``hc``:
    ``u, held = enter(params, x)``, ``y = F(u)``, ``x', err = leave(x, y,
    held)``; ``err`` is the mean over the tokens of ``max_j |sum_i H_res[i,
    j] - 1|`` (how far the rounds are from converged at these weights; no
    gradient). ``held`` is :meth:`leave`'s alone to read."""

    def __init__(self, dim, streams, iters=20, clamp=(-30.0, 30.0),
                 eps=1e-6, dtype=jnp.float32):
        self.dim, self.streams, self.iters = dim, streams, iters
        self.clamp, self.eps, self.dtype = tuple(clamp), eps, dtype

    def param_defs(self):
        n = self.streams
        return {'phi': ParamDef((n * self.dim, n * (n + 2)), (None, None),
                                'fan_in'),
                'alpha': ParamDef((3,), (None,), 'zeros'),
                'bias': ParamDef((n * (n + 2),), (None,), 'zeros')}

    def init(self, rng):
        n = self.streams
        params = super().init(rng)
        params['alpha'] = jnp.full((3,), ALPHA_INIT, jnp.float32)
        params['bias'] = jnp.concatenate([
            jnp.full((n,), -math.log(max(n - 1, 1)), jnp.float32),
            jnp.zeros((n,), jnp.float32),
            RES_INIT * jnp.eye(n, dtype=jnp.float32).ravel()])
        return params

    @jax.named_scope('hc_coeff')
    def coefficients(self, params, x):
        """``(H_pre [n, b, s], H_post [n, b, s], H_res [n, n, b, s])`` in
        f32 for the streams ``x [b, s, n dim]``. ``v phi`` is taken as
        ``(x phi) / rms(x)``: the product reads the streams as they lie,
        and no normalised copy of them exists."""
        n = self.streams
        b, s, _ = x.shape
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1)
        # (the product as [b, s, n (n + 2)] and then the tokens to the
        # lanes: XLA:CPU has no bf16 product that writes f32 transposed)
        logits = jnp.moveaxis(jnp.einsum(
            'bsk,kj->bsj', x, params['phi'].astype(self.dtype),
            preferred_element_type=jnp.float32), -1, 0)
        gate = params['alpha'][np.repeat(np.arange(3), [n, n, n * n])]
        logits = logits * (jax.lax.rsqrt(ms + self.eps)
                           * gate[:, None, None]) \
            + params['bias'][:, None, None]
        pre = jax.nn.sigmoid(logits[:n])
        post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
        res = sinkhorn(jnp.clip(logits[2 * n:].reshape(n, n, b, s),
                                *self.clamp), self.iters, self.eps)
        return pre, post, res

    @jax.named_scope('hc_mix')
    def read(self, x, pre):
        """``u = H_pre x``: ``[b, s, dim]`` in the streams' dtype."""
        return sum(h[..., None] * xi
                   for h, xi in zip(pre, split_streams(x, self.streams))
                   ).astype(x.dtype)

    @jax.named_scope('hc_mix')
    def write(self, x, y, post, res):
        """``x' = H_res x + H_post^T y``: ``[b, s, n dim]``, each stream
        summed in f32 and rounded once."""
        xs, y = split_streams(x, self.streams), y.astype(jnp.float32)
        return jnp.concatenate([
            (sum(h[..., None] * xj for h, xj in zip(res[i], xs))
             + post[i][..., None] * y).astype(x.dtype)
            for i in range(self.streams)], axis=-1)

    def kernel_plan(self, shape, dtype):
        """``kernels/hyper_connections.plan`` for the rows a device holds
        of streams ``[b, s, n dim]`` in the current trace, or None where
        the connection runs in ``jax.numpy``: a shape the kernels do not
        take, or a mesh that shards anything but the batch."""
        b, s, _ = shape
        if dtype != self.dtype or manual_axis(AXIS_SEQUENCE) is not None:
            return None
        if not unsharded_execution():
            mesh = current_mesh()
            dp = mesh.shape.get(AXIS_DATA, 1)
            others = [a for a, size in mesh.shape.items()
                      if size > 1 and a != AXIS_DATA]
            if active_manual_axes() or others or b % dp:
                return None
            b //= dp
        return hc_kernels.plan(b * s, self.streams, self.dim, dtype,
                               self.iters)

    @staticmethod
    def _on_each_device(kernels, operands, in_specs, out_specs):
        """``kernels(*operands)``; under a data-parallel mesh on each
        device's rows, in a manual region (``MultiHeadAttention.
        _qk_normed``'s)."""
        mesh = None if unsharded_execution() else current_mesh()
        if mesh is None:
            return kernels(*operands)
        return shard_map(kernels, mesh, in_specs, out_specs)(*operands)

    def enter(self, params, x):
        """``(u, held)``: what the sublayer reads, ``[b, s, dim]``, and
        what :meth:`leave` writes its output back with."""
        with jax.named_scope('hc'):
            if self.kernel_plan(x.shape, x.dtype) is None:
                pre, post, res = self.coefficients(params, x)
                return self.read(x, pre), (post, res)

            def kernels(x, phi, alpha, bias):
                return hc_kernels.enter(x, phi, alpha, bias, self.streams,
                                        self.iters, self.clamp, self.eps)
            with jax.named_scope('hc_coeff'):
                u, coefficients, x = self._on_each_device(
                    kernels, (x, params['phi'].astype(self.dtype),
                              params['alpha'], params['bias']),
                    (_ROWS, P(), P(), P()), (_ROWS, _HELD, _ROWS))
            return u, _KernelHeld(coefficients, x)

    def leave(self, x, y, held):
        """``(x', err)`` from the streams ``x`` the sublayer read and its
        output ``y [b, s, dim]``."""
        with jax.named_scope('hc'):
            if isinstance(held, _KernelHeld):
                def kernels(x, y, coefficients):
                    return hc_kernels.leave(x, y, coefficients, self.streams,
                                            self.iters)
                with jax.named_scope('hc_mix'):
                    x = self._on_each_device(
                        kernels, (held.streams, y, held.coefficients),
                        (_ROWS, _ROWS, _HELD), _ROWS)
                n = self.streams
                res = held.coefficients[n:].reshape(n, n, -1)
            else:
                post, res = held
                x = self.write(x, y, post, res)
            with jax.named_scope('hc_coeff'):
                err = jax.lax.stop_gradient(jnp.mean(jnp.max(
                    jnp.abs(jnp.sum(res, axis=0) - 1.0), axis=0)))
        return x, err
