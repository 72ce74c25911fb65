"""Minimal functional module system for the model zoo.

Models are pytrees of plain ``jax.Array`` params plus a parallel metadata
tree of *logical axis names* consumed by the sharding compiler
(:mod:`autodist_tpu.parallel.axes`). No framework magic: ``init`` builds
the param dict, ``apply`` is a pure function, so every model composes with
``jit`` / ``shard_map`` / ``jax.grad`` directly. This replaces the
reference's reliance on captured TF graphs + Keras (SURVEY.md §7: the
capture shim is only needed for API parity, not for the compute path).

Conventions:
- ``param_defs()`` -> {name: ParamDef | Module} describes one module level.
- params are nested dicts mirroring that structure.
- ``axes()`` returns the same nesting with ``ParamDef.axes`` at leaves.
- compute dtype is configurable (bfloat16 by default on TPU-class runs);
  params stay float32 (master weights), cast at use.
"""
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from autodist_tpu.parallel.axes import (constrain, current_mesh,
                                        live_mesh_axis, manual_axis)


def sharded_embedding_lookup(table, ids, axis):
    """Row gather from a table sharded along dim 0 over mesh axis ``axis``.

    Each shard takes the rows it owns (out-of-range rows fill with 0) and
    a psum over the axis assembles full rows: comm is O(batch*dim), vs the
    O(batch*vocab) one-hot matmul. Works both inside an already-manual
    region (explicit collectives) and under GSPMD (wrapped in a
    partial-manual shard_map over just the vocab axis)."""
    def masked(shard, ids_):
        size = shard.shape[0]
        local = ids_ - jax.lax.axis_index(axis) * size
        # negative indices would wrap (numpy semantics); send them out of
        # bounds high so mode='fill' zeroes them
        local = jnp.where(local >= 0, local, size)
        rows = jnp.take(shard, local, axis=0, mode='fill', fill_value=0)
        return jax.lax.psum(rows, axis)

    if manual_axis(axis):
        return masked(table, ids)
    if jax.sharding.get_abstract_mesh().shape:
        # already inside a manual region where the vocab axis stays auto
        # (shardy rejects a nested shard_map re-entering those axes):
        # fall back to the one-hot matmul (partitions cleanly under
        # GSPMD and runs on the MXU).
        vocab = table.shape[0]
        oh = jax.nn.one_hot(ids, vocab, dtype=table.dtype)
        return oh @ table
    from jax.sharding import PartitionSpec as P

    from autodist_tpu.parallel.axes import shard_map
    return shard_map(
        masked, current_mesh(), (P(axis), P()), P(),
        axis_names={axis})(table, ids)


@dataclass
class ParamDef:
    shape: tuple
    axes: tuple            # logical axis names, len == len(shape)
    init: str = 'normal'   # normal | zeros | ones | fan_in
    scale: float = 0.02
    # False = a STATE leaf (e.g. BatchNorm running stats): lives in the
    # params tree for checkpoint/sharding purposes, but the optimizer
    # must not touch it — it advances via record_state_update instead.
    trainable: bool = True


class Module:
    """Base: generic init/axes tree walks over ``param_defs()``."""

    def param_defs(self):
        raise NotImplementedError

    def apply(self, params, *args, **kwargs):
        raise NotImplementedError

    def init(self, rng):
        defs = self.param_defs()
        keys = jax.random.split(rng, max(len(defs), 1))
        out = {}
        for k, (name, d) in zip(keys, sorted(defs.items())):
            out[name] = d.init(k) if isinstance(d, Module) \
                else _init_leaf(k, d)
        return out

    def axes(self):
        return {name: (d.axes() if isinstance(d, Module) else d.axes)
                for name, d in sorted(self.param_defs().items())}

    def trainable_mask(self):
        """Bool tree mirroring ``init``: False at state leaves."""
        return {name: (d.trainable_mask() if isinstance(d, Module)
                       else d.trainable)
                for name, d in sorted(self.param_defs().items())}

    def has_state(self):
        return not all(jax.tree.leaves(self.trainable_mask()))

    def __call__(self, params, *args, **kwargs):
        return self.apply(params, *args, **kwargs)


# ---------------------------------------------------------------------------
# Model state (BatchNorm running stats etc.)
#
# State leaves live in the params tree (so sharding/checkpointing need no
# second tree) but advance through a trace-time side channel: during the
# loss trace a collector is active, stateful modules call
# ``record_state_update(path, value)``, and the trainer folds the updates
# back into the non-trainable leaves INSTEAD of an optimizer step. Paths
# are assigned to module instances once per trainer (``assign_state_paths``),
# which requires stateful modules to be held as attributes (they are).
# ---------------------------------------------------------------------------
import threading as _threading

_MODEL_CTX = _threading.local()


class _StateCollector:
    def __init__(self, training):
        self.training = training
        self.updates = {}    # path tuple -> new value (tracer ok)
        self.counters = {}   # name -> scalar of this step (tracer ok)


class model_mode:
    """Context: set training/eval mode and collect state updates during
    a (traced) forward. ``updates`` is populated at trace time."""

    def __init__(self, training=True):
        self._col = _StateCollector(training)

    @property
    def updates(self):
        return self._col.updates

    @property
    def counters(self):
        return self._col.counters

    def __enter__(self):
        stack = getattr(_MODEL_CTX, 'stack', None)
        if stack is None:
            stack = _MODEL_CTX.stack = []
        stack.append(self._col)
        return self

    def __exit__(self, *exc):
        _MODEL_CTX.stack.pop()


def _collector():
    stack = getattr(_MODEL_CTX, 'stack', None)
    return stack[-1] if stack else None


def is_training():
    """True outside any model_mode context (benchmark semantics)."""
    col = _collector()
    return True if col is None else col.training


def record_state_update(module, name, value):
    """Record a new value for state leaf ``name`` of ``module`` (no-op
    when no collector is active, e.g. plain benchmark forwards)."""
    col = _collector()
    if col is None:
        return
    path = getattr(module, '_state_path', None)
    if path is None:
        raise ValueError(
            '%s has state but no assigned path — build it through a '
            'Trainer (assign_state_paths) to track running statistics'
            % type(module).__name__)
    col.updates[path + (name,)] = value


def record_counter(name, value):
    """Record a scalar the model counts in this step (the rows an expert
    layer holds, ...), for the trainer to return beside the loss. Like a
    state update it leaves through the trace-time collector, so it has
    to be a value of the loss function's own trace (not of a scan body
    or a manual region inside it); a no-op when no collector is
    active."""
    col = _collector()
    if col is not None:
        col.counters[name] = value


def assign_state_paths(module, prefix=(), _seen=None):
    """Walk the module tree ONCE, stamping each submodule with its param
    path so state updates can be folded back by position.

    Stateful modules must occupy exactly ONE tree position and run once
    per loss forward — a single stamped path cannot represent two
    positions, so sharing a stateful instance (e.g. one BatchNorm used
    twice) is rejected here rather than silently dropping updates.
    Stateless instances may be shared freely."""
    if _seen is None:
        _seen = set()
    if id(module) in _seen and module.has_state():
        raise ValueError(
            'stateful module %s appears at multiple tree positions '
            '(%s and %s); give each position its own instance so its '
            'running statistics have a unique home'
            % (type(module).__name__, module._state_path, prefix))
    _seen.add(id(module))
    module._state_path = prefix
    for name, d in module.param_defs().items():
        if isinstance(d, Module):
            assign_state_paths(d, prefix + (name,), _seen)


def apply_tree_updates(tree, updates):
    """Return a copy of ``tree`` with ``{path tuple: value}`` entries
    replaced (copy-on-write along each path; the input is untouched)."""
    out = dict(tree)
    for path, value in updates.items():
        node = out
        for key in path[:-1]:
            node[key] = dict(node[key])
            node = node[key]
        node[path[-1]] = value.astype(node[path[-1]].dtype)
    return out


def _init_leaf(rng, d):
    if d.init == 'zeros':
        return jnp.zeros(d.shape, jnp.float32)
    if d.init == 'ones':
        return jnp.ones(d.shape, jnp.float32)
    if d.init == 'fan_in':
        # fan-in = product of all non-output dims (for a dense (in, out)
        # kernel that is `in`; for a conv HWIO kernel it is h*w*in)
        if len(d.shape) > 1:
            fan_in = 1
            for s in d.shape[:-1]:
                fan_in *= s
        else:
            fan_in = max(d.shape[0], 1)
        std = 1.0 / math.sqrt(fan_in)
        return jax.random.normal(rng, d.shape, jnp.float32) * std
    return jax.random.normal(rng, d.shape, jnp.float32) * d.scale


class Sequential(Module):
    """Compose modules; params keyed layer_0, layer_1, ..."""

    def __init__(self, layers):
        self.layers = list(layers)

    def param_defs(self):
        return {'layer_%03d' % i: m for i, m in enumerate(self.layers)}

    def apply(self, params, x, **kw):
        for i, m in enumerate(self.layers):
            x = m.apply(params['layer_%03d' % i], x, **kw)
        return x


class Dense(Module):
    """y = x @ w + b with logical axes for the two matmul dims."""

    def __init__(self, in_dim, out_dim, in_axis='embed', out_axis='mlp',
                 use_bias=True, dtype=jnp.float32, name=None):
        self.in_dim, self.out_dim = in_dim, out_dim
        self.in_axis, self.out_axis = in_axis, out_axis
        self.use_bias = use_bias
        self.dtype = dtype

    def param_defs(self):
        d = {'kernel': ParamDef((self.in_dim, self.out_dim),
                                (self.in_axis, self.out_axis), 'fan_in')}
        if self.use_bias:
            d['bias'] = ParamDef((self.out_dim,), (self.out_axis,), 'zeros')
        return d

    def apply(self, params, x):
        w = params['kernel'].astype(self.dtype)
        y = x.astype(self.dtype) @ w
        if self.use_bias:
            y = y + params['bias'].astype(self.dtype)
        return y


class Embedding(Module):
    """Token embedding; vocab dim shardable (EP-lite of the reference's
    partitioned embeddings, partitioner.py:576-602)."""

    def __init__(self, vocab, dim, vocab_axis='vocab', dim_axis='embed',
                 dtype=jnp.float32, init_scale=0.02):
        self.vocab, self.dim = vocab, dim
        self.vocab_axis, self.dim_axis = vocab_axis, dim_axis
        self.dtype = dtype
        self.init_scale = init_scale   # std of a row's elements at init

    def param_defs(self):
        return {'table': ParamDef((self.vocab, self.dim),
                                  (self.vocab_axis, self.dim_axis),
                                  'normal', self.init_scale)}

    def apply(self, params, ids):
        table = params['table'].astype(self.dtype)
        axis = live_mesh_axis(self.vocab_axis)
        if axis is not None:
            # Vocab-sharded table: masked local gather + psum, O(B*dim)
            # comm instead of the O(B*vocab) one-hot matmul (the sharded
            # analogue of the reference's embedding_lookup_v2 over
            # partitioned vars, partitioner.py:576-602). The backward pass
            # transposes to a per-shard scatter-add of only the rows each
            # shard owns — the sparse gradient path, compiled by XLA.
            return sharded_embedding_lookup(table, ids, axis)
        return jnp.take(table, ids, axis=0)

    def attend(self, params, x):
        """Tied-output logits: x @ table.T"""
        return x @ params['table'].astype(self.dtype).T


class LayerNorm(Module):
    """LayerNorm in f32 over the last dim; ``use_bias=False`` has a
    scale and no bias (ModernBERT's ``norm_bias: false``)."""

    def __init__(self, dim, axis_name='embed', eps=1e-6,
                 dtype=jnp.float32, use_bias=True):
        self.dim, self.axis_name, self.eps = dim, axis_name, eps
        self.dtype = dtype
        self.use_bias = use_bias

    def param_defs(self):
        d = {'scale': ParamDef((self.dim,), (self.axis_name,), 'ones')}
        if self.use_bias:
            d['bias'] = ParamDef((self.dim,), (self.axis_name,), 'zeros')
        return d

    def apply(self, params, x):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + self.eps)
        y = y * params['scale']
        if self.use_bias:
            y = y + params['bias']
        return y.astype(self.dtype)


class RMSNorm(Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` in f32 over the last dim:
    no mean taken out, no bias."""

    def __init__(self, dim, axis_name='embed', eps=1e-6, dtype=jnp.float32):
        self.dim, self.axis_name, self.eps = dim, axis_name, eps
        self.dtype = dtype

    def param_defs(self):
        return {'scale': ParamDef((self.dim,), (self.axis_name,), 'ones')}

    def apply(self, params, x):
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(ms + self.eps)
                * params['scale']).astype(self.dtype)


def group_mean(t, groups, width):
    """``t [..., lanes]`` with the lanes of each of the first ``groups``
    runs of ``width`` lanes replaced by the run's mean, and 0 on the
    lanes behind them: a run reduced where it lies, and the means put
    back on their lanes by selects. (Reshaped to ``[..., groups, width]``
    XLA lays the whole f32 tensor out anew for the reduction, copies
    without a name in every pass: 0.27 GB a layer at 16,384 tokens x
    4096 in ``GatedGroupRMSNorm``, three of 671 MB a pass at 32,768 rows
    of 5120 lanes in attention's q/k norm, PERF.md section 6, PR 41 and
    45; the runs concatenated cost a pass over the tensor each.)"""
    lane_group = jnp.arange(t.shape[-1]) // width
    out = jnp.zeros((), t.dtype)
    for g in range(groups):
        mean = jnp.mean(t[..., g * width:(g + 1) * width], axis=-1,
                        keepdims=True)
        out = jnp.where(lane_group == g, mean, out)
    return out


class GatedGroupRMSNorm(Module):
    """``RMSNorm(x * silu(gate))`` in f32 with the mean of squares taken
    over each of ``groups`` groups of ``dim / groups`` lanes, one scale
    of ``dim``: Mamba-2's gated norm with the gate INSIDE the norm."""

    def __init__(self, dim, groups, axis_name='heads', eps=1e-5,
                 dtype=jnp.float32):
        if dim % groups:
            raise ValueError('%d lanes do not divide over %d groups'
                             % (dim, groups))
        self.dim, self.groups, self.axis_name = dim, groups, axis_name
        self.eps, self.dtype = eps, dtype

    def param_defs(self):
        return {'scale': ParamDef((self.dim,), (self.axis_name,), 'ones')}

    def apply(self, params, x, gate):
        x32 = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        ms = group_mean(jnp.square(x32), self.groups,
                        self.dim // self.groups)
        y = x32 * jax.lax.rsqrt(ms + self.eps)
        return (y * params['scale']).astype(self.dtype)


def gelu_exact(x):
    """The erf GELU (BERT's, ModernBERT's ``"gelu"``); ``jax.nn.gelu``
    alone is the tanh approximation (GPT-2's ``gelu_new``)."""
    return jax.nn.gelu(x, approximate=False)


def relu2(x):
    """``relu(x) ** 2`` (nemotron_h's ``mlp_hidden_act: relu2``)."""
    return jnp.square(jax.nn.relu(x))


# An MLP's activation by the name a configuration gives it.
ACTIVATIONS = {'tanh': jax.nn.gelu, 'erf': gelu_exact, 'silu': jax.nn.silu,
               'relu2': relu2}


def activation(name):
    if name not in ACTIVATIONS:
        raise ValueError('activation must be one of %s, not %r'
                         % (sorted(ACTIVATIONS), name))
    return ACTIVATIONS[name]


class Mlp(Module):
    """Transformer MLP: Megatron column- then row-parallel pair. The
    default ``act`` is the TANH-approximated GELU (``jax.nn.gelu``'s
    default), which is GPT-2's and not BERT's; pass :func:`gelu_exact`
    for the erf form."""

    def __init__(self, dim, hidden, dtype=jnp.float32, act=jax.nn.gelu,
                 use_bias=True):
        self.up = Dense(dim, hidden, 'embed', 'mlp', use_bias=use_bias,
                        dtype=dtype)
        self.down = Dense(hidden, dim, 'mlp', 'embed', use_bias=use_bias,
                          dtype=dtype)
        self.act = act

    def param_defs(self):
        return {'up': self.up, 'down': self.down}

    def apply(self, params, x):
        h = self.act(self.up.apply(params['up'], x))
        h = constrain(h, ('batch', 'seq', 'mlp'))
        return self.down.apply(params['down'], h)


class GatedMlp(Module):
    """Gated MLP (GeGLU with a GELU ``act``): ``down(act(input) * gate)``
    where ``input, gate = split(x @ up)``. The up kernel is kept as
    ``[dim, 2, hidden]`` with the ``mlp`` logical axis on ``hidden``, so
    tensor parallelism cuts inside each half and a shard holds the same
    columns of input and gate (a fused ``[dim, 2 * hidden]`` kernel cut
    in two would put all of input on one shard and all of gate on the
    other). ``kernel.reshape(dim, 2 * hidden)`` is the published fused
    matrix, input first."""

    def __init__(self, dim, hidden, dtype=jnp.float32, act=gelu_exact,
                 use_bias=False):
        self.dim, self.hidden = dim, hidden
        self.dtype = dtype
        self.act = act
        self.use_bias = use_bias
        self.down = Dense(hidden, dim, 'mlp', 'embed', use_bias=use_bias,
                          dtype=dtype)

    def param_defs(self):
        # the fan-in of either half is `dim`
        up = {'kernel': ParamDef((self.dim, 2, self.hidden),
                                 ('embed', None, 'mlp'), 'normal',
                                 self.dim ** -0.5)}
        if self.use_bias:
            up['bias'] = ParamDef((2, self.hidden), (None, 'mlp'), 'zeros')
        return {'up': _Leaves(up), 'down': self.down}

    def apply(self, params, x):
        w = params['up']['kernel'].astype(self.dtype)
        u = jnp.einsum('...d,dgh->...gh', x.astype(self.dtype), w)
        if self.use_bias:
            u = u + params['up']['bias'].astype(self.dtype)
        h = self.act(u[..., 0, :]) * u[..., 1, :]
        h = constrain(h, ('batch', 'seq', 'mlp'))
        return self.down.apply(params['down'], h)


class _Leaves(Module):
    """A level of plain ``ParamDef`` leaves."""

    def __init__(self, defs):
        self._defs = defs

    def param_defs(self):
        return self._defs
