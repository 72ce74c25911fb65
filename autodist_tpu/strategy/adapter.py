"""Bridge: reference-style strategies over functional (pytree) models.

The reference's builders analyze a captured tf.Graph (SURVEY.md §2.1);
the functional path has no graph, just a param pytree with logical-axis
metadata. :class:`PytreeGraphItem` adapts that pytree to the GraphItem
interface the builders consume (``trainable_var_op_to_var`` +
``is_sparse``), so ALL eight builders run unchanged on functional models.

:func:`apply_strategy_to_trainer_shardings` then lowers the built
strategy onto Trainer shardings: a variable the strategy partitions gets
its state sharded over the ``data`` axis along the strategy's partition
axis (the ZeRO realization of PS placement; SURVEY.md §7 design
translation table), while AllReduce variables stay replicated (GSPMD
inserts the gradient psum).
"""
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

import jax

from autodist_tpu.const import AXIS_DATA
from autodist_tpu.strategy.base import PSSynchronizer
from autodist_tpu.utils import logging


class FunctionalModel:
    """Zero-touch adapter for third-party functional models.

    The reference distributes *unmodified* user Keras/TF code by
    monkey-patching TF internals (``autodist/patch.py:96-197``, cases
    c1/c3/c5/c7). The functional equivalent needs no patching: wrap the
    user's own ``init_fn(rng) -> params`` and ``loss_fn(params, batch)
    -> scalar`` (flax, haiku, or plain jax — anything producing a param
    pytree) plus an OPTIONAL logical-axes pytree, and the result speaks
    the Trainer/strategy model protocol:

        import flax.linen as nn
        mod = nn.Dense(128)
        model = FunctionalModel(
            init_fn=lambda rng: mod.init(rng, example)['params'],
            loss_fn=lambda p, b: loss_of(mod.apply({'params': p}, b)),
            axes={'kernel': ('embed', 'mlp'), 'bias': (None,)})
        trainer = trainer_from_strategy(model, optax.adam(1e-3),
                                        PSLoadBalancing())

    ``axes`` leaves are logical-axis tuples (one entry per dim); missing
    ``axes`` means every param is unannotated (replicated until a
    strategy or ZeRO shards it). An optional ``apply_fn`` is carried for
    serving/export convenience.
    """

    def __init__(self, init_fn, loss_fn, axes=None, apply_fn=None):
        self._init_fn = init_fn
        self._loss_fn = loss_fn
        self._axes = axes
        self.apply = apply_fn

    def init(self, rng):
        return self._init_fn(rng)

    def loss(self, params, batch):
        return self._loss_fn(params, batch)

    def axes(self):
        if self._axes is not None:
            return self._axes
        shapes = jax.eval_shape(self._init_fn, jax.random.PRNGKey(0))
        return jax.tree.map(lambda l: (None,) * len(l.shape), shapes)


class _VarLike:
    """Duck-typed Variable for strategy builders (shape/dtype/name)."""

    def __init__(self, name, shape, dtype, sparse=False):
        self.name = name
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.sparse_read = sparse

    @property
    def nbytes(self):
        n = self.dtype.itemsize
        for d in self.shape:
            n *= d
        return n


class PytreeGraphItem:
    """GraphItem facade over a functional model's param pytree.

    Variables are named by their pytree path (``'blocks/mlp/up/kernel'``).
    A leaf whose logical axes include ``vocab`` is flagged sparse —
    embedding tables get gather-style (IndexedSlices-like) gradients,
    which is what Parallax keys its dense/sparse split on
    (parallax_strategy.py:38-70).
    """

    def __init__(self, model, rng=None):
        self.model = model
        shapes = jax.eval_shape(model.init,
                                rng if rng is not None
                                else jax.random.PRNGKey(0))
        axes = model.axes()
        self._vars = {}
        flat_s = _flatten_with_paths(shapes)
        flat_a = dict(_flatten_with_paths(
            axes, is_leaf=lambda x: x is None or (
                isinstance(x, tuple) and
                all(isinstance(a, (str, type(None))) for a in x))))
        for path, leaf in flat_s:
            ax = flat_a.get(path) or ()
            self._vars[path] = _VarLike(
                path, leaf.shape, leaf.dtype,
                sparse='vocab' in ax)

    @property
    def trainable_var_op_to_var(self):
        return self._vars

    def is_sparse(self, var):
        return var.sparse_read

    def var_by_name(self, name):
        return self._vars[name]

    def prepare(self):
        return self


def _flatten_with_paths(tree, is_leaf=None):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    out = []
    for path, leaf in flat:
        name = '/'.join(str(getattr(k, 'key', getattr(k, 'idx', k)))
                        for k in path)
        out.append((name, leaf))
    return out


def apply_strategy_to_shardings(strategy, graph_item, shardings, mesh):
    """Refine a Trainer sharding tree according to a built Strategy.

    Partitioned (PS or AR) variables: state shards over ``data`` along the
    strategy's partition axis when divisible. Plain PS variables with no
    partitioning stay replicated (a single logical server is the
    degenerate shard). Returns a new sharding pytree.
    """
    nodes = {n.var_name: n for n in strategy.node_config}
    flat = dict(_flatten_with_paths(shardings,
                                    is_leaf=lambda x: isinstance(
                                        x, NamedSharding)))
    dp = mesh.shape.get(AXIS_DATA, 1)
    out = {}
    for name, sharding in flat.items():
        node = nodes.get(name)
        out[name] = sharding
        if node is None or dp <= 1:
            continue
        var = graph_item.var_by_name(name)
        axis = node.partition_axis
        if axis is None:
            continue
        spec = list(sharding.spec) + [None] * (len(var.shape) -
                                               len(sharding.spec))
        if spec[axis] is None and var.shape[axis] % dp == 0 and \
                var.shape[axis] >= dp:
            spec[axis] = AXIS_DATA
            out[name] = NamedSharding(mesh, P(*spec))
        else:
            logging.debug('Cannot shard %s axis %d over data (%s)',
                          name, axis, var.shape)
    # rebuild the tree in the original structure
    leaves, treedef = jax.tree_util.tree_flatten(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    names = [n for n, _ in _flatten_with_paths(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))]
    return jax.tree_util.tree_unflatten(
        treedef, [out[n] for n in names])


def grad_bucket_layout(strategy, graph_item):
    """Byte-capped gradient-bucket layout for a strategy's AllReduce vars.

    The same packing the execution plan applies at trace time
    (``parallel.plan.pack_buckets``: same-(group, compressor, spec)
    variables, reverse production order, cap from the synchronizer's
    ``chunk_size`` / ``AUTODIST_BUCKET_BYTES``), computed statically
    from the strategy + variable shapes so callers (bench reporting,
    tooling) can audit the layout without tracing a step. Returns
    ``[{'group', 'vars': [names], 'bytes'}]`` in emission order.
    """
    from autodist_tpu.const import DEFAULT_CHUNK_SIZE
    from autodist_tpu.parallel.plan import bucket_bytes_cap, pack_buckets
    from autodist_tpu.strategy.base import AllReduceSynchronizer

    # mirror sync_gradients' fusable filter and grouping key exactly:
    # only stateless compressors fuse (stateful ones reduce per-var),
    # the key includes the gradient dtype (mixed-dtype groups split),
    # the hierarchical knob (mixed flat/two-level members split) and
    # the weight-update-sharding knob (mixed replicated/sharded-update
    # members split — their emissions differ in kind, not just shape)
    groups = {}   # (group, compressor, spec, dtype, hier, wus) -> items
    for node in strategy.node_config:
        sync = node.synchronizer if not node.part_config \
            else node.part_config[0]
        if not isinstance(sync, AllReduceSynchronizer):
            continue
        if sync.compressor not in ('NoneCompressor',
                                   'HorovodCompressor'):
            continue
        try:
            var = graph_item.var_by_name(node.var_name)
        except KeyError:
            continue
        nbytes = int(np.prod(var.shape or (1,))) * \
            np.dtype(var.dtype).itemsize
        wus = getattr(sync, 'weight_update_sharding', 'never') or \
            'never'
        if getattr(var, 'sparse_read', False):
            wus = 'ineligible'   # mirror VarPlan's row-lazy exclusion
        groups.setdefault(
            (sync.group, sync.compressor, sync.spec,
             str(np.dtype(var.dtype)),
             getattr(sync, 'hierarchical', 'auto') or 'auto', wus),
            []).append(
            (node.var_name, nbytes, getattr(sync, 'chunk_size', 0)))
    out = []
    for (group, *_), items in sorted(groups.items(), reverse=True):
        chunk = max(c for _, _, c in items)
        cap = bucket_bytes_cap(chunk)
        rev = [(name, nbytes) for name, nbytes, _ in reversed(items)]
        sizes = dict(rev)
        for bucket in pack_buckets(rev, cap,
                                   chunk or DEFAULT_CHUNK_SIZE):
            out.append({'group': group, 'vars': list(bucket),
                        'bytes': sum(sizes[n] for n in bucket)})
    return out


def trainer_from_strategy(model, optimizer, strategy_builder,
                          resource_spec=None, spec=None, **kw):
    """Build a Trainer whose state shardings follow a reference-style
    strategy built by ``strategy_builder`` over the model's pytree."""
    from autodist_tpu.api import Trainer
    from autodist_tpu.resource_spec import ResourceSpec

    gi = PytreeGraphItem(model)
    if resource_spec is None:
        import jax as _jax

        from autodist_tpu.autodist import _default_resource_info
        resource_spec = ResourceSpec(
            resource_info=_default_resource_info(_jax.devices()))
    strategy = strategy_builder.build(gi, resource_spec)
    trainer = Trainer(model, optimizer, spec=spec, **kw)
    trainer.param_shardings = apply_strategy_to_shardings(
        strategy, gi, trainer.param_shardings, trainer.mesh)
    trainer.strategy = strategy
    trainer.grad_buckets = grad_bucket_layout(strategy, gi)
    return trainer
