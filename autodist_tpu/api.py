"""Functional training API: the big-model path.

The reference's session path captures an unmodified TF-graph program and
rewrites it (SURVEY.md §3.2). For models written against the functional
module system (:mod:`autodist_tpu.models`), the TPU-native path skips
capture entirely: the user hands a model + optimizer + :class:`ParallelSpec`
to :class:`Trainer`, which

1. builds the device mesh (data/pipe/seq/expert/model axes),
2. binds every param to a ``NamedSharding`` from its logical axes
   (ZeRO stages extend the binding over the data axis),
3. compiles ONE fused XLA train step — forward, backward, collectives,
   optimizer — via ``jit`` with explicit in/out shardings and donated
   state (GSPMD inserts the DP/TP/EP collectives; sequence parallelism
   runs the model inside a partial-manual ``shard_map`` for ring
   attention),
4. exposes reference-shaped ergonomics: ``init`` / ``step`` / fetch.

This is the lowering target the strategy builders compile to for
functional models (strategy → ParallelSpec adapter in
:mod:`autodist_tpu.strategy.adapter`).
"""
import functools
import itertools
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import telemetry
from autodist_tpu.const import AXIS_DATA, AXIS_PIPELINE, AXIS_SEQUENCE
from autodist_tpu.parallel.axes import (ParallelSpec, sharding_ctx,
                                        shardings_for_tree, spec_for_axes)
from autodist_tpu.utils import logging


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any

    @classmethod
    def create(cls, params, opt_state):
        return cls(params=params, opt_state=opt_state,
                   step=jnp.zeros((), jnp.int32))


class Trainer:
    """Compile + drive distributed training of a functional model.

    Args:
        model: a :class:`autodist_tpu.models.core.Module` with
            ``init``/``apply``/``axes`` (and ``loss`` unless ``loss_fn``
            is given).
        optimizer: an optax ``GradientTransformation``.
        spec: :class:`ParallelSpec`; defaults to pure DP over all devices.
        loss_fn: ``loss_fn(params, batch) -> scalar``; defaults to
            ``model.loss``. In sequence-parallel mode the model must
            provide ``per_token_loss`` instead.
        mesh: optional prebuilt mesh (else ``spec.build_mesh()``).
    """

    _built = itertools.count()   # Trainers constructed in this process

    def __init__(self, model, optimizer, spec=None, loss_fn=None,
                 mesh=None, rules=None, donate=True):
        # the tag `trainer` of every loop record this Trainer makes:
        # tells the one that trains from, say, a benchmark's probe
        self._tag = next(Trainer._built)
        with self._span('trainer.new', setup=True):
            self.model = model
            self.optimizer = optimizer
            self.spec = spec or ParallelSpec()
            self.mesh = (mesh if mesh is not None
                         else self.spec.build_mesh())
            self.rules = rules if rules is not None else self.spec.rules
            self._loss_fn = loss_fn
            self._donate = donate
            self._axes_tree = model.axes()
            self.param_shardings = shardings_for_tree(
                self._axes_tree, self.rules, self.mesh)
        self._step_cache = {}
        # calls of step() so far: the number every loop span and
        # event of this trainer is tagged with (telemetry.loop_span)
        self._steps_run = 0
        # model state (BatchNorm running stats): non-trainable leaves
        # advance via recorded updates, not the optimizer
        self._has_state = getattr(model, 'has_state', lambda: False)()
        if self._has_state:
            from autodist_tpu.models.core import assign_state_paths
            assign_state_paths(model)
            self._trainable_mask = model.trainable_mask()
            self._state_paths = [
                tuple(str(k.key) for k in path)
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    self._trainable_mask)[0] if not leaf]
        logging.info('Trainer mesh: %s, zero=%d, sp=%d',
                     dict(self.mesh.shape), self.spec.zero, self.spec.sp)

    def _span(self, name, step=None, **tags):
        """A loop span of this Trainer (``telemetry.loop_span``)."""
        return telemetry.get().loop_span(name, step=step,
                                         trainer=self._tag, **tags)

    # -- sharding helpers --------------------------------------------------
    def _zero_extend(self, sharding, shape):
        """Extend a sharding over the data axis on the first free
        divisible dim (ZeRO/FSDP-style). Used for optimizer slots
        (zero>=2) and params (zero==3)."""
        spec = list(sharding.spec) + [None] * (len(shape) -
                                               len(sharding.spec))
        dp = self.mesh.shape[AXIS_DATA]
        if dp <= 1:
            return sharding
        used = {a for a in spec if a is not None}
        if AXIS_DATA in used:
            return sharding
        for i, dim in enumerate(shape):
            if spec[i] is None and dim % dp == 0 and dim >= dp:
                spec[i] = AXIS_DATA
                return NamedSharding(self.mesh, P(*spec))
        return sharding

    def _param_sharding_tree(self, params):
        shardings = self.param_shardings
        if self.spec.zero >= 3:
            shardings = jax.tree.map(
                lambda s, p: self._zero_extend(s, p.shape),
                shardings, params)
        return shardings

    def _opt_sharding(self, opt_state, params, param_shardings):
        """Shard optimizer slots structurally: optax state trees mirror the
        param treedef (Adam's mu/nu etc.), so any subtree of ``opt_state``
        whose structure equals the params' is given the corresponding
        param's sharding leaf-for-leaf — no shape-collision ambiguity.
        Leaves outside such subtrees (step counters, scalars) replicate."""
        param_def = jax.tree.structure(params)
        flat_params = jax.tree.leaves(params)
        flat_shards = jax.tree.leaves(
            param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        replicated = NamedSharding(self.mesh, P())
        # Fallbacks for leaves inside states that do not mirror the param
        # treedef exactly (optax.masked / multi_transform insert
        # placeholder nodes): first match the leaf's tree PATH against a
        # param path suffix (state trees nest the param tree under
        # wrapper keys like inner_state/mu, so param names survive in the
        # path); only then fall back to shape — and NEVER guess between
        # same-shape params with different shardings: ambiguous shapes
        # replicate (correct via resharding, predictable placement).
        pp = jax.tree_util.tree_flatten_with_path(params)[0]
        param_paths = []
        for (path, p), s in zip(pp, flat_shards):
            keys = tuple(str(getattr(k, 'key', getattr(k, 'idx', k)))
                         for k in path)
            param_paths.append((keys, tuple(p.shape), s))
        by_shape = {}
        for p, s in zip(flat_params, flat_shards):
            by_shape.setdefault(tuple(p.shape), set()).add(s)

        def mirrors_params(node):
            try:
                return jax.tree.structure(node) == param_def
            except Exception:
                return False

        def place_leaf(path_keys, node):
            shape = tuple(getattr(node, 'shape', ()))
            # path-suffix match: unique param whose full path ends the
            # state leaf's path (and whose shape agrees)
            cands = [s for keys, pshape, s in param_paths
                     if pshape == shape and len(path_keys) >= len(keys)
                     and path_keys[-len(keys):] == keys]
            if len(set(cands)) == 1:
                sh = cands[0]
            else:
                shs = by_shape.get(shape, set())
                if len(shs) != 1:
                    if len(shs) > 1:
                        logging.debug(
                            'optimizer leaf %s: shape %s matches params '
                            'with differing shardings; replicating',
                            '/'.join(path_keys), shape)
                    return replicated
                sh = next(iter(shs))
            if self.spec.zero >= 2:
                return self._zero_extend(sh, node.shape)
            return sh

        def place(path, node):
            if mirrors_params(node):
                leaves = jax.tree.leaves(node)
                placed = []
                for leaf, p, sh in zip(leaves, flat_params, flat_shards):
                    if tuple(getattr(leaf, 'shape', ())) != tuple(p.shape):
                        placed.append(replicated)  # e.g. scalar count
                    elif self.spec.zero >= 2:
                        placed.append(self._zero_extend(sh, leaf.shape))
                    else:
                        placed.append(sh)
                return jax.tree.unflatten(param_def, placed)
            keys = tuple(str(getattr(k, 'key', getattr(k, 'idx', k)))
                         for k in path)
            return place_leaf(keys, node)

        return jax.tree_util.tree_map_with_path(
            place, opt_state, is_leaf=mirrors_params)

    def batch_sharding(self, batch):
        """Leading dim over data; dim 1 over seq for rank>=2 leaves when
        sequence parallelism is on."""
        def leaf_sharding(x):
            nd = getattr(x, 'ndim', 0)
            if nd == 0:
                return NamedSharding(self.mesh, P())
            if nd >= 2 and self.spec.sp > 1:
                return NamedSharding(self.mesh, P(AXIS_DATA, AXIS_SEQUENCE))
            return NamedSharding(self.mesh, P(AXIS_DATA))
        return jax.tree.map(leaf_sharding, batch)

    def shard_batch(self, batch):
        """Host batch -> sharded device arrays (remapper feed equivalent)."""
        return jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s),
            batch, self.batch_sharding(batch))

    # -- init --------------------------------------------------------------
    def init(self, rng, params=None):
        """Materialize sharded TrainState (params + optimizer slots)."""
        # every call here dispatches and returns: what the device still
        # has in flight when a span closes is not in it
        with self._span('trainer.init', setup=True):
            with self._span('trainer.init.params'):
                if params is None:
                    with sharding_ctx(self.mesh, self.rules):
                        shapes = jax.eval_shape(self.model.init, rng)
                        shardings = self._param_sharding_tree(shapes)
                        init_fn = jax.jit(self.model.init,
                                          out_shardings=shardings)
                        params = init_fn(rng)
                else:
                    params = jax.tree.map(
                        lambda x, s: jax.device_put(jnp.asarray(x), s),
                        params, self._param_sharding_tree(params))
            with self._span('trainer.init.opt_state'):
                opt_state = jax.jit(self.optimizer.init)(params)
                opt_shardings = self._opt_sharding(
                    opt_state, params, self._param_sharding_tree(params))
            with self._span('trainer.init.place',
                            leaves=len(jax.tree.leaves(opt_state))):
                opt_state = jax.tree.map(
                    lambda x, s: jax.device_put(x, s), opt_state,
                    opt_shardings)
            return TrainState.create(params, opt_state)

    # -- the compiled step -------------------------------------------------
    @property
    def manual_axes(self):
        """Mesh axes the step runs manually (inside shard_map): pipeline
        (GPipe ppermute schedule) and sequence (ring attention)."""
        axes = []
        if self.spec.pp > 1:
            axes.append(AXIS_PIPELINE)
        if self.spec.sp > 1:
            axes.append(AXIS_SEQUENCE)
        return tuple(axes)

    def loss_for(self, params, batch):
        if self.manual_axes:
            return self._manual_loss(params, batch)
        if self._loss_fn is not None:
            return self._loss_fn(params, batch)
        return self.model.loss(params, batch)

    def _manual_spec(self, axes):
        """A param's in_spec for the manual region: its full spec with
        non-manual (still-automatic) mesh axes stripped."""
        full = spec_for_axes(axes, self.rules, self.mesh)
        manual = self.manual_axes
        kept = [a if a in manual else None for a in full]
        while kept and kept[-1] is None:
            kept.pop()
        return P(*kept)

    def _manual_loss(self, params, batch):
        """Sequence/pipeline-parallel loss: the model runs inside a
        partial-manual shard_map (ring attention over ``seq``, GPipe over
        ``pipe``); per-token losses reduce outside."""
        model = self.model
        rules = self.rules
        mesh = self.mesh
        manual = self.manual_axes
        options = {'microbatches': self.spec.microbatches,
                   'pp_schedule': getattr(self.spec, 'pp_schedule',
                                          'gpipe'),
                   'pp_variant': getattr(self.spec, 'pp_variant',
                                         'auto'),
                   'sp_mode': getattr(self.spec, 'sp_mode', 'ring')}

        def per_token(params, batch):
            with sharding_ctx(mesh, rules, manual_axes=manual,
                              options=options):
                if hasattr(model, 'per_token_loss_with_aux'):
                    nll, aux = model.per_token_loss_with_aux(params, batch)
                else:
                    nll = model.per_token_loss(params, batch)
                    aux = jnp.zeros((), jnp.float32)
                # aux (e.g. MoE balance) is computed per manual shard;
                # average to one well-defined replicated value
                for ax in manual:
                    aux = jax.lax.pmean(aux, ax)
                return nll, aux

        param_specs = jax.tree.map(
            self._manual_spec, self._axes_tree,
            is_leaf=lambda x: x is None or (
                isinstance(x, tuple) and
                all(isinstance(a, (str, type(None))) for a in x)))
        sp_on = AXIS_SEQUENCE in manual
        batch_spec = P(None, AXIS_SEQUENCE) if sp_on else P()
        from autodist_tpu.parallel.axes import shard_map
        mapped = shard_map(
            per_token, self.mesh,
            (param_specs, batch_spec),
            (P(None, AXIS_SEQUENCE) if sp_on else P(), P()),
            axis_names=set(manual))
        nll, aux = mapped(params, batch)
        mask = batch.get('mask') if hasattr(batch, 'get') else None
        if mask is not None:
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
        else:
            ce = jnp.mean(nll)
        return ce + getattr(self.model, 'aux_loss_weight', 0.0) * aux

    def _build_step(self, batch_struct):
        accum = max(1, int(getattr(self.spec, 'grad_accum', 1)))

        def grads_of(params, batch):
            """(loss, grads, state_updates, counters) — updates is {} for
            stateless models, counters what the model counted in this
            step (``core.record_counter``; mostly {})."""
            from autodist_tpu.models.core import model_mode

            def loss_fn(p):
                with sharding_ctx(self.mesh, self.rules):
                    with model_mode(training=True) as mm:
                        loss = self.loss_for(p, batch)
                    return loss, (dict(mm.updates), dict(mm.counters))
            if self.spec.remat == 'full':
                loss_fn = jax.checkpoint(loss_fn)
            (loss, (updates, counters)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, grads, updates, counters

        def apply_updates(params, opt_updates, state_updates):
            from autodist_tpu.models.core import apply_tree_updates
            if not self._has_state:
                return jax.tree.map(
                    lambda p, u: p + u.astype(p.dtype),
                    params, opt_updates)
            # static bool mask: state leaves skip the optimizer entirely
            # (weight decay etc. must not touch running statistics) and
            # take their recorded updates instead
            new_params = jax.tree.map(
                lambda p, u, m: (p + u.astype(p.dtype)) if m else p,
                params, opt_updates, self._trainable_mask)
            return apply_tree_updates(new_params, state_updates)

        def step_fn(state, batch):
            if accum > 1:
                # split the leading (batch) dim into `accum` chunks and
                # scan, averaging loss and grads — exact parity with the
                # single-pass mean for equal chunks, at 1/accum the
                # activation memory
                def _chunk(x):
                    if x.shape[0] % accum:
                        raise ValueError(
                            'grad_accum=%d does not divide batch dim %d'
                            % (accum, x.shape[0]))
                    return x.reshape((accum, x.shape[0] // accum)
                                     + x.shape[1:])

                chunked = jax.tree.map(_chunk, batch)

                def body(acc, chunk):
                    loss_c, grads_c, upd_c, _ = grads_of(state.params, chunk)
                    acc_loss, acc_grads, _ = acc
                    # state (BN EMA) keeps the LAST chunk's update: each
                    # chunk computes its EMA from the pre-step state, so
                    # the running stats advance once per optimizer step
                    # (semantics + tf.layers delta documented in
                    # docs/usage/parallelism.md "Gradient accumulation
                    # and BatchNorm statistics")
                    return (acc_loss + loss_c,
                            jax.tree.map(jnp.add, acc_grads, grads_c),
                            upd_c), None

                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32),
                            state.params),
                        self._initial_state_updates(state.params))
                (loss, grads, state_updates), _ = jax.lax.scan(
                    body, zero, chunked)
                loss = loss / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
                counters = {}   # (a chunk's cannot leave the scan)
            else:
                loss, grads, state_updates, counters = grads_of(
                    state.params, batch)
            with jax.named_scope('optimizer'):
                updates, new_opt = self.optimizer.update(
                    grads, state.opt_state, state.params)
                new_params = apply_updates(state.params, updates,
                                           state_updates)
            return TrainState(params=new_params, opt_state=new_opt,
                              step=state.step + 1), dict(counters, loss=loss)

        return step_fn

    def _initial_state_updates(self, params):
        """Scan carry skeleton for state updates: current values of the
        non-trainable leaves (so chunk 1's replacement has a matching
        structure)."""
        if not self._has_state:
            return {}
        out = {}
        for path in self._state_paths:
            node = params
            for key in path:
                node = node[key]
            out[path] = node
        return out

    def _step_key(self, batch):
        struct = jax.tree.structure(batch)
        shapes = tuple((tuple(np.shape(x)), np.asarray(x).dtype.str
                        if not hasattr(x, 'dtype') else str(x.dtype))
                       for x in jax.tree.leaves(batch))
        return (struct, shapes)

    def _ensure_step(self, key, state, batch):
        if key not in self._step_cache:
            # which step met a batch signature with no compiled step yet
            telemetry.get().loop_event(
                'trainer.new_step_signature', step=self._steps_run + 1,
                trainer=self._tag, shapes=str(key[1]))
            step_fn = self._build_step(jax.tree.structure(batch))
            param_sh = self._param_sharding_tree(state.params)
            opt_sh = self._opt_sharding(state.opt_state, state.params,
                                        param_sh)
            state_sh = TrainState(params=param_sh, opt_state=opt_sh,
                                  step=NamedSharding(self.mesh, P()))
            self._step_cache[key] = jax.jit(
                step_fn,
                in_shardings=(state_sh, self.batch_sharding(batch)),
                out_shardings=(state_sh, None),
                donate_argnums=(0,) if self._donate else ())
        return self._step_cache[key]

    def compile_step(self, state, batch):
        """AOT-compile the step for this batch signature, ONCE, and make
        subsequent ``step`` calls with the same signature reuse the same
        executable. Returns the ``jax.stages.Compiled`` (whose
        ``as_text()`` the benchmark's engine and ``chip_smoke.py``
        read)."""
        with self._span('trainer.compile_step', step=self._steps_run + 1,
                        setup=True):
            with self._span('trainer.compile_step.build'):
                key = self._step_key(batch)
                fn = self._ensure_step(key, state, batch)
            if isinstance(fn, jax.stages.Compiled):
                return fn
            with self._span('trainer.compile_step.place'):
                batch = self.shard_batch(batch)
            # Python tracing and lowering to StableHLO, the Mosaic
            # payloads with it
            with self._span('trainer.compile_step.lower'):
                lowered = fn.lower(state, batch)
            # XLA's compile, or the persistent cache's look-up, read
            # and load
            with self._span('trainer.compile_step.compile'):
                compiled = lowered.compile()
            self._step_cache[key] = compiled
            return compiled

    def step(self, state, batch):
        """One optimizer step; returns (new_state, metrics)."""
        with self._span('trainer.step', step=self._steps_run + 1):
            key = self._step_key(batch)
            fn = self._ensure_step(key, state, batch)
            batch = self.shard_batch(batch)
            out = fn(state, batch)
        self._steps_run += 1
        return out

    # -- fit/evaluate conveniences (reference case c7's Model.fit role) ----
    def fit(self, state, data, steps=None, eval_data=None, eval_every=0,
            checkpoint_manager=None, save_every=0, prefetch=0):
        """Train over an iterable of batches (c7 ``Model.fit`` role).

        Args:
            state: TrainState from :meth:`init`.
            data: iterable (or iterator) of batch dicts.
            prefetch: keep this many device-placed batches in flight so
                host->device transfer overlaps compute (0 = off). Safe
                with :meth:`step`: already-placed arrays pass through
                its ``shard_batch`` untouched. NB with ``steps=N`` the
                prefetcher reads up to ``prefetch`` batches PAST the
                N-th from ``data`` — don't share one live iterator
                across fit() phases with prefetch on.
            steps: stop after this many steps (None = exhaust ``data``).
            eval_data: optional sequence of eval batches.
            eval_every: run :meth:`evaluate` every N steps (0 = only at
                the end when ``eval_data`` is given).
            checkpoint_manager: optional CheckpointManager; the FULL
                state (params + optimizer slots + step) is saved every
                ``save_every`` steps and at the end, enabling exact
                resume via :meth:`restore_state`.
            save_every: checkpoint cadence (0 = only at the end).

        Returns:
            (state, history) where history is a dict with 'loss' (one
            entry per step) and, when evaluating, 'eval_loss' entries of
            (step, loss).
        """
        history = {'loss': []}
        if eval_data is not None:
            history['eval_loss'] = []
        n = 0

        # every span is tagged with the number of the step it belongs
        # to: calls of step() so far, plus one before the call
        def evaluate():
            with self._span('trainer.eval', step=self._steps_run):
                history['eval_loss'].append(
                    (n, self.evaluate(state, eval_data)))

        def save():
            with self._span('trainer.save', step=self._steps_run):
                self.save_state(checkpoint_manager, state)

        with self._span('trainer.fit', step=self._steps_run + 1,
                        steps=steps, prefetch=prefetch):
            if prefetch:
                from autodist_tpu.data.prefetch import prefetch_to_device
                data = prefetch_to_device(data, self.shard_batch,
                                          size=prefetch,
                                          first_step=self._steps_run + 1,
                                          trainer=self._tag)
            it = iter(data)
            done = object()
            while True:
                with self._span('trainer.input', step=self._steps_run + 1):
                    batch = next(it, done)
                if batch is done:
                    break
                state, metrics = self.step(state, batch)
                with self._span('trainer.loss_readback',
                                step=self._steps_run):
                    history['loss'].append(float(metrics['loss']))
                if len(metrics) > 1:
                    # what the model counted in the step, read back
                    # after the loss (docs/design/observability.md)
                    with self._span('trainer.counters_readback',
                                    step=self._steps_run):
                        counted = {k: float(v) for k, v in metrics.items()
                                   if k != 'loss'}
                    telemetry.get().loop_event(
                        'trainer.counters', step=self._steps_run,
                        trainer=self._tag, **counted)
                n += 1
                if eval_data is not None and eval_every and \
                        n % eval_every == 0:
                    evaluate()
                if checkpoint_manager is not None and save_every and \
                        n % save_every == 0:
                    save()
                if steps is not None and n >= steps:
                    break
            if eval_data is not None and (not eval_every or
                                          n % eval_every):
                evaluate()
            if checkpoint_manager is not None and (not save_every or
                                                   n % save_every):
                save()
            if checkpoint_manager is not None and \
                    hasattr(checkpoint_manager, 'wait_until_finished'):
                checkpoint_manager.wait_until_finished()  # drain async save
        return state, history

    def evaluate(self, state, batches, metrics_fn=None):
        """Mean loss over batches without updating state (c7
        ``Model.evaluate`` role).

        With ``metrics_fn(params, batch) -> {name: scalar}`` (e.g. an
        accuracy), returns ``{'loss': ..., **means of metrics}``
        instead of the bare loss. Pass a STABLE function object — the
        compiled evaluator is cached per (batch signature, metrics_fn),
        so a fresh lambda per call recompiles (the cache is bounded, so
        this leaks time, not memory).
        """
        if not hasattr(self, '_eval_cache'):
            self._eval_cache = {}
        if len(self._eval_cache) > 16:   # bound churn from unstable fns
            self._eval_cache.clear()
        totals, count = {}, 0
        for batch in batches:
            # key by the metrics_fn itself: different fns with the same
            # batch signature must not share a compiled evaluator
            key = (self._step_key(batch), metrics_fn)

            if key not in self._eval_cache:
                def eval_fn(params, batch):
                    # same sharding context as step: constrain() hints
                    # and sharding-aware module paths (e.g. the sharded
                    # embedding lookup) stay active during eval; eval
                    # mode makes BatchNorm use its running statistics
                    from autodist_tpu.models.core import model_mode
                    with sharding_ctx(self.mesh, self.rules), \
                            model_mode(training=False):
                        out = {'loss': self.loss_for(params, batch)}
                        if metrics_fn is not None:
                            out.update(metrics_fn(params, batch))
                    return out
                self._eval_cache[key] = jax.jit(eval_fn)
            batch = self.shard_batch(batch)
            for name, val in self._eval_cache[key](state.params,
                                                   batch).items():
                totals[name] = totals.get(name, 0.0) + float(val)
            count += 1
        means = {name: val / max(count, 1)
                 for name, val in totals.items()}
        return means if metrics_fn is not None else means.get('loss', 0.0)

    # -- checkpoint/resume of the FULL training state ----------------------
    def state_sharding(self, state):
        """TrainState of NamedShardings matching how ``step`` places
        this state on the mesh."""
        param_sh = self._param_sharding_tree(state.params)
        opt_sh = self._opt_sharding(state.opt_state, state.params,
                                    param_sh)
        return TrainState(params=param_sh, opt_state=opt_sh,
                          step=NamedSharding(self.mesh, P()))

    def save_state(self, manager, state):
        """Checkpoint params + optimizer state + step for exact resume
        (the reference's saver covers variables only; optimizer slots
        ride along here so training continues bit-for-bit).

        Multi-host: the orbax backend receives the live (sharded) arrays
        and writes per-host shards itself; the npy backend gathers
        non-addressable leaves across processes first.
        """
        step = int(jax.device_get(state.step))
        if getattr(manager, 'backend', 'npy') == 'orbax':
            return manager.save(step, state)

        def to_host(x):
            if hasattr(x, 'is_fully_addressable') and \
                    not x.is_fully_addressable:
                from jax.experimental import multihost_utils
                x = multihost_utils.process_allgather(x, tiled=True)
            return np.asarray(jax.device_get(x))
        host = jax.tree.map(to_host, state)   # collective: all processes
        if jax.process_count() > 1 and jax.process_index() != 0:
            return None   # one writer for the self-contained npy layout
        return manager.save(step, host)

    def restore_state(self, manager, state_template, step=None):
        """Restore a :meth:`save_state` checkpoint onto this trainer's
        mesh (any mesh — the files are logical layout). Returns
        ``state_template`` unchanged when no checkpoint exists."""
        # shape/dtype skeleton, not device_get: the template may span
        # non-addressable devices in multi-host runs
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           getattr(x, 'dtype',
                                                   jnp.float32)),
            state_template)
        tree, got_step = manager.restore(like=like, step=step)
        if tree is None:
            return state_template, None
        shardings = self.state_sharding(state_template)
        state = jax.tree.map(
            lambda x, sh: jax.device_put(jnp.asarray(x), sh),
            tree, shardings)
        return state, got_step

    # -- profiling (session path has RunOptions; this is the Trainer's) ----
    def profile(self, state, batch, trace_dir, steps=3):
        """Capture a ``jax.profiler`` trace (TensorBoard/Perfetto) of
        ``steps`` compiled training steps — the functional-path analogue
        of the session's ``RunOptions(trace_level=...)`` (reference
        chrome-trace timelines, runner.py:64-75). Returns ``trace_dir``;
        the traced steps' state updates are DISCARDED (profiling must
        not perturb training).

        The steps go through :meth:`step`, so the trace carries the
        ``trainer.step`` spans on its host plane and the model's named
        scopes in its device operations; the Python tracer is off (it
        records every call of the host loop, which slows the loop and
        makes the trace hundreds of megabytes)."""
        import os
        self.compile_step(state, batch)
        placed = self.shard_batch(batch)
        # profile a COPY when the step donates its input state (the
        # default): donating the caller's state would invalidate their
        # buffers. Without donation the copy would only waste HBM.
        s = jax.tree.map(jnp.copy, state) if self._donate else state
        s, m = self.step(s, placed)    # warmup outside the trace
        jax.block_until_ready(m['loss'])
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(steps):
                s, m = self.step(s, placed)
            jax.block_until_ready(m['loss'])
        finally:
            jax.profiler.stop_trace()
        logging.info('Profiler trace (%d steps) written to %s',
                     steps, trace_dir)
        return trace_dir

    # -- fetch helpers (reference get-variable parity) ---------------------
    def get_params(self, state):
        """Gather params to host in logical (unsharded) layout."""
        return jax.tree.map(np.asarray, jax.device_get(state.params))
