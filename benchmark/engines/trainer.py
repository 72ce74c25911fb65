"""Engine adapter ``trainer``: the program's functional ``Trainer``,
through its public entry points only (``Trainer``, ``ParallelSpec``,
``init``, ``compile_step``, ``fit``, ``step``).

A later engine (the session engine, the strategy adapter) is another
file here with the same five methods; a cell names its engine.
"""


class Engine:
    def __init__(self, model, parallel, devices):
        import optax

        from autodist_tpu.api import Trainer
        from autodist_tpu.parallel.axes import ParallelSpec
        self._model = model
        self._spec = ParallelSpec(**parallel)
        self._mesh = self._spec.build_mesh(devices=devices)
        self.trainer = Trainer(model, optax.adamw(1e-4), spec=self._spec,
                               mesh=self._mesh)
        self._probe = None

    @property
    def devices(self):
        return list(self._mesh.devices.flat)

    def init(self, seed):
        """Weights and optimizer slots, made on the device from the seed."""
        import jax
        return self.trainer.init(jax.random.PRNGKey(seed))

    def compile(self, state, batch):
        """The step for this batch shape and no other, as a
        ``jax.stages.Compiled``; ``fit`` then reuses it."""
        return self.trainer.compile_step(state, batch)

    def fit(self, state, data, steps):
        """The user's loop. Returns (state, per-step losses)."""
        state, history = self.trainer.fit(state, data, steps=steps,
                                          prefetch=2)
        return state, history['loss']

    def loss_and_grad_norm(self, state, batch):
        """The program's loss and global gradient norm on ``batch`` at
        ``state``'s parameters, through the step itself: one SGD(1.0)
        step of a second, non-donating Trainer moves every parameter by
        exactly minus its gradient, so the gradient is read back as
        ``old - new`` with kernels, remat and shardings as in training.
        ``state`` is left as it was."""
        import jax
        import jax.numpy as jnp
        import optax

        from autodist_tpu.api import Trainer
        if self._probe is None:
            self._probe = Trainer(self._model, optax.sgd(1.0),
                                  spec=self._spec, mesh=self._mesh,
                                  donate=False)
        before = self._probe.init(None, params=state.params)
        after, metrics = self._probe.step(before, batch)

        @jax.jit
        def norm(old, new):
            return jnp.sqrt(sum(jnp.sum(jnp.square(o - n)) for o, n in zip(
                jax.tree.leaves(old), jax.tree.leaves(new))))
        return float(metrics['loss']), float(norm(before.params,
                                                  after.params))

    def params_span_mesh(self, state):
        """Every parameter leaf's sharding covers every device of the
        mesh (nothing sits on device 0 alone)."""
        import jax
        mesh_devices = set(self._mesh.devices.flat)
        return all(set(leaf.sharding.device_set) == mesh_devices
                   for leaf in jax.tree.leaves(state.params))
