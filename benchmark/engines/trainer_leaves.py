"""Engine adapter ``trainer_leaves``: the ``trainer`` engine, whose probe
also KEEPS the gradient it read, leaf by leaf, for the family's
reference to compare with (:data:`PROBE`).

Why a cell would want that: ``harness.py`` compares two numbers a side,
the loss and the gradient's global norm. At a random initialisation
whose residual stream is mostly the embedding (Mellum2's cell draws its
rows at N(0, 8^2), ``configs/mellum2-12b-a2.5b.json``) both are the
embedding's and the head's: the layers move the loss in its sixth digit,
and a reference without the window, or with an expert's rows left out,
reads the same on both (PERF.md §6, PR 33). A family that finds the
program's gradient here holds EVERY leaf to a limit of its own and says
so in the norm it returns (``models/mellum2.py:held_to_every_leaf``);
``harness.py`` then compares what the two sides return as it does in
every cell.

The gradient is read back as in ``trainer`` (one SGD step of a second,
non-donating Trainer, ``old - new``), but at a learning rate of 2^20,
divided out again: at 1.0 a parameter of size 8 swallows a gradient of
1e-6 an element in its own f32 rounding (the embedding's leaf read 66%
off the reference's that way, my chip run, PR 33); a power of two scales
exactly.
"""
from benchmark.engines import trainer

READBACK_RATE = 2.0 ** 20

# What the last probe left: 'gradients', the program's gradient in the
# program's tree as host arrays (the chip is full of the training
# state; the reference needs what is left). The family takes it out.
PROBE = {}


class Engine(trainer.Engine):
    def loss_and_grad_norm(self, state, batch):
        import jax
        import numpy as np
        import optax

        from autodist_tpu.api import Trainer
        if self._probe is None:
            self._probe = Trainer(self._model, optax.sgd(READBACK_RATE),
                                  spec=self._spec, mesh=self._mesh,
                                  donate=False)
        before = self._probe.init(None, params=state.params)
        after, metrics = self._probe.step(before, batch)
        grads = jax.tree.map(
            lambda old, new: np.asarray(old - new) / np.float32(READBACK_RATE),
            before.params, after.params)
        del after
        PROBE['gradients'] = grads
        return float(metrics['loss']), float(np.sqrt(sum(
            np.sum(np.square(g), dtype=np.float64)
            for g in jax.tree.leaves(grads))))
