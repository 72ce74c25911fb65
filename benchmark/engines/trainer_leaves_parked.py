"""Engine adapter ``trainer_leaves_parked``: the ``trainer_leaves``
engine for a cell whose training state leaves no room for the comparison
beside it: the optimizer's slots wait on the HOST while the probe and the
family's reference run, and :meth:`Engine.fit` brings them back.

Why a cell would want that: the comparison that decides ``correct`` needs,
beside the parameters, a second copy of them and a gradient for the probe
(``trainer.py``), then the reference's parameters in the published order
and its gradient in f32. With AdamW's two slots on the chip that is five
to six times the parameters; a cell whose parameters are 3 GB of a 16 GB
chip cannot hold it. The slots take no part in either side of the
comparison (the probe steps by SGD from the parameters alone), so they are
copied to host memory bit for bit, their device buffers freed, and put
back on the same shardings before the first training step: the step that
is timed runs on the state ``init`` made, every slot as it was.

``harness.py`` calls ``loss_and_grad_norm(state, ...)``, then the
family's reference, then ``fit(state, ...)`` with the SAME ``state``
object; between the first and the last its slots are not on the device,
and nothing reads them there.
"""
import dataclasses

from benchmark.engines import trainer_leaves


class Engine(trainer_leaves.Engine):
    def __init__(self, model, parallel, devices):
        super().__init__(model, parallel, devices)
        self._parked = None    # (host slots, their shardings)

    def loss_and_grad_norm(self, state, batch):
        import jax
        import numpy as np
        if self._parked is None:
            slots = state.opt_state
            # (read through a copy of each leaf: an Array keeps the host
            # value it was read as, and ``state``'s own would hold the 6 GB
            # until the caller lets ``state`` go: ``restored`` says when)
            self._parked = (jax.tree.map(
                lambda a: np.asarray(jax.numpy.copy(a)), slots),
                            jax.tree.map(lambda a: a.sharding, slots))
            for leaf in jax.tree.leaves(slots):
                leaf.delete()
        return super().loss_and_grad_norm(state, batch)

    def restored(self, state):
        """``state`` with the parked slots back on their shardings (itself
        where nothing is parked), and ON the device when this returns.

        ``harness.py`` sizes the measured window by the last warm-up
        step's time, taken on the host from the request that opens the
        step to the moment ``fit`` has returned to it. In this cell that
        read 0.66-0.74 s for a step of 0.44, and the window had 27-29
        steps for 45 (my chip runs, PR 48): 0.3 s lay between this
        engine's ``fit`` returning and the caller having its result, which
        is where the caller lets its OLD ``state`` go. Its slots' Arrays,
        deleted on the device, still held the host values they had been
        parked as (an Array keeps what ``np.asarray`` read), and giving
        6 GB back to the system takes that long. Now the parked copies
        are read through a copy of each leaf, nothing of ``state`` holds
        them, and they are freed HERE, when the slots are back and before
        the warm-up's clock starts. (Slots made again on the device and
        never copied read 0.443 s, my chip run; this route is NOT measured
        on the chip: PERF.md section 6.)"""
        import jax
        if self._parked is None:
            return state
        host, shardings = self._parked
        self._parked = None
        state = dataclasses.replace(state, opt_state=jax.block_until_ready(
            jax.tree.map(jax.device_put, host, shardings)))
        del host
        return state

    def fit(self, state, data, steps):
        return super().fit(self.restored(state), data, steps)
