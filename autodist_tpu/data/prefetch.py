"""Device prefetch: overlap host->device transfer with device compute.

The reference overlaps input IO with compute through tf.data + the TF
runtime's prefetch ops; the native loader (native/dataloader.cc) covers
the host IO half here. This covers the device half: ``device_put`` is
asynchronous in JAX, so keeping ``size`` placed batches in flight means
the transfer of batch N+1 rides along while the step on batch N runs —
the jax idiom replacing tf.data's ``prefetch_to_device``.
"""
import collections
import itertools

from autodist_tpu import telemetry


def prefetch_to_device(iterator, place_fn, size=2, first_step=1, **tags):
    """Yield device-placed batches with ``size`` batches in flight.

    Args:
        iterator: iterable of host batches.
        place_fn: host batch -> device arrays (e.g.
            ``Trainer.shard_batch`` — async; must not block).
        size: number of placed batches to keep in flight (>= 1).
        first_step: number of the training step that consumes the
            first batch; batch ``i`` is fetched under a
            ``trainer.source`` and placed under a ``trainer.place``
            loop span (:meth:`Telemetry.loop_span`) tagged
            ``first_step + i``.
        **tags: further tags of those spans (``Trainer.fit`` gives its
            ``trainer``).

    Yields:
        placed batches, in order.
    """
    if size < 1:
        raise ValueError('prefetch size must be >= 1, got %d' % size)
    buf = collections.deque()
    it = iter(iterator)
    pending = []   # a source/placement error, deferred until buf drains
    tel = telemetry.get()
    steps = itertools.count(first_step)

    def fill():
        if pending:
            return False
        step = next(steps)
        try:
            with tel.loop_span('trainer.source', step=step, **tags):
                batch = next(it)
            with tel.loop_span('trainer.place', step=step, **tags):
                buf.append(place_fn(batch))
        except StopIteration:
            return False
        except Exception as e:   # noqa: BLE001 - re-raised after drain
            # don't drop the up-to-`size` good batches already placed:
            # surface the error only once they have been consumed
            pending.append(e)
            return False
        return True

    for _ in range(size):
        if not fill():
            break
    while buf:
        out = buf.popleft()
        fill()
        yield out
    if pending:
        raise pending[0]
