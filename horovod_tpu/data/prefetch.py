"""Background host→device prefetch.

`jax.device_put` blocks the calling thread for the transfer enqueue (not
measured on this round's chip) even though the transfer itself is
asynchronous — so a training loop that stages its own
batches serializes transfer enqueue with step dispatch. A `DevicePrefetcher`
moves the staging onto a daemon thread feeding a small queue of
already-device-resident batches: while step k computes, batch k+1 is being
transferred. This is the framework's equivalent of the input-side overlap the
reference gets from tf.data's prefetch + Horovod's background threads.

Composes with the native batch-assembly engine (`native_loader`): the host
iterator it wraps may itself be the C++ producer, giving a two-stage
pipeline: C++ assembles batch bytes → this thread stages them on device →
the main thread only dispatches compiled steps.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator

from horovod_tpu import trace


class DevicePrefetcher:
    """Iterate device-resident items staged ahead by a background thread.

    Args:
      host_iter: yields host-side items (e.g. numpy batch tuples).
      put: host item -> device item (e.g. `trainer._shard`); runs on the
        background thread.
      depth: max staged items. 2 = classic double buffering; more only helps
        when production is bursty.

    Exceptions raised by `host_iter` or `put` re-raise in the consumer at the
    matching `__next__` call. Always `close()` (or exhaust) so the thread and
    its staged device buffers are released promptly.
    """

    _DONE = object()

    def __init__(self, host_iter: Iterator, put: Callable, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(host_iter, put), daemon=True
        )
        self._thread.start()

    def _enqueue(self, item) -> None:
        # Blocking put with a timeout so close() can't strand the producer
        # on a full queue nobody will ever drain.
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def _produce(self, host_iter, put):
        # Three spans a batch, on this thread's line of the profiler's
        # trace: the input engine's assembly (`next(host_iter)`: the C++
        # producer or the Python loader), the host→device staging, and the
        # wait for room in the queue — where a producer that is ahead of
        # the training loop spends its time.
        host_iter = iter(host_iter)
        try:
            while True:
                with trace.span("input.assemble"):
                    item = next(host_iter, self._DONE)
                if item is self._DONE or self._stop.is_set():
                    break
                with trace.span("input.place"):
                    item = put(item)
                with trace.span("input.queue_full"):
                    self._enqueue(item)
            if not self._stop.is_set():
                self._enqueue(self._DONE)
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            self._enqueue(e)
            # Then terminate the stream: a consumer that catches the error
            # and calls next() again must get StopIteration, not a hang.
            self._enqueue(self._DONE)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._DONE:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self) -> None:
        self._stop.set()
        # Drain so a blocked producer can observe the stop flag.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
