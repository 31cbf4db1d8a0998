"""Host->device prefetch: the paper's out-of-core streaming, JAX-style.

cuMF (§4.4 'Out-of-core computation') plans partitions ahead of time, then
uses CPU threads + CUDA streams to preload the next q-batch while the
current one computes, hiding load time "except for the first load".  The
JAX equivalent: a background thread calls ``jax.device_put`` (async on TPU)
``depth`` batches ahead; dispatching the next step's computation overlaps
its transfer with the current step's compute.

Lifecycle: a consumer that abandons iteration early (break, exception, a
wave driver resuming past the end of a half) must call ``close()`` — or use
the prefetcher as a context manager — otherwise the worker thread would sit
blocked forever on a full queue.  ``close()`` wakes a blocked worker, drains
the queue, and joins the thread; it is idempotent and safe after normal
exhaustion.

Observability (``tracer=`` / ``registry=``): the worker thread records two
spans per item on its own named thread track: one ``prefetch_slice`` span
around the ``next()`` of the source iterator (the host slicing and layout
work of the caller's generator) and one ``prefetch_load`` span around the
``put`` transform (the counters and host->device uploads); the consumer
records one ``prefetch`` span per ``__next__`` around the queue wait — the
time compute actually stalled on streaming.  A well-hidden pipeline shows
long ``prefetch_slice``/``prefetch_load`` spans and near-zero ``prefetch``
spans; the inverse means the budget or depth is wrong.  The registry
additionally counts ``prefetch/items`` and samples queue depth at each
hand-off.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator, Optional

import jax

from repro.obs.trace import phase

_POLL_S = 0.05


class Prefetcher:
    def __init__(self, it: Iterator, *, depth: int = 2,
                 put: Optional[Callable] = None,
                 tracer=None, registry=None):
        self._it = it
        self._put = put or (lambda x: jax.tree.map(jax.device_put, x))
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._done = object()
        self._stop = threading.Event()
        self._tracer = tracer
        self._registry = registry
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="prefetch-worker")
        self._thread.start()

    def _offer(self, item) -> bool:
        """put() that a concurrent close() can interrupt; False if stopped."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            it = iter(self._it)
            while True:
                try:
                    with phase("prefetch.slice", cat="prefetch_slice",
                               tracer=self._tracer, registry=self._registry):
                        item = next(it)           # the generator's host work
                except StopIteration:
                    break
                if self._stop.is_set():
                    return
                with phase("prefetch.load", cat="prefetch_load",
                           tracer=self._tracer, registry=self._registry):
                    loaded = self._put(item)      # device_put is async: the
                if not self._offer(loaded):       # transfer runs while
                    return                        # compute proceeds on
        except BaseException as e:                # earlier batches
            self._offer(e)
            return
        self._offer(self._done)

    def close(self):
        """Stop the worker, drain queued items, join the thread (idempotent)."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._q.get_nowait()     # unblock a worker stuck in _offer
            except queue.Empty:
                pass
            self._thread.join(timeout=_POLL_S)

    @property
    def closed(self) -> bool:
        return self._stop.is_set() and not self._thread.is_alive()

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        with phase("prefetch.wait", cat="prefetch",
                   tracer=self._tracer, registry=self._registry):
            item = self._q.get()
        if self._registry is not None:
            self._registry.gauge("prefetch/queue_depth").set(
                self._q.qsize())
            if not (item is self._done or isinstance(item, BaseException)):
                self._registry.counter("prefetch/items").inc()
        if item is self._done:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item
