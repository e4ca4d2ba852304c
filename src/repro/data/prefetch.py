"""Async host->device prefetch for the federated round loop.

The round step is a single pjit'd function, so the host is idle while
the device runs a round — and the device is idle while the host packs
the next round batch and transfers it. ``PrefetchIterator`` overlaps
the two with a background thread and a small bounded buffer
(double-buffering by default): the worker packs round r+1 (and
``jax.device_put``s it) while the device crunches round r.

One worker thread keeps the sampler's RNG stream strictly ordered, so
prefetched runs are bit-identical to serial runs.

Host spans (``repro.profile.trace.span``): ``repro.prefetch.put``
around the worker's host-to-device copy, ``repro.prefetch.wait``
around the consumer's blocking wait for the next item.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

import jax

from repro.profile.trace import span

_END = object()


class PrefetchIterator:
    """Iterate ``source`` with a background worker and a depth-bounded
    buffer; optionally ``jax.device_put`` each item on the worker thread
    so device transfer also overlaps compute.

    ``sharding`` (a ``jax.sharding.Sharding``) routes the worker-thread
    transfer straight to the target placement — for mesh-sharded rounds
    each item lands pre-split over the ``clients`` axis, so the round
    step starts without a host-side gather/reshard stall.

    Use as a context manager (or call ``close()``) to guarantee the
    worker is torn down when the consumer stops early.
    """

    def __init__(
        self,
        source: Iterable[Any],
        depth: int = 2,
        device_put: bool = True,
        transform: Optional[Callable[[Any], Any]] = None,
        sharding: Optional[Any] = None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._done = False
        self._transform = transform
        self._device_put = device_put or sharding is not None
        self._sharding = sharding
        self._thread = threading.Thread(
            target=self._worker, args=(iter(source),), daemon=True,
            name="repro-prefetch")
        self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that aborts when close() is requested."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, it: Iterator[Any]) -> None:
        try:
            for item in it:
                if self._stop.is_set():
                    return
                if self._transform is not None:
                    item = self._transform(item)
                if self._device_put:
                    with span("prefetch.put"):
                        if self._sharding is not None:
                            item = jax.device_put(item, self._sharding)
                        else:
                            item = jax.device_put(item)
                if not self._put(item):
                    return
        except BaseException as e:  # surfaced on the consumer thread
            self._error = e
        finally:
            self._put(_END)

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with span("prefetch.wait"):
            return self._next()

    def _next(self):
        while True:
            try:
                item = self._queue.get(timeout=0.5)
            except queue.Empty:
                if not self._thread.is_alive():
                    # worker died without posting the sentinel
                    self._done = True
                    if self._error is not None:
                        raise self._error
                    raise StopIteration
                continue
            if item is _END:
                self._done = True
                if self._error is not None:
                    raise self._error
                raise StopIteration
            return item

    def close(self) -> None:
        """Stop the worker and release the buffer. Idempotent."""
        self._stop.set()
        # drain so a blocked worker can observe the stop event
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        self._done = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def round_batches(sampler, rounds: int) -> Iterator[dict]:
    """Host-side round batch stream in the engine's input layout."""
    for _ in range(rounds):
        yield sampler.next_round().engine_batch()
