"""Shared ordered upload pipeline: overlap producer work with the consumer.

One mechanism, three users (SURVEY.md §7.3.4; the reference hides
host→device transfer behind compute with cuIO/UCX stream overlap):

- the legacy arrow scan path (decode → align → ``arrow_to_device`` per
  batch) runs its upload stage on a feeder thread ahead of the consumer;
- the device-decode parquet path runs blob assembly + ``device_put`` +
  fused-decode dispatch for row group N+1 on feeder thread(s) while the
  consumer computes on batch N;
- the host shuffle read side uploads partition file N+1 while the
  consumer computes on N.

``pipelined_map`` is the whole contract: results come back in
submission order, the in-flight window is bounded (a slot is released
only when the consumer RETRIEVES a result, so not-yet-consumed uploads
— i.e. device residency — are capped at ``window``), worker and source
exceptions surface at the consumer's corresponding ``next()``, and
closing the generator early never deadlocks a feeder stuck on a full
window. With ``weigher``/``max_weight`` the window is ALSO bounded in
item weight (decoded bytes for the scan): the widened decode envelope
feeds string blobs whose decoded size dwarfs a numeric row group's, so
a count-only window could pin several oversized batches in HBM at
once — the weight bound keeps the feeder from running ahead of the
consumer by more bytes than the budget allows (one over-weight item is
still admitted alone, so progress never stalls).
"""
from __future__ import annotations

import concurrent.futures
import queue
import threading
from typing import Callable, Iterable, Iterator, Optional, TypeVar

__all__ = ["pipelined_map"]

T = TypeVar("T")
R = TypeVar("R")

_END = "end"
_ERR = "err"
_FUT = "fut"


class _WeightedWindow:
    """Count + weight bounded admission: acquire blocks while the
    window holds ``window`` items OR ``max_weight`` total weight (a
    single item heavier than the whole budget admits alone — otherwise
    it could never run). ``close()`` unblocks a parked feeder.

    Lock order: ``_cv`` is level 30 in the declared hierarchy
    (analysis/locks.py::LOCK_HIERARCHY) — nothing else is ever
    acquired under it (``wait()`` releases it), and callers may hold
    only sub-30 locks when entering. tpu-lint's lock analysis and the
    runtime watchdog both enforce this."""

    def __init__(self, window: int, max_weight: Optional[int],
                 token=None):
        self._window = window
        self._max_weight = max_weight
        self._count = 0
        self._weight = 0
        self._closed = False
        self._cv = threading.Condition()
        # lifecycle.CancellationToken: a cancelled query's parked
        # feeder must not sit on a full window forever — acquire
        # becomes a cancellation point (checked on a bounded wait)
        self._token = token

    def acquire(self, weight: int = 0) -> None:
        with self._cv:
            while not self._closed and (
                    self._count >= self._window
                    or (self._max_weight is not None and self._count
                        and self._weight + weight > self._max_weight)):
                if self._token is not None \
                        and self._token.poll_local() is not None:
                    raise self._token.error()
                self._cv.wait(timeout=None if self._token is None
                              else 0.05)
            self._count += 1
            self._weight += weight

    def release(self, weight: int = 0) -> None:
        with self._cv:
            self._count -= 1
            self._weight -= weight
            self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


def pipelined_map(fn: Callable[[T], R], items: Iterable[T],
                  threads: int = 1, window: int = 2,
                  weigher: Optional[Callable[[T], int]] = None,
                  max_weight: Optional[int] = None,
                  token=None,
                  thread_name: str = "pipelined-map") -> Iterator[R]:
    """Yield ``fn(item)`` for each item, in order, with up to ``window``
    results in flight across ``threads`` worker threads.

    - ``threads <= 0`` or ``window <= 0`` degrades to the serial map
      (no threads, no overlap) — the kill-switch path.
    - The source iterator is advanced on a dedicated feeder thread, so
      a blocking source (e.g. a row-group planner waiting on its own
      pool) overlaps both the workers and the consumer.
    - An exception raised by ``fn`` is re-raised at the ``next()`` call
      that would have yielded that item's result; an exception raised
      by the source iterator is re-raised after every earlier result
      was delivered.
    - ``weigher(item)`` + ``max_weight`` additionally bound the summed
      weight of in-flight items (see module docstring); a weigher
      exception is a source exception.
    - ``close()`` (or GC) of the generator stops the feeder, cancels
      queued work, and returns without waiting for stragglers.
    - ``token`` (a lifecycle.CancellationToken) makes the window's
      admission gate AND the consumer loop cancellation points: a
      cancelled query's feeder stops feeding (even parked on a full
      window) and the consumer raises the classified QueryCancelled at
      its next ``next()``, early-draining in-flight work through the
      normal close path.
    - The workers run ``fn`` under the consumer's ``jax.default_device``
      where it has set one (the thread that first advances this
      generator).
    - ``thread_name`` prefixes the names of the worker threads and of
      the source's feeder (``<prefix>_<n>``, ``<prefix>-src``): a trace
      then says whose lines they are.
    """
    if threads <= 0 or window <= 0:
        for x in items:
            if token is not None:
                token.check()
            yield fn(x)
        return

    # a consumer pinned to a chip (a gang member: exec/gang.py) has its
    # workers upload and dispatch there too; jax's default device is the
    # calling thread's alone
    import jax
    device = jax.config.jax_default_device
    if device is not None:
        work = fn

        def fn(x):
            with jax.default_device(device):
                return work(x)

    out: "queue.Queue" = queue.Queue()
    slots = _WeightedWindow(window,
                            max_weight if weigher is not None else None,
                            token=token)
    stop = threading.Event()
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=threads, thread_name_prefix=thread_name)

    def feeder():
        try:
            for x in items:
                if stop.is_set():
                    return
                if token is not None:
                    token.check()  # stop feeding a cancelled query
                w = int(weigher(x)) if weigher is not None else 0
                slots.acquire(w)
                if stop.is_set():
                    return
                out.put((_FUT, (pool.submit(fn, x), w)))
            out.put((_END, None))
        except BaseException as e:  # source iterator failed/cancelled
            out.put((_ERR, e))

    th = threading.Thread(target=feeder, daemon=True,
                          name=thread_name + "-src")
    th.start()
    try:
        while True:
            if token is None:
                kind, val = out.get()
            else:
                # bounded waits so cancellation interrupts a consumer
                # blocked on a stalled producer
                while True:
                    token.check()
                    try:
                        kind, val = out.get(timeout=0.05)
                        break
                    except queue.Empty:
                        continue
            if kind == _END:
                return
            if kind == _ERR:
                raise val
            fut, w = val
            try:
                # tpu-lint: allow[blocking-call-in-thread] consumer side: must re-raise worker exceptions; bounded by the in-flight window + pool shutdown in finally
                result = fut.result()  # re-raises worker exceptions
            finally:
                slots.release(w)
            yield result
    finally:
        stop.set()
        slots.close()  # unblock a feeder parked on a full window
        while True:  # drop queued work so the pool can drain
            try:
                kind, val = out.get_nowait()
            except queue.Empty:
                break
            if kind == _FUT:
                val[0].cancel()
        pool.shutdown(wait=False, cancel_futures=True)
