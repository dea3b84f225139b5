"""Bounded asynchronous prefetching: overlap host batch prep + H2D transfer
with device compute.

The trainer's feed path and the serving loops are producer/consumer pairs
where the producer is HOST work (index gather, pad, weight-mask build,
``device_put``/``put_global_batch`` transfers, request-batch assembly) and
the consumer is a jitted device dispatch. Run serially, the device idles
through every host phase — the executor-feeds-accelerator stall MMLSpark's
CNTK layer solved with streaming minibatch sources (arXiv:1804.04031) and
TPU input pipelines solve with host-side double buffering. Here the host
work for step ``s+1..s+depth`` runs on a daemon thread while step ``s``
executes on device, so the consuming loop receives already-placed arrays.

Semantics (the contract the tests pin):

  * **bounded depth** — at most ``depth`` produced-but-unconsumed items
    exist at any moment (a semaphore slot is acquired BEFORE the producer
    runs, so prefetched device batches never hold more than ``depth``
    batches of HBM);
  * **in-order** — items arrive exactly in producer order (one worker
    thread, one FIFO queue), so a prefetched fit replays the synchronous
    loss trajectory bit for bit;
  * **exception propagation** — a producer error re-raises at the
    consuming ``next()``; the worker never dies silently and the consumer
    never deadlocks on a dead producer;
  * **prompt shutdown** — ``close()`` (or exiting the ``with`` block)
    wakes a blocked producer and joins the thread; safe to call from a
    consumer that exits early (divergence halt, serving stop).

Thread-safety note: JAX dispatch/`device_put` are thread-safe, but
*collective* programs issued from multiple threads can interleave across
processes and deadlock — producers must only do per-process work
(transfers, host prep). Callers with per-step host collectives (e.g.
fitStream's multi-host lockstep allgather) must stay synchronous.

Fit-side fusion note: under a fused featurize->train fit
(Pipeline.fusePipeline, docs/performance.md "Fit-side fusion") the
trainer's feed producer places the RAW wire-dtype columns (int8/int16/…)
instead of the f32-widened feature matrix — featurization happens inside
the consuming device dispatch. The prefetch window then bounds *wire*
bytes of HBM, which is strictly less than the staged window for any
sub-f32 input, so ``depth`` can be raised on narrow-dtype pipelines at no
extra HBM cost.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional, Union

from .. import telemetry

# prefetch telemetry (off-by-default no-ops; MMLSPARK_TPU_TELEMETRY=1)
_m_queue_depth = telemetry.registry.gauge(
    "mmlspark_prefetch_queue_depth",
    "prefetched items currently produced but not yet consumed")
_m_produce_time = telemetry.registry.histogram(
    "mmlspark_prefetch_produce_seconds",
    "host prep + device placement time per prefetched item (producer "
    "thread) — the work the prefetcher hides behind device compute")
_m_producer_stall = telemetry.registry.histogram(
    "mmlspark_prefetch_producer_stall_seconds",
    "time the producer spent blocked because `depth` items were already "
    "outstanding (consumer-bound; harmless)")
_m_consumer_stall = telemetry.registry.histogram(
    "mmlspark_prefetch_consumer_stall_seconds",
    "time the consumer spent waiting for the next prefetched item "
    "(host-bound; the stall the prefetcher exists to shrink)")

#: queue sentinels (kind tags; unique objects, compared by identity)
_ITEM, _DONE, _ERROR = object(), object(), object()


class DevicePrefetcher:
    """Iterator running ``source`` on a background thread, ``depth`` ahead.

    ``source`` is an iterable (or a zero-arg callable returning one) whose
    ``next()`` performs the per-item host work — build the batch AND place
    it on device there, so the consumer receives ready jax Arrays.

    ``depth=0`` is honored by :func:`prefetched`, which returns the plain
    iterator (the synchronous path); ``DevicePrefetcher`` itself requires
    ``depth >= 1``.
    """

    def __init__(self, source: Union[Iterable, Callable[[], Iterable]],
                 depth: int = 2, name: str = "prefetch",
                 span: Optional[str] = None, first_item: int = 0):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = depth
        self.name = name
        #: the consumer's number for this prefetcher's first item: a span
        #: carries ``item = first_item + <items produced before it>``, the
        #: identifier the consumer's own spans of that item share (a fit
        #: with one prefetcher an epoch numbers its steps across them)
        self._first_item = first_item
        #: items the producer thread has finished placing (monotonic);
        #: lets tests assert a prefetched run actually ran ahead instead
        #: of degenerating to lockstep
        self.items = 0
        self._span = span
        self._source = source
        # slots acquired BEFORE producing bound produced-but-unconsumed
        # items (and therefore prefetched HBM) at exactly `depth`; the
        # queue itself can stay unbounded
        self._slots = threading.Semaphore(depth)
        self._q: "queue.Queue[tuple[str, object]]" = queue.Queue()
        self._stop = threading.Event()
        # consumer-side cursor: thread-confined, never touched by the
        # producer thread (whose entry point is _work)
        self._finished = False   # guarded-by: !_work
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name=f"prefetch-{name}")
        self._thread.start()

    # ---- producer (worker thread) ----
    def _acquire_slot(self) -> bool:
        """Blocking slot acquire that stays responsive to close()."""
        t0 = time.perf_counter()
        while not self._stop.is_set():
            if self._slots.acquire(timeout=0.05):
                _m_producer_stall.observe(time.perf_counter() - t0)
                return True
        return False

    def _work(self):
        try:
            it = iter(self._source() if callable(self._source)
                      else self._source)
            while not self._stop.is_set():
                if not self._acquire_slot():
                    return              # closed while waiting for a slot
                t0 = time.perf_counter()
                if self._span:
                    with telemetry.trace.span(
                            self._span, source=self.name,
                            item=self._first_item + self.items) as sp:
                        item = next(it, _DONE)
                        if item is _DONE:
                            sp.discard()    # no such item
                else:
                    item = next(it, _DONE)
                if item is _DONE:
                    break
                _m_produce_time.observe(time.perf_counter() - t0)
                self.items += 1
                self._q.put((_ITEM, item))
                _m_queue_depth.set(self._q.qsize())
        except BaseException as e:       # re-raised at the consumer's next()
            self._q.put((_ERROR, e))
        else:
            self._q.put((_DONE, None))

    # ---- consumer ----
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            try:
                kind, item = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                # belt-and-braces: the worker's except/else clauses always
                # enqueue a terminal record, but a worker killed without
                # running them (interpreter teardown) must not hang us
                if not self._thread.is_alive():
                    self._finished = True
                    raise RuntimeError(
                        f"prefetch worker {self.name!r} died without "
                        f"delivering") from None
        if kind is _ITEM:
            _m_consumer_stall.observe(time.perf_counter() - t0)
            _m_queue_depth.set(self._q.qsize())
            self._slots.release()
            return item
        self._finished = True
        if kind is _ERROR:
            self.close()
            raise item
        self._thread.join(timeout=5.0)
        raise StopIteration

    # ---- lifecycle ----
    def close(self):
        """Stop the producer and reclaim the thread. Idempotent; safe on
        early consumer exit (divergence halt, serving stop) — a producer
        blocked on a full prefetch window wakes within one slot-poll tick."""
        self._stop.set()
        self._finished = True
        # drain queued items so a producer blocked in q.put (unbounded
        # queue: never happens, but cheap) or mid-produce can finish
        try:
            while True:
                self._q.get_nowait()
                self._slots.release()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        _m_queue_depth.set(0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def prefetched(source: Union[Iterable, Callable[[], Iterable]],
               depth: int = 2, name: str = "prefetch",
               span: Optional[str] = None, first_item: int = 0) -> Iterator:
    """``DevicePrefetcher`` when ``depth >= 1``, the plain (synchronous)
    iterator when ``depth == 0`` — the one switch call sites need. The
    returned iterator always supports ``close()`` so consumer ``finally``
    blocks are uniform."""
    if depth <= 0:
        it = iter(source() if callable(source) else source)
        return _SyncIter(it)
    return DevicePrefetcher(source, depth=depth, name=name, span=span,
                            first_item=first_item)


class _SyncIter:
    """Plain iterator with a no-op close() (depth=0 fallback)."""

    __slots__ = ("_it",)

    def __init__(self, it: Iterator):
        self._it = it

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
