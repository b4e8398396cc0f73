"""Collection utilities (reference: the vendored berkeley/ package —
Counter/CounterMap/Pair/Triple/PriorityQueue, SURVEY.md §2.1 — plus
util/DiskBasedQueue.java and parallelism/MagicQueue.java/AsyncIterator.java
from deeplearning4j-core §2.2).

Python's stdlib covers most of Berkeley's surface (collections.Counter,
tuples, heapq); what this module adds are the reference behaviors with no
stdlib equivalent: normalized/arg-max counters, a two-key counter map, a
disk-spilling queue, and the device-affinity round-robin queue + async
iterator used by the parallel trainers.
"""

from __future__ import annotations

import collections
import os
import pickle
import queue
import tempfile
import threading
import time
from typing import Any, Dict, Hashable, Iterable, Iterator, List, Optional, Tuple


class Counter(collections.Counter):
    """berkeley/Counter.java behaviors on top of collections.Counter."""

    def arg_max(self) -> Optional[Hashable]:
        return max(self, key=self.get) if self else None

    def total_count(self) -> float:
        return float(sum(self.values()))

    def normalize(self) -> "Counter":
        total = self.total_count()
        if total > 0:
            for k in self:
                self[k] /= total
        return self

    def keep_top_n(self, n: int) -> "Counter":
        for k, _ in self.most_common()[n:]:
            del self[k]
        return self


class CounterMap:
    """key → Counter of sub-keys (berkeley/CounterMap.java)."""

    def __init__(self):
        self._map: Dict[Hashable, Counter] = collections.defaultdict(Counter)

    def increment_count(self, key: Hashable, sub: Hashable, amount: float = 1.0):
        self._map[key][sub] += amount

    def get_count(self, key: Hashable, sub: Hashable) -> float:
        return float(self._map.get(key, Counter()).get(sub, 0.0))

    def get_counter(self, key: Hashable) -> Counter:
        return self._map[key]

    def keys(self):
        return self._map.keys()

    def total_count(self) -> float:
        return sum(c.total_count() for c in self._map.values())

    def normalize(self) -> "CounterMap":
        for c in self._map.values():
            c.normalize()
        return self


class DiskBasedQueue:
    """FIFO that spills to disk past a memory bound (reference:
    util/DiskBasedQueue.java — unbounded corpora through bounded RAM)."""

    def __init__(self, memory_items: int = 1024, dir: Optional[str] = None):
        self._mem: collections.deque = collections.deque()
        self._limit = int(memory_items)
        self._dir = dir or tempfile.mkdtemp(prefix="dl4j-queue-")
        self._spill: collections.deque = collections.deque()  # file paths
        self._count = 0
        self._lock = threading.Lock()

    def add(self, item: Any) -> None:
        with self._lock:
            if len(self._mem) < self._limit and not self._spill:
                self._mem.append(item)
            else:
                path = os.path.join(self._dir, f"item_{self._count}.pkl")
                with open(path, "wb") as f:
                    pickle.dump(item, f)
                self._spill.append(path)
            self._count += 1

    def poll(self) -> Any:
        with self._lock:
            if self._mem:
                item = self._mem.popleft()
            elif self._spill:
                path = self._spill.popleft()
                with open(path, "rb") as f:
                    item = pickle.load(f)
                os.unlink(path)
            else:
                raise IndexError("queue empty")
            # refill memory tier from disk to keep pops cheap
            while self._spill and len(self._mem) < self._limit:
                p = self._spill.popleft()
                with open(p, "rb") as f:
                    self._mem.append(pickle.load(f))
                os.unlink(p)
            return item

    def __len__(self) -> int:
        return len(self._mem) + len(self._spill)

    def is_empty(self) -> bool:
        return len(self) == 0


class MagicQueue:
    """Round-robin multi-consumer queue (reference:
    parallelism/MagicQueue.java: device-affinity-aware distribution — each
    consumer lane gets its own backlog; here lanes map to mesh devices)."""

    def __init__(self, n_lanes: int, capacity: int = 64):
        self._lanes: List[queue.Queue] = [
            queue.Queue(maxsize=capacity) for _ in range(max(1, n_lanes))
        ]
        self._next = 0

    @property
    def n_lanes(self) -> int:
        return len(self._lanes)

    def add(self, item: Any) -> None:
        self._lanes[self._next].put(item)
        self._next = (self._next + 1) % len(self._lanes)

    def poll(self, lane: int, timeout: Optional[float] = None) -> Optional[Any]:
        try:
            return self._lanes[lane].get(
                block=timeout is not None, timeout=timeout
            )
        except queue.Empty:
            return None

    def size(self, lane: Optional[int] = None) -> int:
        if lane is not None:
            return self._lanes[lane].qsize()
        return sum(q.qsize() for q in self._lanes)


class AsyncIterator:
    """Background-thread prefetch over any iterator (reference:
    parallelism/AsyncIterator.java; the generic sibling of
    AsyncDataSetIterator).

    Counted in the default registry, where the work happens:

    - ``dl4jtpu_iterator_gets_total{state}`` at the consumer's get, the end
      sentinel's included: ``ready`` when an item was waiting, ``empty`` when
      the consumer had to block for the producer;
    - ``dl4jtpu_iterator_produce_seconds`` on the producer thread: each
      ``next(base)``;
    - ``dl4jtpu_iterator_queue_full_seconds`` on the producer thread: each
      wait for room in a full queue (a put that found room observes nothing).

    The producer thread opens no span: a span of another thread would take
    the attribution of device-idle time from the consumer's (the span that
    started later wins). ``fit`` puts ``dl4j.fit.next_batch`` around the
    consumer's side."""

    _SENTINEL = object()

    def __init__(self, base: Iterable, queue_size: int = 8):
        self._base = base
        self._size = int(queue_size)

    def __iter__(self) -> Iterator:
        from ..telemetry import get_registry  # noqa: PLC0415

        reg = get_registry()
        gets = reg.counter(
            "dl4jtpu_iterator_gets_total",
            "consumer gets of the prefetch queue, by what they found",
            labelnames=("state",))
        ready, empty = gets.labels(state="ready"), gets.labels(state="empty")
        produce_s = reg.histogram(
            "dl4jtpu_iterator_produce_seconds",
            "producer thread: seconds inside next(base)")
        full_s = reg.histogram(
            "dl4jtpu_iterator_queue_full_seconds",
            "producer thread: seconds blocked on a full prefetch queue")
        q: "queue.Queue" = queue.Queue(maxsize=self._size)
        err: List[BaseException] = []
        stop = threading.Event()

        def put(item) -> None:
            """Until there is room or the consumer has gone. The one
            producer: a queue it finds with room still has room at its put."""
            if not q.full():
                q.put(item)
                return
            t0 = time.perf_counter()
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            full_s.observe(time.perf_counter() - t0)

        def producer():
            try:
                base = iter(self._base)
                while not stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        item = next(base)
                    except StopIteration:
                        break
                    produce_s.observe(time.perf_counter() - t0)
                    put(item)
            except BaseException as e:
                err.append(e)
            finally:
                put(self._SENTINEL)

        t = threading.Thread(target=producer, daemon=True, name="async-iterator")
        t.start()
        try:
            while True:
                # the one consumer: an item it finds waiting is its to get
                (empty if q.empty() else ready).inc()
                item = q.get()
                if item is self._SENTINEL:
                    break
                yield item
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5)
        if err:
            raise err[0]
