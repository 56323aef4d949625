"""Continuous-batching query loop (DESIGN.md §13).

The serving analogue of the prefill→decode micro-batch loop in
``src/repro/launch/serve.py``: queries accumulate in a queue and flush as
one micro-batch when either ``max_batch`` queries are waiting or the oldest
has waited ``max_wait_ms`` — the standard continuous-batching contract.

Every flush routes queries by partition label: known nodes gather their
embedding from the owning shard (through the LRU hot-node cache) and run
the trained classifier MLP; unknown nodes take the inductive fallback
(:mod:`repro.serving.inductive`) on the shard owning most of their
neighbors.

**Zero-recompile discipline.** Device calls happen at *fixed bucket
shapes*: a flush of ``b`` queries pads to the next power of two ≤
``max_batch``, and the inductive path additionally fixes the neighbor axis
at ``max_neighbors``. ``warmup()`` pre-compiles every bucket once; after
that, a steady-state flush can never introduce a new shape, which
:class:`CompileLog` verifies by watching the jit caches — the
``steady_state_recompiles`` counter the serving benchmark gates on is a
measurement, not an assumption.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import obs

from .cache import LruNodeCache
from .inductive import InductiveEngine

__all__ = ["Query", "Answer", "CompileLog", "ContinuousBatcher",
           "bucket_sizes", "bucket_of"]


def bucket_sizes(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two flush buckets: 1, 2, 4, ..., max_batch."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def bucket_of(n: int, max_batch: int) -> int:
    """Smallest bucket holding ``n`` queries."""
    for b in bucket_sizes(max_batch):
        if n <= b:
            return b
    return max_batch


@dataclasses.dataclass
class Query:
    qid: int
    node_id: int
    neighbors: Optional[np.ndarray]     # only for unknown nodes
    t_submit: float


@dataclasses.dataclass
class Answer:
    qid: int
    node_id: int
    label: int
    shard: int
    source: str           # "cache" | "store" | "inductive" | "degraded"
    latency_ms: float
    logits: Optional[np.ndarray] = None
    embedding: Optional[np.ndarray] = None


class CompileLog:
    """Measured compile counts per jitted callable, split warmup/steady.

    Reads each function's jit cache size around the call (``_cache_size``),
    so the count is what XLA actually compiled."""

    def __init__(self):
        self.warm_compiles: Dict[str, int] = {}
        self.steady_compiles: Dict[str, int] = {}
        self._steady = False

    def mark_steady(self) -> None:
        """End of warmup: every compile from here on is a violation."""
        self._steady = True

    def call(self, name: str, fn: Callable, *args, **kwargs):
        before = fn._cache_size()
        out = fn(*args, **kwargs)
        compiled = fn._cache_size() - before
        if compiled:
            book = (self.steady_compiles if self._steady
                    else self.warm_compiles)
            book[name] = book.get(name, 0) + compiled
            phase = "steady" if self._steady else "warm"
            obs.counter(f"serving.compiles.{phase}").inc(compiled)
        return out

    @property
    def steady_state_recompiles(self) -> int:
        return sum(self.steady_compiles.values())

    def stats(self) -> Dict[str, Any]:
        return {"warm_compiles": dict(self.warm_compiles),
                "steady_compiles": dict(self.steady_compiles),
                "steady_state_recompiles": self.steady_state_recompiles}


class ContinuousBatcher:
    """max_batch/max_wait_ms flush loop over a sharded embedding store."""

    def __init__(self, store, cache: Optional[LruNodeCache] = None,
                 max_batch: int = 64, max_wait_ms: float = 2.0,
                 max_neighbors: int = 32, use_kernel: bool = False,
                 now: Callable[[], float] = time.perf_counter):
        from repro.gnn import mlp_forward
        self.store = store
        self.cache = cache if cache is not None else LruNodeCache()
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.now = now
        self.inductive = InductiveEngine(store, max_neighbors=max_neighbors,
                                         use_kernel=use_kernel)
        self.compiles = CompileLog()
        self._classify = jax.jit(mlp_forward)
        self._queue: deque[Query] = deque()
        self._next_qid = 0
        self.flushes = 0
        self.queries_served = 0
        self.per_shard_served: Dict[int, int] = {}
        self.flush_reasons: Dict[str, int] = {}

    # ----- intake ---------------------------------------------------------
    def submit(self, node_id: int, neighbors=None,
               now: Optional[float] = None) -> int:
        qid = self._next_qid
        self._next_qid += 1
        nb = None
        if neighbors is not None:
            nb = np.asarray(neighbors, dtype=np.int64).reshape(-1)
        self._queue.append(Query(qid=qid, node_id=int(node_id), neighbors=nb,
                                 t_submit=self.now() if now is None else now))
        return qid

    def pending(self) -> int:
        return len(self._queue)

    # ----- flush policy ---------------------------------------------------
    def due(self, now: Optional[float] = None) -> bool:
        if not self._queue:
            return False
        if len(self._queue) >= self.max_batch:
            return True
        now = self.now() if now is None else now
        return (now - self._queue[0].t_submit) * 1000.0 >= self.max_wait_ms

    def pump(self, now: Optional[float] = None) -> List[Answer]:
        """Flush as long as a flush is due; the serving loop's heartbeat."""
        out: List[Answer] = []
        while self.due(now):
            reason = ("max_batch" if len(self._queue) >= self.max_batch
                      else "max_wait_ms")
            out.extend(self.flush(reason))
        return out

    def drain(self) -> List[Answer]:
        """Flush everything regardless of the policy (end of a replay)."""
        out: List[Answer] = []
        while self._queue:
            out.extend(self.flush("drain"))
        return out

    # ----- the micro-batch ------------------------------------------------
    def warmup(self) -> int:
        """Pre-compile every bucket shape; returns the number of compiles.

        After ``warmup()`` the steady state must never compile again —
        ``compiles.steady_state_recompiles`` counts violations."""
        e = self.store.embed_dim
        clf = {k: np.asarray(v) for k, v in self.store.classifier.items()}
        for b in bucket_sizes(self.max_batch):
            self.compiles.call("classify", self._classify, clf,
                               np.zeros((b, e), np.float32))
            self.compiles.call(
                "inductive", self.inductive.jitted,
                np.zeros((b, self.inductive.max_neighbors, e), np.float32),
                np.zeros((b, self.inductive.max_neighbors), np.float32),
                np.zeros((b, e, self.store.num_classes), np.float32),
                np.zeros((b, self.store.num_classes), np.float32),
                max_neighbors=self.inductive.max_neighbors,
                use_kernel=self.inductive.use_kernel,
                kernel_config=self.inductive.kernel_config(b))
        warmed = sum(self.compiles.warm_compiles.values())
        self.compiles.mark_steady()
        return warmed

    def flush(self, reason: str = "drain") -> List[Answer]:
        batch = [self._queue.popleft()
                 for _ in range(min(self.max_batch, len(self._queue)))]
        if not batch:
            return []
        self.flushes += 1
        self.flush_reasons[reason] = self.flush_reasons.get(reason, 0) + 1
        obs.counter(f"serving.flush.{reason}").inc()
        obs.histogram("serving.batch_size").record(len(batch))
        known = [q for q in batch if self.store.is_known(q.node_id)]
        unknown = [q for q in batch if not self.store.is_known(q.node_id)]
        answers: List[Answer] = []
        with obs.span("serving.flush", reason=reason, batch=len(batch),
                      known=len(known), unknown=len(unknown)):
            answers.extend(self._flush_known(known))
            answers.extend(self._flush_inductive(unknown))
        self.queries_served += len(answers)
        return answers

    def _flush_known(self, queries: List[Query]) -> List[Answer]:
        if not queries:
            return []
        e = self.store.embed_dim
        b_pad = bucket_of(len(queries), self.max_batch)
        obs.counter(f"serving.bucket.classify.{b_pad}").inc()
        emb = np.zeros((b_pad, e), dtype=np.float32)
        sources: List[str] = []
        miss_pos: List[int] = []
        miss_ids: List[int] = []
        for i, q in enumerate(queries):
            row = self.cache.get(q.node_id)
            if row is None:
                miss_pos.append(i)
                miss_ids.append(q.node_id)
                sources.append("store")
            else:
                emb[i] = row
                sources.append("cache")
        if miss_ids:
            rows = self.store.lookup(np.asarray(miss_ids))  # shard-routed
            for pos, nid, row in zip(miss_pos, miss_ids, rows):
                emb[pos] = row
                self.cache.put(nid, row)
        clf = self.store.classifier
        logits = np.asarray(self.compiles.call(
            "classify", self._classify, clf, emb))
        labels = logits[:len(queries)].argmax(-1)
        t_done = self.now()
        out = []
        for i, q in enumerate(queries):
            pid = int(self.store.partition_of[q.node_id])
            self.per_shard_served[pid] = self.per_shard_served.get(pid, 0) + 1
            out.append(Answer(
                qid=q.qid, node_id=q.node_id, label=int(labels[i]),
                shard=pid, source=sources[i],
                latency_ms=(t_done - q.t_submit) * 1000.0,
                logits=logits[i], embedding=emb[i]))
        return out

    def _flush_inductive(self, queries: List[Query]) -> List[Answer]:
        if not queries:
            return []
        b_pad = bucket_of(len(queries), self.max_batch)
        obs.counter(f"serving.bucket.inductive.{b_pad}").inc()
        nb_lists = [q.neighbors if q.neighbors is not None
                    else np.zeros(0, np.int64) for q in queries]
        nb_emb, nb_mask, pids = self.inductive.prepare(nb_lists, b_pad)
        emb, logits = self.compiles.call(
            "inductive", self.inductive.jitted,
            nb_emb, nb_mask,
            self.store.head_w[pids], self.store.head_b[pids],
            max_neighbors=self.inductive.max_neighbors,
            use_kernel=self.inductive.use_kernel,
            kernel_config=self.inductive.kernel_config(b_pad))
        emb, logits = np.asarray(emb), np.asarray(logits)
        degraded = nb_mask.sum(axis=1) == 0
        labels = logits[:len(queries)].argmax(-1)
        t_done = self.now()
        out = []
        for i, q in enumerate(queries):
            pid = int(pids[i])
            self.per_shard_served[pid] = self.per_shard_served.get(pid, 0) + 1
            out.append(Answer(
                qid=q.qid, node_id=q.node_id, label=int(labels[i]),
                shard=pid,
                source="degraded" if degraded[i] else "inductive",
                latency_ms=(t_done - q.t_submit) * 1000.0,
                logits=logits[i], embedding=emb[i]))
        return out

    # ----- reporting ------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        return {
            "flushes": self.flushes,
            "flush_reasons": dict(sorted(self.flush_reasons.items())),
            "queries_served": self.queries_served,
            "max_batch": self.max_batch,
            "max_wait_ms": self.max_wait_ms,
            "buckets": list(bucket_sizes(self.max_batch)),
            "per_shard_served": {str(k): v for k, v in
                                 sorted(self.per_shard_served.items())},
            "cache": self.cache.stats(),
            **self.compiles.stats(),
        }
