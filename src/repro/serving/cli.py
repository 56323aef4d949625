"""CLI for the serving subsystem (DESIGN.md §13).

    # end-to-end: export (or reuse) a 4-partition pipeline bundle, replay a
    # 10k-query Zipf workload through the continuous batcher, verify served
    # labels against the offline answer key, append BENCH_serving.json
    PYTHONPATH=src python -m repro.serving

    # multi-process layout (the DGL server/client shape, SNIPPETS §2):
    PYTHONPATH=src python -m repro.serving serve  --port 7431 &
    PYTHONPATH=src python -m repro.serving client --port 7431 --queries 2000

The server hosts the partition-sharded store behind one continuous batcher;
any number of clients connect concurrently (batching happens *across*
connections — that is the point of continuous batching). The line protocol
is JSON per line: ``{"op": "query", "node": 17}``,
``{"op": "query", "node": 99999, "neighbors": [3, 14, 15]}`` (inductive),
``{"op": "meta"}``, ``{"op": "stats"}``.

Bundles are keyed by the partitioner-spec fingerprint: a bundle exported
under different partitioner hyperparameters is a *hard error*
(:class:`repro.serving.store.StaleServingArtifact`), never silently served.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import socketserver
import sys
import threading
import time
from typing import List, Optional

from repro.launch.compile_cache import enable_compile_cache

log = logging.getLogger("repro.serving")

DEFAULT_BUNDLE_DIR = os.path.join("~", ".cache", "repro", "serving")
DEFAULT_CACHE = os.path.join("~", ".cache", "repro", "partitions")


# ---------------------------------------------------------------------------
# argparse
# ---------------------------------------------------------------------------
def _add_bundle_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--bundle-dir", default=DEFAULT_BUNDLE_DIR,
                    help="directory of serving bundles (fingerprint-named)")
    ap.add_argument("--bundle", default=None,
                    help="explicit bundle .npz (skips the pipeline export)")
    ap.add_argument("--dataset", default="arxiv-like")
    ap.add_argument("--nodes", type=int, default=2000,
                    help="synthetic dataset size for the export pipeline")
    ap.add_argument("--method", default="leiden_fusion",
                    help="partitioner spec; its config fingerprint keys "
                         "the bundle — mismatches are hard errors")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--classifier-epochs", type=int, default=80)
    ap.add_argument("--hidden-dim", type=int, default=64)
    ap.add_argument("--embed-dim", type=int, default=64)
    ap.add_argument("--cache-dir", default=DEFAULT_CACHE,
                    help="partition artifact cache for the export pipeline")
    ap.add_argument("--rebuild", action="store_true",
                    help="re-run the pipeline even if a bundle exists")


def _add_batcher_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--cache-capacity", type=int, default=512,
                    help="LRU hot-node cache size (embedding rows)")
    ap.add_argument("--max-neighbors", type=int, default=32,
                    help="inductive fallback: neighbor-axis pad size")
    ap.add_argument("--use-kernel", action="store_true",
                    help="inductive aggregation through the Pallas kernel "
                         "(DESIGN.md §11) instead of the jnp segment-sum")


def _add_workload_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--queries", type=int, default=10_000)
    ap.add_argument("--alpha", type=float, default=1.1,
                    help="Zipf exponent of the node popularity law")
    ap.add_argument("--unseen-frac", type=float, default=0.02,
                    help="fraction of queries for nodes outside the store "
                         "(answered by the inductive fallback)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serving",
        description="partition-sharded embedding serving: continuous "
                    "batching + LRU cache + inductive fallback")
    sub = ap.add_subparsers(dest="cmd", required=True)

    rp = sub.add_parser("replay", help="in-process Zipf replay (default)")
    _add_bundle_args(rp)
    _add_batcher_args(rp)
    _add_workload_args(rp)
    rp.add_argument("--bench-json", default=None,
                    help="BENCH trajectory path (default benchmarks/"
                         "artifacts/BENCH_serving.json; 'none' to skip)")
    rp.add_argument("--no-verify", action="store_true",
                    help="skip the exact-match check against the offline "
                         "answer key")
    rp.add_argument("--json", action="store_true")

    sv = sub.add_parser("serve", help="host the store behind a TCP server")
    _add_bundle_args(sv)
    _add_batcher_args(sv)
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=7431)

    cl = sub.add_parser("client", help="replay a workload against a server")
    _add_workload_args(cl)
    cl.add_argument("--host", default="127.0.0.1")
    cl.add_argument("--port", type=int, default=7431)
    cl.add_argument("--concurrency", type=int, default=8,
                    help="parallel connections (batching happens across "
                         "them on the server)")
    cl.add_argument("--seed", type=int, default=0)
    cl.add_argument("--json", action="store_true")
    return ap


# ---------------------------------------------------------------------------
# bundle resolution (export-on-miss through the pipeline)
# ---------------------------------------------------------------------------
def ensure_bundle(args) -> str:
    """Resolve the serving bundle, exporting one via the pipeline on miss.

    Returns the bundle path; the caller loads it with
    ``expect_fingerprint`` so a stale bundle can never be served."""
    from repro.core import PartitionerSpec
    fp = PartitionerSpec.parse(args.method).fingerprint()
    if args.bundle:
        return args.bundle
    bundle_dir = os.path.expanduser(args.bundle_dir)
    cand = os.path.join(bundle_dir, f"serving-{fp}.npz")
    if os.path.exists(cand) and not args.rebuild:
        log.info("serving bundle HIT: %s", cand)
        return cand
    log.info("serving bundle MISS: running the export pipeline "
             "(dataset=%s n=%d k=%d)", args.dataset, args.nodes, args.k)
    from repro.pipeline import Pipeline, PipelineConfig
    dataset_kwargs = {}
    if args.dataset.replace("-", "_") != "karate":
        dataset_kwargs["n"] = args.nodes
    cfg = PipelineConfig(
        dataset=args.dataset, method=args.method, k=args.k, seed=args.seed,
        mode="local", hidden_dim=args.hidden_dim, embed_dim=args.embed_dim,
        epochs=args.epochs, classifier_epochs=args.classifier_epochs,
        cache_dir=args.cache_dir, collect_hlo=False,
        serving_dir=bundle_dir, dataset_kwargs=dataset_kwargs)
    report = Pipeline(cfg).run()
    log.info("exported serving bundle: %s (test acc %.3f)",
             report.serving_path, report.accuracy.get("test", float("nan")))
    return report.serving_path


def load_store(args):
    from repro.core import PartitionerSpec
    from .store import EmbeddingStore
    path = ensure_bundle(args)
    fp = PartitionerSpec.parse(args.method).fingerprint() \
        if not args.bundle else None
    return EmbeddingStore.load(path, expect_fingerprint=fp)


def make_batcher(store, args):
    from .batcher import ContinuousBatcher
    from .cache import LruNodeCache
    return ContinuousBatcher(
        store, cache=LruNodeCache(args.cache_capacity),
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        max_neighbors=args.max_neighbors, use_kernel=args.use_kernel)


# ---------------------------------------------------------------------------
# replay (the default command — the end-to-end acceptance path)
# ---------------------------------------------------------------------------
def cmd_replay(args) -> int:
    from .replay import (DEFAULT_BENCH_JSON, append_bench_rows,
                         make_zipf_workload, run_replay)
    store = load_store(args)
    log.info("%s", store.summary())
    batcher = make_batcher(store, args)
    workload = make_zipf_workload(
        store.n, num_queries=args.queries, alpha=args.alpha,
        unseen_frac=args.unseen_frac, max_neighbors=args.max_neighbors,
        seed=args.seed)
    row = run_replay(batcher, workload, verify=not args.no_verify)
    bench = args.bench_json or DEFAULT_BENCH_JSON
    if bench != "none":
        append_bench_rows([row], path=bench)
        log.info("BENCH row appended: %s", bench)
    if args.json:
        print(json.dumps(row, indent=2))
    else:
        srcs = ", ".join(f"{k}={v}" for k, v in
                         sorted(row["served_by_source"].items()))
        print(f"serving replay: {row['queries']} queries in "
              f"{row['wall_s']}s ({row['throughput_qps']} qps)")
        print(f"  latency      p50={row['p50_ms']}ms p99={row['p99_ms']}ms")
        print(f"  cache        hit_rate={row['cache_hit_rate']}")
        print(f"  compiles     warm={row['warm_compiles']} "
              f"steady_state={row['steady_state_recompiles']}")
        reasons = ", ".join(f"{k}={v}" for k, v in
                            sorted(row["flush_reasons"].items()))
        print(f"  flushes      {row['flushes']} ({reasons})")
        print(f"  answers      {srcs}")
        print(f"  exact-match  {row['queries'] - row['label_mismatches']}"
              f"/{row['queries']} (mismatches={row['label_mismatches']})")
    return 0


# ---------------------------------------------------------------------------
# serve / client (multi-process, SNIPPETS §2 shape)
# ---------------------------------------------------------------------------
class _ServingState:
    """Shared batcher + answer dispatch for the threaded TCP server."""

    def __init__(self, store, batcher):
        self.store = store
        self.batcher = batcher
        self.lock = threading.Lock()
        self.answers = {}
        self.events = {}
        self.closing = threading.Event()

    def submit_and_wait(self, node, neighbors, timeout=60.0):
        ev = threading.Event()
        with self.lock:
            qid = self.batcher.submit(node, neighbors=neighbors)
            self.events[qid] = ev
        if not ev.wait(timeout):
            raise TimeoutError(f"query {qid} timed out")
        with self.lock:
            return self.answers.pop(qid)

    def pump_loop(self):
        tick = max(self.batcher.max_wait_ms / 1000.0 / 4, 1e-4)
        while not self.closing.is_set():
            with self.lock:
                ready = self.batcher.pump()
                events = []
                for a in ready:
                    self.answers[a.qid] = a
                    ev = self.events.pop(a.qid, None)
                    if ev is not None:
                        events.append(ev)
            for ev in events:        # wake waiters outside the lock
                ev.set()
            self.closing.wait(tick)


def _serving_state_pump(state: _ServingState) -> None:
    state.pump_loop()


def cmd_serve(args) -> int:
    store = load_store(args)
    batcher = make_batcher(store, args)
    warmed = batcher.warmup()
    state = _ServingState(store, batcher)

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            for raw in self.rfile:
                try:
                    req = json.loads(raw)
                except ValueError:
                    self._reply({"error": "bad json"})
                    continue
                op = req.get("op", "query")
                if op == "meta":
                    self._reply({"n": store.n, "k": store.k,
                                 "num_classes": store.num_classes,
                                 "embed_dim": store.embed_dim,
                                 "fingerprint": store.fingerprint})
                elif op == "stats":
                    with state.lock:
                        self._reply(batcher.stats())
                elif op == "query":
                    a = state.submit_and_wait(int(req["node"]),
                                              req.get("neighbors"))
                    self._reply({"id": req.get("id"), "node": a.node_id,
                                 "label": a.label, "shard": a.shard,
                                 "source": a.source,
                                 "latency_ms": round(a.latency_ms, 3)})
                else:
                    self._reply({"error": f"unknown op {op!r}"})

        def _reply(self, obj):
            self.wfile.write((json.dumps(obj) + "\n").encode())
            self.wfile.flush()

    srv = socketserver.ThreadingTCPServer((args.host, args.port), Handler)
    srv.daemon_threads = True
    pump = threading.Thread(target=_serving_state_pump, args=(state,),
                            daemon=True)
    pump.start()
    print(f"serving {store.summary()}")
    print(f"listening on {args.host}:{args.port} "
          f"(warmup compiled {warmed} bucket shapes; ctrl-c to stop)")
    sys.stdout.flush()
    try:
        srv.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        pass
    finally:
        state.closing.set()
        srv.server_close()
    return 0


def cmd_client(args) -> int:
    from .replay import make_zipf_workload

    def _rpc(sock_file, wfile, obj):
        wfile.write((json.dumps(obj) + "\n").encode())
        wfile.flush()
        return json.loads(sock_file.readline())

    with socket.create_connection((args.host, args.port), timeout=60) as s:
        rf, wf = s.makefile("rb"), s.makefile("wb")
        meta = _rpc(rf, wf, {"op": "meta"})
    workload = make_zipf_workload(
        int(meta["n"]), num_queries=args.queries, alpha=args.alpha,
        unseen_frac=args.unseen_frac, seed=args.seed)
    shards = [workload[i::args.concurrency]
              for i in range(args.concurrency)]
    lats: List[List[float]] = [[] for _ in shards]
    by_source: List[dict] = [{} for _ in shards]

    def worker(wi: int):
        with socket.create_connection((args.host, args.port),
                                      timeout=60) as s:
            rf, wf = s.makefile("rb"), s.makefile("wb")
            for node, nbs in shards[wi]:
                req = {"op": "query", "id": wi, "node": int(node)}
                if nbs is not None:
                    req["neighbors"] = [int(x) for x in nbs]
                t0 = time.perf_counter()
                resp = _rpc(rf, wf, req)
                lats[wi].append((time.perf_counter() - t0) * 1000.0)
                src = resp.get("source", "?")
                by_source[wi][src] = by_source[wi].get(src, 0) + 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(args.concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    import numpy as np
    flat = np.asarray([x for ls in lats for x in ls])
    merged: dict = {}
    for d in by_source:
        for k, v in d.items():
            merged[k] = merged.get(k, 0) + v
    out = {"queries": int(flat.size), "wall_s": round(wall, 3),
           "throughput_qps": round(flat.size / max(wall, 1e-9), 1),
           "p50_ms": round(float(np.percentile(flat, 50)), 3),
           "p99_ms": round(float(np.percentile(flat, 99)), 3),
           "served_by_source": merged,
           "concurrency": args.concurrency,
           "server": f"{args.host}:{args.port}",
           "fingerprint": meta["fingerprint"]}
    print(json.dumps(out, indent=2) if args.json else
          f"client: {out['queries']} queries, {out['throughput_qps']} qps, "
          f"p50={out['p50_ms']}ms p99={out['p99_ms']}ms, "
          f"sources={merged}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(levelname)s %(name)s: %(message)s")
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        argv = ["replay"]        # `python -m repro.serving` end-to-end
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.cmd == "replay":
        return cmd_replay(args)
    if args.cmd == "serve":
        return cmd_serve(args)
    return cmd_client(args)


if __name__ == "__main__":
    sys.exit(main())
