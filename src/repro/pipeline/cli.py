"""CLI for the end-to-end pipeline.

    PYTHONPATH=src python -m repro.pipeline run \
        --dataset karate --method "lpa+f(alpha=0.1)" --k 4 --mode local

    PYTHONPATH=src python -m repro.pipeline partitioners
    PYTHONPATH=src python -m repro.pipeline cache --list
    PYTHONPATH=src python -m repro.pipeline cache --clear

``--method`` accepts any Partitioner API v2 spec string (DESIGN.md §9):
``method``, ``method(field=value,...)``, optionally followed by the ``+f``
fusion combinator — ``"metis"``, ``"lpa(max_iter=30)+f(alpha=0.1)"``,
``"leiden_fusion(resolution=0.5)"``. ``partitioners`` lists the registry
with each method's config schema, defaults, and capability flags.

Partition artifacts land under ``--cache-dir`` (default
``~/.cache/repro/partitions``); a second run with the same dataset/spec/
k/seed logs a cache hit and skips re-partitioning. The key includes the
spec's config fingerprint, so changing any hyperparameter is a cache miss.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
from typing import List, Optional

from repro.launch.compile_cache import enable_compile_cache

DEFAULT_CACHE = os.path.join("~", ".cache", "repro", "partitions")


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.pipeline",
        description="Leiden-Fusion end-to-end pipeline: partition -> "
                    "communication-free GNN training -> embedding assembly "
                    "-> node classification.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run the full pipeline once")
    run.add_argument("--dataset", default="arxiv-like",
                     help="karate | arxiv-like | proteins(-like) | "
                          "arxiv-like-stream (out-of-core: generation "
                          "streams to a chunked mmap CSR bundle on disk, "
                          "DESIGN.md §15)")
    run.add_argument("--nodes", type=int, default=None,
                     help="node count override for synthetic datasets")
    run.add_argument("--dataset-scale", type=float, default=None,
                     help="node-count multiplier for synthetic datasets "
                          "(e.g. 12.5 on arxiv-like -> 500k nodes; the "
                          "vectorized engine partitions it in seconds; "
                          "works for proteins(-like) and the streamed "
                          "variants too)")
    run.add_argument("--dataset-dir", default=None,
                     help="bundle directory for streamed datasets "
                          "(arxiv-like-stream); defaults to a deterministic "
                          "path under the system temp dir")
    run.add_argument("--method", default="leiden_fusion",
                     help="partitioner spec, e.g. leiden_fusion | metis | "
                          "\"lpa+f(alpha=0.1)\" | "
                          "\"leiden_fusion(resolution=0.5)\" — see the "
                          "'partitioners' subcommand for the registry")
    run.add_argument("--k", type=int, default=8)
    run.add_argument("--seed", type=int, default=0)
    run.add_argument("--scheme", default="repli", choices=["inner", "repli"])
    run.add_argument("--mode", default="local",
                     choices=["local", "sync", "stale"],
                     help="local = zero communication (the paper); sync = "
                          "halo exchange every step; stale = exchange every "
                          "--sync-period epochs, frozen halos in between "
                          "(DESIGN.md §12)")
    run.add_argument("--sync-period", type=int, default=4,
                     help="stale mode: halo-exchange period in epochs "
                          "(1 ≡ sync, 0 = never exchange ≡ local)")
    run.add_argument("--integrate", default="none",
                     choices=["none", "model_avg", "ensemble"],
                     help="aggregate the k per-partition models before "
                          "embedding assembly: model_avg parameter-averages "
                          "(arxiv 2305.09887), ensemble averages embeddings")
    run.add_argument("--model", default="gcn",
                     choices=["gcn", "sage", "gat"])
    run.add_argument("--heads", type=int, default=1,
                     help="attention heads a GAT layer (--model gat): hidden "
                          "layers concatenate them, the last averages them; "
                          "--hidden-dim is the width of one head")
    run.add_argument("--use-kernel", action="store_true",
                     help="route GNN layers through the autotuned kernel "
                          "dispatcher (fused Pallas layer on TPU, XLA "
                          "strategy on interpret-mode backends — "
                          "DESIGN.md §3/§11/§14)")
    run.add_argument("--kernel-autotune", action="store_true",
                     help="sweep the kernel tile/strategy search space for "
                          "this run's shape buckets before training and "
                          "cache the winners on disk (DESIGN.md §14; "
                          "no-op without --use-kernel)")
    run.add_argument("--hidden-dim", type=int, default=128)
    run.add_argument("--embed-dim", type=int, default=128)
    run.add_argument("--num-layers", type=int, default=3)
    run.add_argument("--dropout", type=float, default=0.3)
    run.add_argument("--epochs", type=int, default=60)
    run.add_argument("--lr", type=float, default=5e-3)
    run.add_argument("--classifier-epochs", type=int, default=150)
    run.add_argument("--cache-dir", default=DEFAULT_CACHE)
    run.add_argument("--no-cache", action="store_true",
                     help="disable the partition artifact cache")
    run.add_argument("--checkpoint-dir", default=None,
                     help="save trained per-partition params here")
    run.add_argument("--serving-dir", default=None,
                     help="export a repro.serving bundle here (embeddings + "
                          "per-partition heads + classifier + offline "
                          "answer key; requires --classifier-epochs > 0)")
    run.add_argument("--no-hlo", action="store_true",
                     help="skip lowering the train step for the "
                          "collective-bytes report (saves one compile)")
    run.add_argument("--low-memory", action="store_true",
                     help="local mode: train partitions one at a time "
                          "(same math, ~1/k the transient RAM; implies "
                          "unsharded + --no-hlo — DESIGN.md §15)")
    run.add_argument("--trace", default=None, metavar="PATH",
                     help="enable repro.obs tracing and export a Chrome "
                          "trace-event JSON here after the run (open in "
                          "Perfetto; aggregate with 'python -m repro.obs "
                          "summarize PATH' — DESIGN.md §16)")
    run.add_argument("--jax-profile", default=None, metavar="DIR",
                     help="start a jax.profiler session around the "
                          "training stage, writing to DIR")
    run.add_argument("--json", action="store_true",
                     help="print the report as JSON instead of the summary")

    cache = sub.add_parser("cache", help="inspect/clear the artifact cache")
    cache.add_argument("--cache-dir", default=DEFAULT_CACHE)
    cache.add_argument("--list", action="store_true", default=True)
    cache.add_argument("--clear", action="store_true")

    part = sub.add_parser(
        "partitioners",
        help="list registered partitioners with config schemas and "
             "capability flags")
    part.add_argument("--json", action="store_true",
                      help="machine-readable schema dump")
    return ap


def _cmd_run(args: argparse.Namespace) -> int:
    from repro import obs

    from .pipeline import Pipeline, PipelineConfig
    if args.trace:
        obs.enable()
    dataset_kwargs = {}
    if args.nodes is not None:
        dataset_kwargs["n"] = args.nodes
    if args.dataset_scale is not None:
        dataset_kwargs["scale"] = args.dataset_scale
    if args.dataset_dir is not None:
        dataset_kwargs["out_dir"] = args.dataset_dir
    cfg = PipelineConfig(
        dataset=args.dataset, method=args.method, k=args.k, seed=args.seed,
        scheme=args.scheme, mode=args.mode, sync_period=args.sync_period,
        integrate=args.integrate, model=args.model, heads=args.heads,
        use_kernel=args.use_kernel,
        kernel_autotune=args.kernel_autotune,
        hidden_dim=args.hidden_dim, embed_dim=args.embed_dim,
        num_layers=args.num_layers, dropout=args.dropout,
        epochs=args.epochs, lr=args.lr,
        classifier_epochs=args.classifier_epochs,
        cache_dir=None if args.no_cache else args.cache_dir,
        checkpoint_dir=args.checkpoint_dir,
        serving_dir=args.serving_dir,
        collect_hlo=not args.no_hlo,
        low_memory=args.low_memory,
        jax_profile_dir=args.jax_profile,
        dataset_kwargs=dataset_kwargs)
    report = Pipeline(cfg).run()
    if args.trace:
        path = obs.export_trace(args.trace)
        print(f"trace written: {path} "
              f"({obs.tracer().event_count()} spans) — summarize with "
              f"'python -m repro.obs summarize {path}'", file=sys.stderr)
    if args.json:
        import json
        print(json.dumps(report.as_dict(), indent=2))
    else:
        print(report.summary())
    return 0


def _cmd_cache(args: argparse.Namespace) -> int:
    from .artifacts import PartitionArtifactStore
    store = PartitionArtifactStore(args.cache_dir)
    if args.clear:
        n = store.clear()
        print(f"removed {n} artifact(s) from {store.cache_dir}")
        return 0
    entries = store.entries()
    if not entries:
        print(f"cache empty: {store.cache_dir}")
        return 0
    total = 0
    for name, size in entries:
        total += size
        print(f"{size:>12d}  {name}")
    print(f"{total:>12d}  total ({len(entries)} artifacts) "
          f"in {store.cache_dir}")
    return 0


def _config_schema(config_type) -> dict:
    import dataclasses
    out = {}
    for f in dataclasses.fields(config_type):
        default = f.default if f.default is not dataclasses.MISSING else None
        hint = f.metadata.get("help", "")
        type_name = getattr(f.type, "__name__", str(f.type))
        out[f.name] = {"type": type_name, "default": default, "help": hint}
    return out


def _cmd_partitioners(args: argparse.Namespace) -> int:
    import dataclasses
    from repro.core import FusionConfig, registered_partitioners
    entries = registered_partitioners()
    if args.json:
        import json
        payload = {
            name: {
                "capabilities": dataclasses.asdict(e.capabilities),
                "config": e.config_type.__name__,
                "fields": _config_schema(e.config_type),
                "doc": e.doc,
            } for name, e in entries.items()}
        payload["+f"] = {
            "doc": "fusion combinator over any base method (paper §5.4)",
            "config": FusionConfig.__name__,
            "fields": _config_schema(FusionConfig)}
        print(json.dumps(payload, indent=2))
        return 0
    for name, e in entries.items():
        print(f"{name:16s} [{e.capabilities.describe()}]  {e.doc}")
        schema = _config_schema(e.config_type)
        if not schema:
            print(f"{'':16s}   (no config fields)")
        for field, info in schema.items():
            hint = f"  — {info['help']}" if info["help"] else ""
            print(f"{'':16s}   {field}: {info['type']} = "
                  f"{info['default']!r}{hint}")
    print()
    print("+f               fusion combinator: any spec may end in "
          "\"+f(...)\" (paper §5.4)")
    for field, info in _config_schema(FusionConfig).items():
        hint = f"  — {info['help']}" if info["help"] else ""
        print(f"{'':16s}   {field}: {info['type']} = "
              f"{info['default']!r}{hint}")
    print()
    print("spec grammar: method | method(field=value,...) | base+f | "
          "base(...)+f(field=value,...)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(levelname)s %(name)s: %(message)s")
    args = _build_parser().parse_args(argv)
    enable_compile_cache()
    if args.cmd == "run":
        return _cmd_run(args)
    if args.cmd == "partitioners":
        return _cmd_partitioners(args)
    return _cmd_cache(args)


if __name__ == "__main__":
    sys.exit(main())
