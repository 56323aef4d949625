"""The end-to-end pipeline orchestrator (DESIGN.md §1).

Chains the paper's three stages behind one call:

    dataset -> partition (cached) -> per-partition GNN training -> model
    integration -> embedding assembly -> MLP classifier eval

and returns a single :class:`PipelineReport` carrying partition quality,
collective bytes of the lowered train step, classification accuracy, and
per-stage timings. Training mode is ``local`` (the paper's communication-free
scheme), ``sync`` (the DGL-style halo-exchange baseline), or ``stale``
(periodic halo exchange every ``sync_period`` epochs — the comm-vs-accuracy
middle ground, DESIGN.md §12). ``integrate`` optionally parameter-averages
(``model_avg``) or ensembles the k per-partition models before assembly.

Every stage runs under a ``repro.obs`` span (``pipeline.dataset``,
``pipeline.partition``, ``pipeline.train``, ``pipeline.classifier``, ...)
nested in one ``pipeline.total`` root. ``PipelineReport.timings`` is a view
over those span durations — when tracing is enabled each timing IS the
corresponding span's duration (pinned by ``tests/test_obs.py``); when
disabled, the same windows are measured with bare ``perf_counter`` pairs so
the dict stays API-compatible at zero tracing cost (DESIGN.md §16).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Dict, Mapping, Optional

from repro import obs
from repro.core import (INTEGRATION_KINDS, NodeDataset, PartitionerSpec,
                        evaluate_partition)
from repro.gnn import (GNNConfig, stale_bytes_per_epoch,
                       stale_exchange_epochs, train_classifier, train_local,
                       train_stale, train_sync)

from .artifacts import ArtifactBundle, PartitionArtifactStore, compute_bundle
from .datasets import get_dataset

__all__ = ["PipelineConfig", "PipelineReport", "Pipeline"]

log = logging.getLogger("repro.pipeline")


@contextlib.contextmanager
def _stage_span(timings: Dict[str, float], key: str, name: str,
                **attrs: Any):
    """Time one pipeline stage into ``timings[key]``.

    Tracing enabled: the timing is exactly the span's recorded duration, so
    ``timings`` is a faithful view over the trace. Disabled: a plain
    ``perf_counter`` pair over the identical window.
    """
    if obs.enabled():
        with obs.span(name, **attrs) as sp:
            yield sp
        timings[key] = sp.duration
    else:
        t0 = time.perf_counter()
        yield obs.span(name)     # the shared no-op span
        timings[key] = time.perf_counter() - t0


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """One run of the end-to-end pipeline. Mirrors the CLI flags 1:1."""
    dataset: str = "arxiv-like"
    method: str = "leiden_fusion"   # partitioner spec string (DESIGN.md §9),
                                    # e.g. "metis", "lpa+f(alpha=0.1)",
                                    # "leiden_fusion(resolution=0.5)"
    k: int = 8
    seed: int = 0
    scheme: str = "repli"           # "inner" | "repli" (sync/stale force repli)
    mode: str = "local"             # "local" | "sync" | "stale"
    sync_period: int = 4            # stale mode: exchange halos every N
                                    # epochs (1 ≡ sync; 0 = never ≡ local)
    integrate: str = "none"         # "none" | "model_avg" | "ensemble" —
                                    # aggregate the k models pre-assembly
    model: str = "gcn"              # "gcn" | "sage" | "gat"
    heads: int = 1                  # "gat": attention heads a layer
    use_kernel: bool = False        # route GNN layers through the kernel
                                    # dispatcher (DESIGN.md §3/§11/§14);
                                    # differentiable, so every training
                                    # mode supports it
    kernel_autotune: bool = False   # sweep the kernel search space for this
                                    # run's shape buckets before training
                                    # (cached on disk; implies use_kernel
                                    # semantics only when use_kernel=True)
    hidden_dim: int = 128
    embed_dim: int = 128
    num_layers: int = 3
    dropout: float = 0.3
    epochs: int = 60
    lr: float = 5e-3
    classifier_epochs: int = 150    # <= 0 skips the classifier stage
    classifier_hidden: int = 256
    cache_dir: Optional[str] = None     # None disables the artifact cache
    checkpoint_dir: Optional[str] = None
    serving_dir: Optional[str] = None   # export a serving bundle here
                                        # (repro.serving, DESIGN.md §13);
                                        # requires the classifier stage
    collect_hlo: bool = True        # lower+compile once to count collectives
    shard_data_axis: bool = True    # local mode: shard k over the mesh
    low_memory: bool = False        # local mode: train partitions one at a
                                    # time (same math, ~1/k the transient
                                    # footprint; forces unsharded + no HLO
                                    # collection — DESIGN.md §15)
                                    # `data` axis; False forces unsharded
                                    # (sequential) execution, e.g. for
                                    # per-partition wall-time measurement
    jax_profile_dir: Optional[str] = None   # start a jax.profiler session
                                            # around the training stage and
                                            # write it here (DESIGN.md §16)
    dataset_kwargs: Mapping[str, Any] = dataclasses.field(
        default_factory=dict)


@dataclasses.dataclass(frozen=True)
class PipelineReport:
    """Structured result of one pipeline run."""
    config: Dict[str, Any]
    dataset: str
    num_nodes: int
    num_edges: int
    num_devices: int
    partition: Dict[str, Any]        # PartitionReport.as_dict()
    partition_cache_hit: bool
    batch_cache_hit: bool
    artifact_paths: Dict[str, Optional[str]]
    shapes: Dict[str, int]           # k, n_pad, e_pad; local mode adds the
                                     # partition group and its step-memory
                                     # estimate (bytes)
    collectives: Dict[str, int]      # collective_bytes() of the train step
    accuracy: Dict[str, float]       # train/val/test (empty if skipped)
    timings: Dict[str, float]
    checkpoint_path: Optional[str] = None
    partition_fingerprint: Optional[str] = None   # spec config fingerprint
    serving_path: Optional[str] = None            # exported serving bundle
    kernel: Optional[Dict[str, Any]] = None       # resolved KernelConfig per
                                                  # layer-input width
                                                  # (use_kernel runs only)

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def summary(self) -> str:
        c = self.config
        lines = ["PipelineReport"]
        lines.append(f"  dataset      {self.dataset} (n={self.num_nodes}, "
                     f"edges={self.num_edges})")
        hit = "HIT" if self.partition_cache_hit else "miss"
        fp = f" fp={self.partition_fingerprint}" \
            if self.partition_fingerprint else ""
        lines.append(f"  partition    {c['method']} k={c['k']} "
                     f"seed={c['seed']}{fp} [cache {hit}]")
        p = self.partition
        lines.append(f"               cut={p['edge_cut_pct']:.1f}% "
                     f"components={p['total_components']} "
                     f"isolated={p['total_isolated']} "
                     f"balance={p['node_balance']:.2f} "
                     f"replication={p['replication_factor']:.2f}")
        bhit = "HIT" if self.batch_cache_hit else "miss"
        lines.append(f"  assembly     scheme={c['scheme']} "
                     f"n_pad={self.shapes['n_pad']} "
                     f"e_pad={self.shapes['e_pad']} [cache {bhit}]")
        agg = "jnp"
        if self.kernel:
            # the strategy resolved per layer-input width, named as it is
            # (an "xla" resolution is not a kernel run)
            agg = ",".join(f"{width}:{entry['strategy']}"
                           for width, entry in sorted(self.kernel.items()))
        mode = c["mode"]
        if mode == "stale":
            period = c.get("sync_period", 0)
            mode = f"stale(period={period if period else '∞'})"
        model = c["model"]
        if c.get("heads", 1) != 1:
            model += f"(heads={c['heads']})"
        group = ""
        if "partition_group" in self.shapes:
            group = (f" partition_group={self.shapes['partition_group']} "
                     f"(~{self.shapes['step_bytes_estimate'] / 2**30:.2f} "
                     f"GiB a step)")
        lines.append(f"  training     mode={mode} model={model} "
                     f"layers={c['num_layers']} epochs={c['epochs']} "
                     f"aggregation={agg} devices={self.num_devices}{group}")
        if c.get("integrate", "none") != "none":
            lines.append(f"  integration  {c['integrate']} over k={c['k']} "
                         f"partition models (pre-assembly)")
        if self.collectives:
            lines.append(f"  collectives  {self.collectives['total']} "
                         f"bytes/step (all-gather="
                         f"{self.collectives['all-gather']}, all-reduce="
                         f"{self.collectives['all-reduce']})")
            if c["mode"] == "stale":
                lines.append(
                    f"  stale comm   "
                    f"{self.collectives.get('per_epoch_avg', 0)} bytes/epoch "
                    f"avg ({self.collectives.get('n_exchange_epochs', 0)}/"
                    f"{c['epochs']} exchange epochs, between-exchange step="
                    f"{self.collectives.get('stale_step_total', 0)} bytes)")
        if self.accuracy:
            lines.append(f"  accuracy     train={self.accuracy['train']:.3f} "
                         f"val={self.accuracy['val']:.3f} "
                         f"test={self.accuracy['test']:.3f}")
        if self.checkpoint_path:
            lines.append(f"  checkpoint   {self.checkpoint_path}")
        if self.serving_path:
            lines.append(f"  serving      {self.serving_path}")
        t = self.timings
        lines.append("  timings      " + " ".join(
            f"{k}={v:.2f}s" for k, v in t.items()))
        return "\n".join(lines)


class Pipeline:
    """Orchestrates partition -> train -> assemble -> eval.

    ``store``/``mesh`` may be injected (the benchmarks share one store across
    every grid point); otherwise they are derived from the config /
    ``repro.launch.mesh``.
    """

    def __init__(self, config: PipelineConfig,
                 store: Optional[PartitionArtifactStore] = None,
                 mesh=None):
        self.config = config
        if store is None and config.cache_dir:
            store = PartitionArtifactStore(config.cache_dir)
        self.store = store
        self.mesh = mesh

    # ------------------------------------------------------------------
    def _resolve_mesh(self, k: int):
        """Mesh for the train step, from repro.launch when not injected."""
        import jax
        from repro.launch.mesh import make_local_mesh
        mesh = self.mesh
        if self.config.mode == "local" and not self.config.shard_data_axis:
            return None
        if mesh is None:
            mesh = make_local_mesh()
        data = int(mesh.shape["data"])
        if self.config.mode in ("sync", "stale"):
            return mesh          # train_sync/train_stale validate data == k
        if k % data != 0:
            log.warning("k=%d not divisible by mesh data axis %d — "
                        "running unsharded", k, data)
            return None
        return mesh
    # ------------------------------------------------------------------
    def run(self, ds: Optional[NodeDataset] = None) -> PipelineReport:
        cfg = self.config
        if cfg.mode not in ("local", "sync", "stale"):
            raise ValueError(
                f"mode must be local|sync|stale, got {cfg.mode!r}")
        if cfg.k < 1:
            raise ValueError(f"k must be >= 1, got {cfg.k}")
        if cfg.sync_period < 0:
            raise ValueError(
                f"sync_period must be >= 0 (0 = never exchange), "
                f"got {cfg.sync_period}")
        if cfg.integrate not in INTEGRATION_KINDS:
            raise ValueError(
                f"integrate must be one of {INTEGRATION_KINDS}, "
                f"got {cfg.integrate!r}")
        if cfg.serving_dir and cfg.classifier_epochs <= 0:
            raise ValueError(
                "serving_dir requires the classifier stage "
                "(classifier_epochs > 0): the serving bundle carries the "
                "trained classifier and its offline answer key")
        # resolve the partitioner spec up front: a bad method string fails
        # here, before any dataset/partition work happens
        spec = PartitionerSpec.parse(cfg.method)
        scheme = cfg.scheme
        if cfg.mode in ("sync", "stale") and scheme != "repli":
            log.info("%s mode requires halo replicas — forcing "
                     "scheme=repli (was %s)", cfg.mode, scheme)
            scheme = "repli"
        timings: Dict[str, float] = {}
        with _stage_span(timings, "total", "pipeline.total",
                         dataset=cfg.dataset, mode=cfg.mode, k=cfg.k):
            fields = self._run_stages(ds, spec, scheme, timings)
        obs.sample_memory_now()
        fields["timings"] = {k: round(v, 4) for k, v in timings.items()}
        return PipelineReport(**fields)

    # ------------------------------------------------------------------
    def _run_stages(self, ds: Optional[NodeDataset], spec: PartitionerSpec,
                    scheme: str, timings: Dict[str, float]) -> Dict[str, Any]:
        import jax
        cfg = self.config

        # -- stage 1: dataset ------------------------------------------
        with _stage_span(timings, "dataset", "pipeline.dataset",
                         dataset=cfg.dataset):
            if ds is None:
                ds = get_dataset(cfg.dataset, **dict(cfg.dataset_kwargs))
        obs.sample_memory_now()

        # -- stage 2: partition + assembly (load-or-compute) -----------
        need_halo = cfg.mode in ("sync", "stale")
        with _stage_span(timings, "partition_stage", "pipeline.partition",
                         method=spec.canonical(), k=cfg.k,
                         scheme=scheme) as psp:
            if self.store is not None:
                bundle = self.store.load_or_compute(
                    ds.graph, spec, cfg.k, cfg.seed, scheme,
                    with_halo=need_halo)
            else:
                bundle = compute_bundle(ds.graph, spec, cfg.k, cfg.seed,
                                        scheme, with_halo=need_halo)
            timings["partition"] = bundle.partition_seconds
            timings["assemble"] = bundle.assemble_seconds
            psp.set(cache_hit=bundle.labels_hit)
            with obs.span("pipeline.partition_eval"):
                part_report = evaluate_partition(
                    ds.graph, bundle.labels).as_dict()
        obs.sample_memory_now()

        # -- stage 3: per-partition GNN training -----------------------
        with _stage_span(timings, "train", "pipeline.train", mode=cfg.mode,
                         epochs=cfg.epochs, model=cfg.model, k=cfg.k):
            gnn_cfg = GNNConfig(kind=cfg.model,
                                feature_dim=int(ds.features.shape[1]),
                                hidden_dim=cfg.hidden_dim,
                                embed_dim=cfg.embed_dim,
                                num_layers=cfg.num_layers,
                                dropout=cfg.dropout,
                                use_kernel=cfg.use_kernel,
                                heads=cfg.heads)
            # kernel config resolution/tuning: one bucket per distinct
            # aggregated width at this run's padded partition shape
            # (DESIGN.md §14): a GCN/SAGE layer aggregates its input, a GAT
            # layer each head's projected rows
            kernel_info: Optional[Dict[str, Any]] = None
            if cfg.use_kernel:
                from repro.kernels.autotune import autotune as tune_bucket
                from repro.kernels.autotune import get_config
                n_pad, e_pad = bundle.batch.n_pad, bundle.batch.e_pad
                widths = sorted({gnn_cfg.hidden_dim, gnn_cfg.embed_dim}
                                if gnn_cfg.kind == "gat" else
                                {gnn_cfg.feature_dim, gnn_cfg.hidden_dim})
                if cfg.kernel_autotune:
                    with _stage_span(timings, "kernel_autotune",
                                     "pipeline.kernel_autotune",
                                     widths=widths):
                        for width in widths:
                            chosen, measured = tune_bucket(n_pad, e_pad,
                                                           width)
                            log.info("kernel autotune f=%d -> %s "
                                     "(%d candidates)", width, chosen,
                                     len(measured))
                kernel_info = {
                    f"f{width}": get_config(n_pad, e_pad, width).as_dict()
                    for width in widths}
            mesh = self._resolve_mesh(bundle.batch.k)
            low_memory = cfg.low_memory and cfg.mode == "local"
            if low_memory:
                mesh = None       # sequential path is inherently unsharded
            hlo_out: Optional[Dict[str, str]] = (
                {} if cfg.collect_hlo and not low_memory else None)
            with obs.profiler_session(cfg.jax_profile_dir):
                if cfg.mode == "local":
                    params, embeddings = train_local(
                        ds, bundle.batch, gnn_cfg, epochs=cfg.epochs,
                        lr=cfg.lr, seed=cfg.seed, mesh=mesh,
                        hlo_out=hlo_out, integrate=cfg.integrate,
                        sequential=low_memory)
                elif cfg.mode == "sync":
                    params, embeddings = train_sync(
                        ds, bundle.batch, bundle.halo, gnn_cfg, mesh,
                        epochs=cfg.epochs, lr=cfg.lr, seed=cfg.seed,
                        hlo_out=hlo_out, integrate=cfg.integrate)
                else:
                    params, embeddings = train_stale(
                        ds, bundle.batch, bundle.halo, gnn_cfg, mesh,
                        epochs=cfg.epochs, lr=cfg.lr, seed=cfg.seed,
                        sync_period=cfg.sync_period, hlo_out=hlo_out,
                        integrate=cfg.integrate)
        obs.sample_memory_now()
        shapes = {"k": bundle.batch.k, "n_pad": bundle.batch.n_pad,
                  "e_pad": bundle.batch.e_pad}
        if cfg.mode == "local":
            for name in ("partition_group", "step_bytes_estimate"):
                shapes[name] = int(obs.gauge(f"train.{name}").value)

        collectives: Dict[str, int] = {}
        if hlo_out:
            from repro.launch.hlo_analysis import collective_bytes
            collectives = collective_bytes(hlo_out["hlo"])
            # per-epoch average: what one training epoch actually moves.
            # local: 0; sync: every epoch is an exchange; stale: only every
            # sync_period-th epoch moves the exchange-step bytes.
            if cfg.mode == "stale":
                per_epoch = stale_bytes_per_epoch(
                    collectives["total"], cfg.epochs, cfg.sync_period)
                stale_hlo = hlo_out.get("hlo_stale")
                collectives["stale_step_total"] = (
                    collective_bytes(stale_hlo)["total"] if stale_hlo else 0)
                collectives["n_exchange_epochs"] = len(
                    stale_exchange_epochs(cfg.epochs, cfg.sync_period))
                collectives["per_epoch_avg"] = int(round(
                    sum(per_epoch) / max(cfg.epochs, 1)))
            else:
                collectives["per_epoch_avg"] = collectives["total"]
            # reconcile the HLO byte count with the registry: gauges carry
            # the same numbers the report does, so a trace is self-contained
            obs.gauge("train.collective_bytes_per_step").set(
                collectives["total"])
            obs.gauge("train.collective_bytes_per_epoch_avg").set(
                collectives["per_epoch_avg"])
            log.info("train-step collectives: %d bytes/step, %d bytes/epoch "
                     "avg (mode=%s)", collectives["total"],
                     collectives["per_epoch_avg"], cfg.mode)

        # -- stage 4: classifier on assembled embeddings ---------------
        accuracy: Dict[str, float] = {}
        classifier_params = None
        if cfg.classifier_epochs > 0:
            with _stage_span(timings, "classifier", "pipeline.classifier",
                             epochs=cfg.classifier_epochs):
                accuracy, classifier_params = train_classifier(
                    ds, embeddings, hidden=cfg.classifier_hidden,
                    epochs=cfg.classifier_epochs, seed=cfg.seed,
                    return_params=True)

        # -- stage 5: optional checkpoint ------------------------------
        checkpoint_path = None
        if cfg.checkpoint_dir:
            from repro.checkpoint import save_checkpoint
            checkpoint_path = save_checkpoint(cfg.checkpoint_dir,
                                              cfg.epochs, params)
            log.info("saved model checkpoint: %s", checkpoint_path)

        # -- stage 6: serving bundle export (DESIGN.md §13) ------------
        serving_path = None
        if cfg.serving_dir:
            # lazy import: repro.serving imports repro.gnn/pipeline pieces
            from repro.serving.store import export_from_pipeline
            with _stage_span(timings, "serving_export",
                             "pipeline.serving_export"):
                serving_path = export_from_pipeline(
                    cfg.serving_dir, ds=ds, bundle=bundle, params=params,
                    classifier=classifier_params, embeddings=embeddings)
            log.info("exported serving bundle: %s", serving_path)

        src_once = ds.graph.num_arcs // 2
        return dict(
            config={**dataclasses.asdict(cfg), "scheme": scheme,
                    "method": spec.canonical(),
                    "dataset_kwargs": dict(cfg.dataset_kwargs)},
            dataset=ds.name,
            num_nodes=int(ds.graph.n),
            num_edges=int(src_once),
            num_devices=len(jax.devices()),
            partition=part_report,
            partition_cache_hit=bundle.labels_hit,
            batch_cache_hit=bundle.batch_hit,
            artifact_paths={"labels": bundle.labels_path,
                            "batch": bundle.batch_path},
            shapes=shapes,
            collectives=collectives,
            accuracy={k: float(v) for k, v in accuracy.items()},
            checkpoint_path=checkpoint_path,
            partition_fingerprint=bundle.fingerprint or spec.fingerprint(),
            serving_path=serving_path,
            kernel=kernel_info,
        )
