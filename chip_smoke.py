#!/usr/bin/env python3
"""Bring-up check: the Leiden-Fusion pipeline end to end on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip (phases 1-5 below)
    python chip_smoke.py --chips 4    # four chips: the distributed phase only

One chip, at the published shape of ogbn-arxiv (169,343 nodes, 128
features, 40 classes; GCN hidden/embed 128, 3 layers), k=8 ``repli``:

1. the device JAX reports and the compile-cache directory;
2. the pipeline (partition, local training, classifier, serving export):
   zero collective bytes per step, a finite loss, and the peak memory of
   every device;
3. one local train step's loss and gradients on one real partition, on the
   TPU and on the CPU backend of this process;
4. the Pallas kernels: the pipeline again with ``use_kernel=True`` (the
   partition cache hits), and each Pallas strategy of the fused GCN layer,
   forward and gradient, against the jnp reference, with a Mosaic
   ``tpu_custom_call`` in every compiled program;
5. serving: the exported bundle replays Zipf queries (2% unseen nodes)
   with answers equal to the offline answer key and no steady-state
   recompile.

``--chips 4`` runs the paper's distributed layout at k=4 instead: ``local``
with one partition per chip against the same run on one chip, and the
``sync``/``stale(4)`` halo-exchange baselines.

Every check that fails raises, so the exit code is non-zero and no result
line is printed. The script refuses to run without a TPU: no CPU fallback.
Bulky outputs (partition cache, serving bundle, autotune cache) go to the
git-ignored ``.chip_smoke/`` of the checkout. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")

# ogbn-arxiv at its published shape (the paper's own dataset)
W1_NODES = 169_343
GNN = dict(model="gcn", hidden_dim=128, embed_dim=128, num_layers=3)
SERVING_QUERIES = 500

# TPU f32 matmuls run at default precision: one bf16 pass per product, so
# each operand is rounded to 8 mantissa bits (relative error <= 2^-9). The
# CPU backend multiplies in f32. Three GCN layers, the head and their
# backward pass compound that rounding to about 1e-3 relative; the bounds
# leave a factor of ~10 for the widest layers.
STEP_LOSS_RTOL = 5e-3
STEP_GRAD_REL_L2 = 2e-2
# The kernels and the reference both multiply at Precision.HIGHEST; what
# remains is f32 summation order over up to ~10^3 arcs per node.
KERNEL_OUT_REL_L2 = 1e-4
KERNEL_GRAD_REL_L2 = 1e-3
# One partition per chip and all four on one chip are the same math in
# differently compiled programs; Adam turns last-bit gradient differences
# near zero into sign flips of single updates over the epochs.
SHARDED_EMB_REL_L2 = 1e-2


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase(title: str) -> None:
    print(f"\n== {title}", flush=True)


# ---------------------------------------------------------------------------
def require_tpu():
    """Phase 1: the device, or exit before any result is printed."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"refusing to fall back", file=sys.stderr)
        sys.exit(1)
    return devices


def pipeline_config(**overrides):
    from repro.pipeline import PipelineConfig
    fields = dict(dataset="arxiv-like", dataset_kwargs={"n": W1_NODES},
                  k=8, mode="local", scheme="repli", epochs=5,
                  classifier_epochs=20, cache_dir=os.path.join(OUT, "parts"),
                  **GNN)
    fields.update(overrides)
    return PipelineConfig(**fields)


def run_pipeline(cfg, ds):
    """One Pipeline run; returns (report, last epoch's mean loss)."""
    from repro import obs
    from repro.pipeline import Pipeline
    obs.reset()
    obs.enable()                  # epoch spans record the realized loss
    t0 = time.perf_counter()
    report = Pipeline(cfg).run(ds)
    wall = time.perf_counter() - t0
    loss = obs.registry().snapshot(kinds=("gauge",))["train.loss"]["value"]
    obs.reset()
    print(report.summary())
    print(f"  wall {wall:.1f}s, last epoch loss {loss}")
    check(math.isfinite(loss), f"non-finite training loss {loss}")
    return report, loss


def device_peaks():
    import jax
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in jax.local_devices()]
    check(all(p > 0 for p in peaks), f"peak_bytes_in_use missing: {peaks}")
    return peaks


def layer_strategies(report, feature_dim: int):
    """The kernel strategy each GCN layer resolved to, by input width."""
    c = report.config
    dims = [feature_dim] + [c["hidden_dim"]] * (c["num_layers"] - 1)
    return [report.kernel[f"f{d}"]["strategy"] for d in dims]


def partition_tensors(ds, batch, p: int):
    import numpy as np
    from repro.gnn import gather_partition_tensors
    pt = gather_partition_tensors(ds, batch)
    names = ("features", "labels", "train_mask", "edge_src", "edge_dst",
             "edge_weight", "in_degree", "node_mask")
    return {n: np.asarray(getattr(pt, n)[p]) for n in names}


# ---------------------------------------------------------------------------
def step_agreement(ds, batch):
    """Phase 3: a train step's loss and grads, TPU against CPU."""
    import jax
    from repro.gnn import GNNConfig, init_partition_models
    from repro.gnn.train import _loss_one
    cfg = GNNConfig(kind="gcn", feature_dim=ds.features.shape[1],
                    hidden_dim=128, embed_dim=128, num_layers=3, dropout=0.3)
    t = partition_tensors(ds, batch, 0)
    params = jax.tree.map(lambda x: x[0], init_partition_models(
        jax.random.PRNGKey(0), cfg, ds.num_classes, 1))
    key = jax.random.PRNGKey(1)
    value_and_grad = jax.jit(jax.value_and_grad(_loss_one),
                             static_argnums=(1, 3))
    results = []
    for device in (jax.devices()[0], jax.devices("cpu")[0]):
        p, tt, kk = jax.device_put((params, t, key), device)
        loss, grads = value_and_grad(p, cfg, tt, False, kk)
        results.append((float(loss), jax.device_get(grads)))
    (loss_t, g_t), (loss_c, g_c) = results
    loss_err = abs(loss_t - loss_c) / abs(loss_c)
    grad_err = max(jax.tree.leaves(jax.tree.map(rel_l2, g_t, g_c)))
    print(f"  partition 0: n_pad={batch.n_pad} e_pad={batch.e_pad}")
    print(f"  loss tpu={loss_t:.7f} cpu={loss_c:.7f} rel_err={loss_err:.2e} "
          f"(bound {STEP_LOSS_RTOL})")
    print(f"  grads max leaf rel_l2={grad_err:.2e} (bound {STEP_GRAD_REL_L2})")
    check(loss_err <= STEP_LOSS_RTOL, f"loss disagrees: {loss_err}")
    check(grad_err <= STEP_GRAD_REL_L2, f"grads disagree: {grad_err}")
    return {"loss_rel_err": loss_err, "grad_rel_l2": grad_err}


def has_custom_call(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def kernel_parity(ds, batch):
    """Phase 4b: each Pallas strategy of the GCN layer against jnp."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.gnn.layers import gcn_layer
    from repro.kernels.autotune import KernelConfig, override
    from repro.kernels.fused_layer import fused_gcn_reference
    t = partition_tensors(ds, batch, 0)
    rng = np.random.default_rng(0)
    f = t["features"].shape[1]
    params = {"w": jnp.asarray(rng.normal(size=(f, 128)) * np.sqrt(2 / f),
                               jnp.float32),
              "b": jnp.asarray(rng.normal(size=(128,)) * 0.1, jnp.float32)}
    h = jnp.asarray(t["features"])
    arcs = tuple(jnp.asarray(t[n]) for n in
                 ("edge_src", "edge_dst", "edge_weight", "in_degree"))
    cot = jnp.asarray(rng.normal(size=(h.shape[0], 128)), jnp.float32)

    def reference(params, h, w_edge):
        src, dst, _, deg = arcs
        inv = 1.0 / jnp.maximum(deg, 1.0)
        with jax.default_matmul_precision("highest"):
            return fused_gcn_reference(h, src, dst, w_edge, inv,
                                       params["w"], params["b"])

    def kernel(params, h, w_edge):
        src, dst, _, deg = arcs
        return gcn_layer(params, h, src, dst, w_edge, deg, use_kernel=True)

    def grads_of(layer):
        return jax.grad(lambda p, h, w: jnp.vdot(layer(p, h, w), cot),
                        argnums=(0, 1, 2))

    args = (params, h, arcs[2])
    ref_out = jax.jit(reference)(*args)
    ref_grads = jax.jit(grads_of(reference))(*args)
    out = {}
    for strategy in ("pallas_fused", "pallas"):
        with override(KernelConfig(strategy=strategy)):
            fwd = jax.jit(kernel).lower(*args).compile()
            bwd = jax.jit(grads_of(kernel)).lower(*args).compile()
        check(has_custom_call(fwd) and has_custom_call(bwd),
              f"{strategy}: no tpu_custom_call in the compiled HLO")
        out_err = rel_l2(fwd(*args), ref_out)
        grad_err = max(jax.tree.leaves(jax.tree.map(
            rel_l2, bwd(*args), ref_grads)))
        print(f"  {strategy:12s} tpu_custom_call=yes out rel_l2={out_err:.2e}"
              f" (bound {KERNEL_OUT_REL_L2}) grads max rel_l2={grad_err:.2e}"
              f" (bound {KERNEL_GRAD_REL_L2})")
        check(out_err <= KERNEL_OUT_REL_L2, f"{strategy} output: {out_err}")
        check(grad_err <= KERNEL_GRAD_REL_L2, f"{strategy} grads: {grad_err}")
        out[strategy] = {"out_rel_l2": out_err, "grad_rel_l2": grad_err}
    return out


def serving_replay(bundle_path, use_kernel: bool):
    """Phase 5: replay Zipf traffic against the exported bundle."""
    from repro.serving.batcher import ContinuousBatcher
    from repro.serving.replay import make_zipf_workload, run_replay
    from repro.serving.store import EmbeddingStore
    store = EmbeddingStore.load(bundle_path)
    batcher = ContinuousBatcher(store, use_kernel=use_kernel)
    workload = make_zipf_workload(store.n, num_queries=SERVING_QUERIES,
                                  unseen_frac=0.02, seed=0)
    row = run_replay(batcher, workload, verify=True)
    exact = row["queries"] - row["label_mismatches"]
    print(f"  use_kernel={use_kernel}: {row['queries']} queries "
          f"{row['served_by_source']} exact-match {exact}/{row['queries']} "
          f"warm_compiles={row['warm_compiles']} "
          f"steady_state_recompiles={row['steady_state_recompiles']}")
    check(row["label_mismatches"] == 0, "served labels differ from the key")
    check(row["steady_state_recompiles"] == 0, "steady-state recompile")
    return row


# ---------------------------------------------------------------------------
def one_chip(ds) -> dict:
    import numpy as np
    from repro.pipeline import PartitionArtifactStore

    phase("2. W1 pipeline: k=8 repli, local, 5 epochs")
    cfg = pipeline_config(serving_dir=os.path.join(OUT, "serving"))
    report, loss = run_pipeline(cfg, ds)
    check(report.collectives["total"] == 0,
          f"local step moves {report.collectives['total']} bytes")
    check(all(np.isfinite(v) for v in report.accuracy.values()),
          f"accuracy {report.accuracy}")
    peaks = device_peaks()
    print(f"  peak_bytes_in_use per device: {peaks}")
    bundle = PartitionArtifactStore(cfg.cache_dir).load_or_compute(
        ds.graph, cfg.method, cfg.k, cfg.seed, cfg.scheme)

    phase("3. one local train step: TPU vs CPU backend")
    agreement = step_agreement(ds, bundle.batch)

    phase("4a. W1 pipeline with use_kernel=True")
    kreport, kloss = run_pipeline(pipeline_config(use_kernel=True), ds)
    check(kreport.partition_cache_hit, "partition cache missed")
    check(kreport.collectives["total"] == 0, "kernel local step moves bytes")
    strategies = layer_strategies(kreport, ds.features.shape[1])
    print(f"  resolved strategy per layer: {strategies}")

    phase("4b. fused GCN layer per Pallas strategy vs jnp reference")
    parity = kernel_parity(ds, bundle.batch)

    phase("5. serving replay of the exported bundle")
    rows = [serving_replay(report.serving_path, use_kernel=uk)
            for uk in (False, True)]

    phase("summary")
    summary = {
        "n_pad": report.shapes["n_pad"], "e_pad": report.shapes["e_pad"],
        "peak_bytes_in_use": peaks, "loss": loss, "kernel_loss": kloss,
        "accuracy": report.accuracy, "kernel_accuracy": kreport.accuracy,
        "layer_strategies": strategies, "step_agreement": agreement,
        "kernel_parity": parity,
        "serving_exact_match": [r["queries"] - r["label_mismatches"]
                                for r in rows],
        "serving_queries": SERVING_QUERIES,
        "timings": report.timings,
    }
    print(json.dumps(summary))
    return summary


def four_chips(ds, devices) -> dict:
    """The paper's distributed layout at k=4: local sharded one partition
    per chip (against all four on one chip) and the halo baselines."""
    import jax
    import numpy as np
    from repro.gnn import GNNConfig, train_local
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_local_mesh
    from repro.pipeline import PartitionArtifactStore
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    base = pipeline_config(k=4, epochs=4, sync_period=4, classifier_epochs=0)
    bytes_per_epoch = {}

    phase("f1. sync and stale(4): k=4 repli, one partition per chip")
    for mode in ("sync", "stale"):
        report, _ = run_pipeline(dataclasses.replace(base, mode=mode), ds)
        bytes_per_epoch[mode] = report.collectives["per_epoch_avg"]

    phase("f2. local: sharded one partition per chip vs one chip")
    bundle = PartitionArtifactStore(base.cache_dir).load_or_compute(
        ds.graph, base.method, base.k, base.seed, base.scheme,
        with_halo=True)
    gcfg = GNNConfig(kind="gcn", feature_dim=ds.features.shape[1],
                     hidden_dim=128, embed_dim=128, num_layers=3,
                     dropout=base.dropout)
    train = functools.partial(train_local, ds, bundle.batch, gcfg,
                              epochs=base.epochs, lr=base.lr, seed=base.seed)
    mesh = make_local_mesh()
    hlo = {}
    params, emb = train(mesh=mesh, hlo_out=hlo)
    bytes_per_epoch["local"] = collective_bytes(hlo["hlo"])["total"]
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        check(sorted(s.device.id for s in shards) ==
              sorted(d.id for d in devices),
              f"params not on every chip: {[s.device for s in shards]}")
        check(all(s.data.shape[0] == 1 for s in shards),
              f"a chip holds {[s.data.shape[0] for s in shards]} partitions")
    print(f"  every params leaf: 1 partition on each of {len(devices)} chips")
    _, emb1 = train()
    emb_err = rel_l2(emb, emb1)
    print(f"  embeddings sharded vs one chip rel_l2={emb_err:.2e} "
          f"(bound {SHARDED_EMB_REL_L2})")
    check(emb_err <= SHARDED_EMB_REL_L2, f"sharded embeddings: {emb_err}")
    check(np.isfinite(emb).all(), "non-finite embeddings")

    print(f"  collective bytes per epoch: {bytes_per_epoch}")
    check(bytes_per_epoch["local"] == 0
          < bytes_per_epoch["stale"] < bytes_per_epoch["sync"],
          f"expected local 0 < stale < sync: {bytes_per_epoch}")
    summary = {"n_pad": bundle.batch.n_pad, "e_pad": bundle.batch.e_pad,
               "collective_bytes_per_epoch": bytes_per_epoch,
               "sharded_vs_one_chip_rel_l2": emb_err,
               "peak_bytes_in_use": device_peaks()}
    phase("summary")
    print(json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip distributed phase")
    args = ap.parse_args(argv)

    # the CPU backend must exist next to the TPU for phase 3
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(OUT, "autotune.json")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    phase("1. device")
    devices = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.pipeline import get_dataset
    cache = enable_compile_cache()
    d0 = devices[0]
    print(f"  platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)

    t0 = time.perf_counter()
    ds = get_dataset("arxiv-like", n=W1_NODES)
    print(f"  dataset arxiv-like n={ds.graph.n} arcs={ds.graph.num_arcs} "
          f"features={ds.features.shape[1]} classes={ds.num_classes} "
          f"({time.perf_counter() - t0:.1f}s)")
    if args.chips == 4:
        four_chips(ds, devices)
    else:
        one_chip(ds)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
