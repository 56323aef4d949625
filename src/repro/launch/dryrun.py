import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.
"""Multi-pod dry-run: lower + compile every (arch × input-shape) combination
on the production meshes, prove it fits and shards, and extract the roofline
terms from the compiled artifact.

Usage:
    python -m repro.launch.dryrun --arch qwen3_4b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--mode fsdp_tp]
    python -m repro.launch.dryrun --gnn            # the paper's own workload

Artifacts land in benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>__<mode>.json
"""
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np


ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../benchmarks/artifacts/dryrun")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _compile_combo(cfg, shape_name, mesh, mode, fast: bool = False,
                   shape_override=None):
    """lower+compile one config; returns (compiled, lower_s, compile_s).

    ``fast`` compiles at backend optimization level 0 — used for the shallow
    cost-model lowerings only (cost_analysis numbers are identical; verified
    flops/hbm/collective bytes match the default pipeline bit-for-bit)."""
    from repro.launch.steps import build
    t0 = time.time()
    with mesh:
        fn, args_sds = build(cfg, shape_name, mesh, mode=mode,
                             shape_override=shape_override)
        lowered = fn.lower(*args_sds)
        t_lower = time.time() - t0
        opts = ({"xla_backend_optimization_level": 0} if fast else None)
        compiled = lowered.compile(compiler_options=opts)
    return compiled, t_lower, time.time() - t0 - t_lower


def _cost_terms(compiled) -> dict:
    from repro.launch.hlo_analysis import (collective_bytes,
                                           normalize_cost_analysis)
    ca = normalize_cost_analysis(compiled.cost_analysis())
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "hbm": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]), "coll_detail": coll}


def _depth_pair(cfg) -> tuple:
    """Two reduced depths (same block-pattern period) for the linear
    extrapolation flops(L) = a + b*L. See module docstring of
    repro.models.config (unroll) for why trip counts need this."""
    period = max(len(cfg.block_pattern), 1)
    base = max(cfg.first_k_dense, 0)
    l1 = base + period
    l2 = base + 2 * period
    return l1, l2


def _depth_extrapolate(cfg, shape_name, mesh, mode, shape_override=None):
    """term(L) = a + b*L from two shallow unrolled lowerings."""
    import dataclasses as dc
    l1, l2 = _depth_pair(cfg)
    enc_scale = cfg.encoder_layers / max(cfg.num_layers, 1)
    samples = {}
    for li in (l1, l2):
        c = dc.replace(cfg, num_layers=li, scan_layers=False, unroll=True,
                       encoder_layers=int(round(enc_scale * li)))
        compiled, _, _ = _compile_combo(c, shape_name, mesh, mode, fast=True,
                                        shape_override=shape_override)
        samples[li] = _cost_terms(compiled)
    full = cfg.num_layers
    out = {}
    for key in ("flops", "hbm", "coll"):
        y1, y2 = samples[l1][key], samples[l2][key]
        b = (y2 - y1) / (l2 - l1)
        out[key] = y1 + b * (full - l1)
    out["samples"] = {str(k): {kk: v[kk] for kk in ("flops", "hbm", "coll")}
                      for k, v in samples.items()}
    out["coll_detail_shallow"] = samples[l2]["coll_detail"]
    return out


def extrapolated_costs(cfg, shape_name, mesh, mode) -> dict:
    """Cost terms at full depth (and, for long-sequence heterogeneous archs,
    full sequence) from shallow UNROLLED lowerings.

    XLA's HloCostAnalysis counts while-loop bodies once, so the scanned
    full-depth module undercounts by ~num_layers. We lower the same config
    at depths L1 < L2 with every chunk loop unrolled and fit
    term(L) = a + b*L (exact for repeated identical layers).

    For block-pattern archs (zamba2/xlstm) at train/prefill seq >= 8k the
    unrolled chunk loops would produce intractable HLO (S/chunk * L chunk
    bodies), so we additionally sample three shorter sequences and fit the
    exact quadratic term(S) = a + b*S + c*S^2 (costs are polynomial in S:
    linear SSD chunk terms + quadratic attention) — both fits are exact for
    deterministic cost models, not statistical estimates."""
    import dataclasses as dc
    from repro.models.inputs import SHAPES, InputShape
    shape = SHAPES[shape_name]
    needs_seq_fit = (cfg.block_pattern and shape.kind in ("train", "prefill")
                     and shape.seq_len >= 8192)
    if not needs_seq_fit:
        return _depth_extrapolate(cfg, shape_name, mesh, mode)
    s_pts = (1024, 2048, 4096)
    fits = {}
    for s in s_pts:
        ov = InputShape(shape.name, s, shape.global_batch, shape.kind)
        fits[s] = _depth_extrapolate(cfg, shape_name, mesh, mode,
                                     shape_override=ov)
    out = {}
    for key in ("flops", "hbm", "coll"):
        ys = [fits[s][key] for s in s_pts]
        # exact quadratic through 3 points
        coef = np.polyfit(np.array(s_pts, dtype=np.float64), ys, 2)
        out[key] = float(np.polyval(coef, shape.seq_len))
    out["samples"] = {f"S{s}": fits[s]["samples"] for s in s_pts}
    out["coll_detail_shallow"] = fits[s_pts[-1]]["coll_detail_shallow"]
    out["seq_fit"] = True
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool, mode: str,
            out_dir: str, verbose: bool = True,
            accurate: bool | None = None, tag: str = "",
            cfg_transform=None) -> dict:
    """Full-depth scanned lower+compile proves the combo shards and fits
    (memory_analysis); cost terms come from the depth-extrapolated unrolled
    lowerings when ``accurate`` (default on the single-pod mesh)."""
    from repro.configs import get_config
    from repro.launch.hlo_analysis import collective_bytes, roofline_terms
    from repro.launch.mesh import make_production_mesh
    from repro.models import effective_config
    from repro.models.inputs import SHAPES

    if accurate is None:
        accurate = not multi_pod
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    eff = effective_config(cfg, shape_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    record = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
        "mode": mode, "chips": chips, "kind": shape.kind,
        "attention_variant": eff.attention, "accurate_costs": accurate,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    try:
        compiled, t_lower, t_compile = _compile_combo(cfg, shape_name, mesh,
                                                      mode)
        # ---- memory (full-depth module: while-loop buffers are real) ------
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
        except Exception as e:                                # noqa: BLE001
            mem = {"error": str(e)}
        # ---- cost terms ----------------------------------------------------
        if accurate:
            costs = extrapolated_costs(cfg, shape_name, mesh, mode)
            flops, hbm, coll_total = costs["flops"], costs["hbm"], costs["coll"]
            record["cost_extrapolation"] = costs["samples"]
            record["collectives"] = costs["coll_detail_shallow"]
        else:
            terms0 = _cost_terms(compiled)
            flops, hbm, coll_total = (terms0["flops"], terms0["hbm"],
                                      terms0["coll"])
            record["collectives"] = terms0["coll_detail"]
        # ---- roofline ------------------------------------------------------
        terms = roofline_terms(flops, hbm, coll_total, chips)
        n_act = cfg.active_param_count()
        tokens = shape.global_batch * (shape.seq_len if shape.kind in
                                       ("train", "prefill") else 1)
        mf_mult = 6 if shape.kind == "train" else 2
        model_flops = mf_mult * n_act * tokens
        flops_global = flops * chips
        record.update({
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "memory": mem, "flops_per_device": flops,
            "hbm_bytes_per_device": hbm, "collective_bytes": coll_total,
            "roofline": terms,
            "model_flops": model_flops,
            "useful_flops_frac": (model_flops / flops_global
                                  if flops_global else None),
            "ok": True,
        })
    except Exception as e:                                    # noqa: BLE001
        record.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]})
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{_mesh_tag(multi_pod)}__{mode}{tag}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1, default=str)
    if verbose:
        status = "OK " if record["ok"] else "FAIL"
        extra = ""
        if record["ok"]:
            r = record["roofline"]
            extra = (f"compute={r['compute_s']:.2e}s "
                     f"mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s"
                     f" dom={r['dominant']}")
        else:
            extra = record["error"][:160]
        print(f"[{status}] {arch:24s} {shape_name:12s} "
              f"{_mesh_tag(multi_pod):10s} {mode:7s} {extra}", flush=True)
    return record


def run_gnn_dryrun(multi_pod: bool, out_dir: str) -> dict:
    """The paper's own workload on the production mesh: one partition per
    chip, (a) LF local training — must be ZERO collectives — (b) the
    synchronized halo-exchange baseline — whose collective bytes quantify
    exactly the traffic the paper eliminates — and (c) the stale(period=N)
    middle ground: its exchange step moves the sync bytes, its
    between-exchange step must lower to zero (DESIGN.md §12)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (make_arxiv_like, leiden_fusion,
                            build_partition_batch, build_halo_exchange)
    from repro.gnn import (GNNConfig, gather_partition_tensors,
                           init_partition_models, make_local_train_step,
                           make_stale_train_steps, make_sync_train_step,
                           stale_bytes_per_epoch)
    from repro.launch.hlo_analysis import (collective_bytes,
                                           normalize_cost_analysis)
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.optim import adamw_init

    mesh = make_production_mesh(multi_pod=multi_pod)
    k = int(np.prod(list(mesh.shape.values())))    # one partition per chip
    ds = make_arxiv_like(n=4096, feature_dim=128, seed=5)
    base_k = min(k, 64)
    labels = leiden_fusion(ds.graph, base_k, alpha=0.3)
    # build a k-partition batch by tiling (structure identical per partition)
    batch = build_partition_batch(ds.graph, labels, scheme="repli")
    halo = build_halo_exchange(ds.graph, labels, batch)
    reps = (k + batch.k - 1) // batch.k
    import dataclasses as dc
    tile = lambda a: np.concatenate([a] * reps, 0)[:k]
    batch = dc.replace(batch, node_ids=tile(batch.node_ids),
                       node_mask=tile(batch.node_mask),
                       owned_mask=tile(batch.owned_mask),
                       edge_src=tile(batch.edge_src),
                       edge_dst=tile(batch.edge_dst),
                       edge_weight=tile(batch.edge_weight),
                       in_degree=tile(batch.in_degree))
    # halo plan tiled to k partitions (peer indices stay within each block of
    # base_k partitions; good enough for a traffic-volume dry-run)
    halo_send = np.zeros((k, k, halo.h_pad), np.int32) - 1
    halo_recv = np.zeros((k, k, halo.h_pad), np.int32) - 1
    for r in range(reps):
        o = r * base_k
        if o + base_k <= k:
            halo_send[o:o + base_k, o:o + base_k] = halo.send_rows
            halo_recv[o:o + base_k, o:o + base_k] = halo.recv_rows
    halo = dc.replace(halo, send_rows=halo_send, recv_rows=halo_recv)
    pt = gather_partition_tensors(ds, batch)
    cfg = GNNConfig(kind="gcn", feature_dim=128, hidden_dim=256,
                    embed_dim=256, num_layers=3, dropout=0.0)
    p_sds = jax.eval_shape(
        lambda key: init_partition_models(key, cfg, ds.num_classes, k),
        jax.random.PRNGKey(0))
    o_sds = jax.eval_shape(lambda p: jax.vmap(adamw_init)(p), p_sds)
    tensors_sds = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for n, v in {
                       "features": pt.features, "labels": pt.labels,
                       "train_mask": pt.train_mask, "edge_src": pt.edge_src,
                       "edge_dst": pt.edge_dst,
                       "edge_weight": pt.edge_weight,
                       "in_degree": pt.in_degree,
                       "node_mask": pt.node_mask}.items()}
    keys_sds = jax.ShapeDtypeStruct((k, 2), jnp.uint32)
    daxes = ("pod", "data") if multi_pod else ("data",)
    shard = NamedSharding(mesh, P(daxes))
    sh_tree = lambda t: jax.tree.map(lambda _: shard, t)
    record = {"workload": "gnn_lf_local", "mesh": _mesh_tag(multi_pod),
              "k_partitions": k, "n_pad": batch.n_pad, "e_pad": batch.e_pad,
              "halo_rows": int(halo.h_pad)}
    with mesh:
        step = jax.jit(make_local_train_step(cfg, False, 1e-2),
                       in_shardings=(sh_tree(p_sds), sh_tree(o_sds),
                                     sh_tree(tensors_sds), shard),
                       out_shardings=(sh_tree(p_sds), sh_tree(o_sds), shard))
        compiled = step.lower(p_sds, o_sds, tensors_sds, keys_sds).compile()
    coll = collective_bytes(compiled.as_text())
    ca = normalize_cost_analysis(compiled.cost_analysis())
    record.update({
        "collectives": coll,
        "flops_per_device": float(ca.get("flops", 0.0)),
        "zero_collectives": coll["total"] == 0,
        "ok": True,
    })
    # --- synchronized halo-exchange baseline (single-axis mesh only: the
    # shard_map step uses a flat "data" axis) ---------------------------------
    if not multi_pod:
        sync_mesh = make_mesh((k,), ("data",))
        with sync_mesh:
            sync = make_sync_train_step(cfg, halo, False, sync_mesh, 1e-2)
            sync_compiled = sync.lower(p_sds, o_sds, tensors_sds,
                                       keys_sds).compile()
        sync_coll = collective_bytes(sync_compiled.as_text())
        record["sync_baseline_collectives"] = sync_coll
        record["communication_eliminated_bytes"] = sync_coll["total"]
        # --- stale(period=N): exchange step should match the sync traffic,
        # the between-exchange step must be collective-free -----------------
        from repro.gnn.train import _stale_cache_shapes
        with sync_mesh:
            steps = make_stale_train_steps(cfg, halo, False, sync_mesh, 1e-2)
            ex_compiled = steps["exchange"].lower(
                p_sds, o_sds, tensors_sds, keys_sds).compile()
            caches_sds = tuple(
                jax.ShapeDtypeStruct((k,) + s, jnp.float32)
                for s in _stale_cache_shapes(cfg, batch.n_pad))
            st_compiled = steps["stale"].lower(
                p_sds, o_sds, tensors_sds, keys_sds, caches_sds).compile()
        ex_coll = collective_bytes(ex_compiled.as_text())
        st_coll = collective_bytes(st_compiled.as_text())
        record["stale_exchange_collectives"] = ex_coll
        record["stale_step_collectives"] = st_coll
        record["stale_step_zero_collectives"] = st_coll["total"] == 0
        # the comm-vs-staleness frontier this mesh would see over 16 epochs
        record["stale_frontier_bytes_per_epoch"] = {
            str(p): int(np.mean(
                stale_bytes_per_epoch(ex_coll["total"], 16, p)))
            for p in (1, 2, 4, 8, 16)}
        # fair point-to-point lower bound (the all-gather implementation
        # over-fetches): actual halo rows x feature bytes x layers x fwd+bwd
        real_rows = int((halo_send >= 0).sum())
        record["halo_p2p_bytes_analytic"] = (
            real_rows * cfg.hidden_dim * 4 * cfg.num_layers * 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"gnn_lf__{_mesh_tag(multi_pod)}.json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"[OK ] gnn_lf_local {_mesh_tag(multi_pod)} "
          f"zero_collectives={record['zero_collectives']} "
          f"sync_bytes={record.get('communication_eliminated_bytes')} "
          f"stale_step_zero={record.get('stale_step_zero_collectives')}",
          flush=True)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", type=str, default="dp_tp",
                    choices=["dp_tp", "fsdp_tp", "ddp_fsdp"])
    ap.add_argument("--gnn", action="store_true")
    ap.add_argument("--out", type=str, default=ARTIFACT_DIR)
    args = ap.parse_args()

    from repro.configs import ARCH_IDS
    from repro.models.inputs import SHAPES

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    if args.gnn:
        for mp in meshes:
            run_gnn_dryrun(mp, args.out)
        return 0
    if args.all:
        combos = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]
    for mp in meshes:
        for arch, shape in combos:
            rec = run_one(arch, shape, mp, args.mode, args.out)
            failures += 0 if rec["ok"] else 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
