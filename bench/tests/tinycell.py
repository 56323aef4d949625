"""A tiny cell written into a scratch checkout, for runs of the harness on
the CPU: the real BENCHMARK.json's metrics, readers and model modules, a
600-node graph and a 16-wide GCN."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

TINY = {"dataset": "arxiv-like",
        "dataset_kwargs": {"n": 600, "feature_dim": 8, "num_classes": 4,
                           "seed": 3},
        "num_nodes": 600, "feature_dim": 8, "num_classes": 4,
        "model": "gcn", "num_layers": 3, "hidden_dim": 16, "embed_dim": 16,
        "dropout": 0.5, "lr": 0.01}


def write_root(tmp: str, mode: str = "local", use_kernel: bool = False,
               k: int = 4, limits_from: str = "arxiv-gcn-pallas.k8-local",
               config: dict = None) -> str:
    """A checkout under ``tmp`` holding one cell named ``tiny.<mode>``;
    returns the cell's name. Limits are the real cell's; ``config`` adds
    to or overrides the tiny configuration's keys."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = f"tiny.{mode}"
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": cell, "config": "tiny", "traffic": mode,
                          "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    bench = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "models"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    json.dump(spec, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    precision = {"storage": "float32", "head_product": "default",
                 "body_products": "highest" if use_kernel else "default"}
    json.dump({**TINY, "use_kernel": use_kernel, "precision": precision,
               **(config or {})},
              open(os.path.join(bench, "configs", "tiny.json"), "w"))
    json.dump({"k": k, "mode": mode, "scheme": "repli",
               "partitioner": "leiden_fusion", "partition_seed": 0,
               "epochs_per_call": 2, "check_steps": 3},
              open(os.path.join(bench, "traffic", mode + ".json"), "w"))
    shutil.copy(os.path.join(BENCH, "limits", limits_from + ".json"),
                os.path.join(bench, "limits", cell + ".json"))
    return cell
