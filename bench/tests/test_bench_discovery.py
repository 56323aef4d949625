"""A configuration, a traffic mix, a cell, a per-layer metric and a model
added as files and entries are found by name; nothing else is edited."""
import json
import os
import sys
import time

import pytest

import tinycell
from bench import harness


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_cell_found_by_name(tmp_path):
    root = str(tmp_path)
    spec = {
        "configs": [{"name": "m", "source": "x", "reduced": [], "why": "x",
                     "file": "bench/configs/m.json"}],
        "workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                       "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "e2e", "unit": "s"},
                       {"name": "other_e2e", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "x_pct", "unit": "%",
                       "workloads": ["m.t"]},
                      {"name": "y_pct", "unit": "%"},
                      {"name": "z_pct", "unit": "%",
                       "workloads": ["elsewhere"]}]}
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    _write(os.path.join(root, "bench/configs/m.json"), '{"hidden_dim": 7}')
    _write(os.path.join(root, "bench/traffic/t.json"), '{"k": 3}')
    _write(os.path.join(root, "bench/limits/m.t.json"),
           '{"loss_gap": {"limit": 1}}')
    _write(os.path.join(root, "bench/metrics/x_pct.py"),
           "def read(ctx):\n    return 42.0\n")
    _write(os.path.join(root, "bench/metrics/y_pct.py"),
           "def read(ctx):\n    return None\n")
    cell = harness.load_cell("m.t", root=root)
    assert cell.config == {"hidden_dim": 7}
    assert cell.traffic == {"k": 3}
    assert cell.limits == {"loss_gap": {"limit": 1}}
    assert [m["name"] for m in cell.end_to_end] == ["e2e"]
    assert [m["name"] for m in cell.per_layer] == ["x_pct", "y_pct"]
    assert harness.load_reader(cell, "x_pct")(None) == 42.0
    assert harness.load_reader(cell, "y_pct")(None) is None
    with pytest.raises(KeyError):
        harness.load_cell("m.absent", root=root)


def test_real_benchmark_parts_exist():
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert callable(harness.load_model(cell).forward)
        for m in cell.per_layer:
            assert callable(harness.load_reader(cell, m["name"]))
        assert set(harness.NUMBERS) <= set(cell.limits)


def test_unknown_device_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


# GraphSAGE with the mean aggregator (the program's "sage" layer), under
# widths of its own ("width", "depth"), as a model module a checkout adds;
# with SWAP its reference crosses the self and neighbour weights.
TOY = """
import jax
import jax.numpy as jnp

from bench import reference

SWAP = {swap}


def program_config(config, feature_dim):
    from repro.gnn import GNNConfig
    return GNNConfig(kind="sage", feature_dim=feature_dim,
                     hidden_dim=config["width"], embed_dim=config["width"],
                     num_layers=config["depth"], dropout=config["dropout"],
                     use_kernel=False)


def _widths(config, feature_dim):
    dims = [feature_dim] + [config["width"]] * config["depth"]
    return list(zip(dims[:-1], dims[1:]))


def init_params(seed, m, k):
    widths = _widths(m.config, m.feature_dim)
    embed = widths[-1][1]

    def one(key):
        kb, kh = jax.random.split(key)
        layers = []
        for lk, (fi, fo) in zip(jax.random.split(kb, len(widths)), widths):
            k1, k2 = jax.random.split(lk)
            s = jnp.sqrt(2.0 / fi)
            layers.append({{
                "w_self": jax.random.normal(k1, (fi, fo), jnp.float32) * s,
                "w_neigh": jax.random.normal(k2, (fi, fo), jnp.float32) * s,
                "b": jnp.zeros((fo,), jnp.float32)}})
        head = {{"w": jax.random.normal(kh, (embed, m.num_classes))
                 * jnp.sqrt(2.0 / embed),
                 "b": jnp.zeros((m.num_classes,))}}
        return {{"body": {{"layers": layers}}, "head": head}}
    return jax.vmap(one)(jax.random.split(jax.random.PRNGKey(seed), k))


def _mean(h, s, d, w, deg):
    tot = jax.ops.segment_sum(h[s] * w[:, None], d, num_segments=h.shape[0])
    return tot / jnp.maximum(deg, 1.0)[:, None]


def forward(params, m, t, keys, ar):
    mask = t["mask"][..., None]
    h = t["x"] * mask
    layers = params["body"]["layers"]
    for i, lp in enumerate(layers):
        last = i == len(layers) - 1
        if m.sync:
            h = reference.refresh(h, t)
        agg = jax.vmap(_mean)(h, t["src"], t["dst"], t["w"], t["deg"])
        own, other = (agg, h) if SWAP else (h, agg)
        z = (reference.product("knf,kfo->kno", own, lp["w_self"], ar.body, ar)
             + reference.product("knf,kfo->kno", other, lp["w_neigh"],
                                 ar.body, ar)
             + lp["b"][:, None, :])
        h = z if last else jax.nn.relu(z)
        h = h * mask
        if not last:
            h, keys = reference.dropout(h, keys, m.dropout)
    return h, reference.head_logits(params["head"], h, ar)


def flops_per_epoch(config, nodes, arcs, num_classes):
    return 1.0


def aggregation_least_work(config, nodes, arcs, backward):
    return [(1.0, 1.0)]
"""


@pytest.mark.parametrize("swap", [False, True])
def test_new_model_found_built_and_checked(tmp_path, monkeypatch, swap):
    root = str(tmp_path)
    name = tinycell.write_root(root, config={"model": "toy", "width": 12,
                                             "depth": 2})
    _write(os.path.join(root, "bench/models/toy.py"), TOY.format(swap=swap))
    # harness.run points the program's autotune cache into its cache and
    # puts the checkout's src on sys.path; both are restored after
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    monkeypatch.setattr(sys, "path", list(sys.path))
    import jax
    cell = harness.load_cell(name, root=root)
    model = harness.load_model(cell)
    assert model.program_config(cell.config, 8).kind == "sage"
    res = harness.run(cell, 2**31 + 11, 0.0, False, time.perf_counter(),
                      cache=os.path.join(root, "cache"),
                      devices=jax.devices())
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] == (not swap), res["checks"]
    metrics = res["metrics"]
    assert set(metrics) == {m["name"] for m in cell.end_to_end}
    assert metrics["peak_hbm_gib.sync"] == metrics["peak_hbm_gib"]


def test_unknown_model_names_its_file(tmp_path):
    root = str(tmp_path)
    name = tinycell.write_root(root, config={"model": "absent"})
    cell = harness.load_cell(name, root=root)
    with pytest.raises(KeyError, match=r"bench/models/absent\.py"):
        harness.load_model(cell)
