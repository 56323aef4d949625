"""Pipeline subsystem tests: artifact store round-trip, load-or-compute
cache semantics, Pipeline orchestration, and a CLI smoke test on the karate
club graph."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro.core import PartitionerSpec, build_partition_batch, \
    build_halo_exchange, leiden_fusion
from repro.pipeline import (ARTIFACT_VERSION, Pipeline, PipelineConfig,
                            PartitionArtifactStore, get_dataset,
                            graph_fingerprint, make_karate_dataset)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def karate():
    return make_karate_dataset()


@pytest.fixture()
def store(tmp_path):
    return PartitionArtifactStore(str(tmp_path / "cache"))


# ---------------------------------------------------------------------------
# datasets
# ---------------------------------------------------------------------------
def test_dataset_registry_normalizes_names():
    ds = get_dataset("arxiv-like", n=200, feature_dim=8, num_classes=4)
    assert ds.name == "arxiv_like" and ds.graph.n == 200
    with pytest.raises(KeyError, match="unknown dataset"):
        get_dataset("nope")


def test_karate_dataset_shapes(karate):
    assert karate.graph.n == 34
    assert karate.num_classes == 2
    assert karate.features.shape == (34, 34)
    assert set(np.unique(karate.labels)) == {0, 1}
    # masks partition the node set
    total = (karate.train_mask.astype(int) + karate.val_mask.astype(int)
             + karate.test_mask.astype(int))
    assert (total == 1).all()


def test_graph_fingerprint_is_content_addressed(karate):
    h1 = graph_fingerprint(karate.graph)
    h2 = graph_fingerprint(make_karate_dataset(seed=7).graph)
    assert h1 == h2          # same topology, different masks -> same hash
    other = get_dataset("arxiv-like", n=100, feature_dim=4, num_classes=2)
    assert graph_fingerprint(other.graph) != h1


# ---------------------------------------------------------------------------
# artifact store
# ---------------------------------------------------------------------------
def _assert_batches_equal(a, b):
    assert a.n_pad == b.n_pad and a.e_pad == b.e_pad and a.k == b.k
    for f in ("node_ids", "node_mask", "owned_mask", "edge_src", "edge_dst",
              "edge_weight", "in_degree"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_roundtrip_partition_save_load(karate, store):
    """partition -> save -> load gives back an identical PartitionBatch."""
    g = karate.graph
    first = store.load_or_compute(g, "leiden_fusion", 4, 0, "repli",
                                  with_halo=True)
    assert not first.labels_hit and not first.batch_hit
    assert os.path.exists(first.labels_path)
    assert os.path.exists(first.batch_path)

    second = store.load_or_compute(g, "leiden_fusion", 4, 0, "repli",
                                   with_halo=True)
    assert second.labels_hit and second.batch_hit
    np.testing.assert_array_equal(first.labels, second.labels)
    _assert_batches_equal(first.batch, second.batch)
    np.testing.assert_array_equal(first.halo.send_rows,
                                  second.halo.send_rows)
    np.testing.assert_array_equal(first.halo.recv_rows,
                                  second.halo.recv_rows)
    assert first.halo.h_pad == second.halo.h_pad

    # loaded bundle matches a from-scratch rebuild exactly
    fresh_labels = leiden_fusion(g, 4, seed=0)
    np.testing.assert_array_equal(second.labels, fresh_labels)
    fresh = build_partition_batch(g, fresh_labels, scheme="repli")
    _assert_batches_equal(second.batch, fresh)
    fresh_halo = build_halo_exchange(g, fresh_labels, fresh)
    np.testing.assert_array_equal(second.halo.send_rows,
                                  fresh_halo.send_rows)


def test_cache_hit_skips_repartitioning(karate, store, monkeypatch):
    """Second load must NOT invoke the partitioner again."""
    g = karate.graph
    store.load_or_compute(g, "leiden_fusion", 2, 0, "inner")

    def boom(*a, **k):
        raise AssertionError("partitioner re-invoked despite cache hit")
    import repro.pipeline.artifacts as artifacts_mod
    monkeypatch.setattr(artifacts_mod, "partition_from_spec", boom)
    bundle = store.load_or_compute(g, "leiden_fusion", 2, 0, "inner")
    assert bundle.labels_hit and bundle.batch_hit


def test_labels_shared_across_schemes(karate, store):
    """inner and repli runs share ONE labels artifact (partition once)."""
    g = karate.graph
    a = store.load_or_compute(g, "metis", 2, 0, "inner")
    b = store.load_or_compute(g, "metis", 2, 0, "repli")
    assert not a.labels_hit
    assert b.labels_hit                   # second scheme reuses the labels
    assert not b.batch_hit                # but assembles its own batch
    assert a.labels_path == b.labels_path
    assert a.batch_path != b.batch_path


def test_key_separates_method_k_seed(karate, store):
    g = karate.graph
    base = store.load_or_compute(g, "random", 2, 0, "inner")
    for method, k, seed in (("lpa", 2, 0), ("random", 4, 0),
                            ("random", 2, 1)):
        other = store.load_or_compute(g, method, k, seed, "inner")
        assert not other.labels_hit
        assert other.labels_path != base.labels_path


def test_halo_augments_cached_batch(karate, store):
    """A batch cached without halo gets upgraded in place when halo is
    requested — the batch itself is still a hit."""
    g = karate.graph
    a = store.load_or_compute(g, "leiden_fusion", 2, 0, "repli",
                              with_halo=False)
    assert a.halo is None
    b = store.load_or_compute(g, "leiden_fusion", 2, 0, "repli",
                              with_halo=True)
    assert b.batch_hit and b.halo is not None
    c = store.load_or_compute(g, "leiden_fusion", 2, 0, "repli",
                              with_halo=True)
    assert c.batch_hit and c.halo is not None
    np.testing.assert_array_equal(b.halo.send_rows, c.halo.send_rows)


def test_artifact_version_is_5():
    """v5 turns monolithic compressed npz bundles into directory bundles
    whose batch tensors memory-map per-partition shards (DESIGN.md §15);
    pre-v5 bundles must degrade to misses."""
    assert ARTIFACT_VERSION == 5


def test_v2_bundles_degrade_to_misses(karate, store):
    """A bundle written under the v2 key must be a MISS today (recompute),
    never a wrong hit — even when graph/spec/k/seed all match."""
    g = karate.graph
    spec = PartitionerSpec.parse("leiden_fusion")
    ghash = graph_fingerprint(g)
    # forge the exact bundle a v2 store would have written (npz file keyed
    # by a version=2 meta)
    v2_meta = store._labels_meta(ghash, spec, 2, 0)
    v2_meta["version"] = 2
    v2_path = store._path(v2_meta, spec) + ".npz"
    bogus = np.zeros(g.n, dtype=np.int64)       # stale labels, must not leak
    store._atomic_savez(v2_path, labels=bogus,
                        meta_json=np.asarray(json.dumps(v2_meta)))
    labels, hit, path, _ = store.load_or_partition(g, spec, 2, 0)
    assert not hit                              # degraded to a miss
    assert path != v2_path                      # current keys land elsewhere
    assert os.path.exists(v2_path)              # v2 bundle left untouched
    assert int(labels.max()) + 1 == 2           # freshly recomputed


def test_v4_bundles_degrade_to_misses(karate, store):
    """The v4->v5 format skew: a monolithic npz bundle keyed version=4 must
    be a clean MISS under the v5 store — the on-disk format changed (npz ->
    mmap directory bundle), so old bundles can never be half-read as new
    ones. Mirrors the v2->v3 engine-skew guarantee one format later."""
    g = karate.graph
    spec = PartitionerSpec.parse("leiden_fusion")
    ghash = graph_fingerprint(g)
    v4_meta = store._labels_meta(ghash, spec, 2, 0)
    v4_meta["version"] = 4
    v4_path = store._path(v4_meta, spec) + ".npz"
    bogus = np.full(g.n, 1, dtype=np.int64)     # stale labels, must not leak
    store._atomic_savez(v4_path, labels=bogus,
                        meta_json=np.asarray(json.dumps(v4_meta)))
    labels, hit, path, _ = store.load_or_partition(g, spec, 2, 0)
    assert not hit                              # degraded to a miss
    assert path != v4_path                      # v5 keys land elsewhere
    assert os.path.isdir(path)                  # v5 wrote a directory bundle
    assert os.path.exists(v4_path)              # v4 bundle left untouched
    assert not np.array_equal(labels, bogus)    # stale labels did not leak
    # the legacy npz still shows up in maintenance listings beside the v5
    # bundle directories, and clear() removes both kinds
    names = [name for name, _ in store.entries()]
    assert os.path.basename(v4_path) in names
    assert os.path.basename(path) in names
    assert store.clear() == len(names)
    assert store.entries() == []


def test_key_separates_partitioner_config(karate, store):
    """Regression for the v1 collision: same method, different
    hyperparameters must land in distinct cache entries."""
    g = karate.graph
    a = store.load_or_compute(g, "lpa(balance_cap=1.1)", 2, 0, "inner")
    b = store.load_or_compute(g, "lpa(balance_cap=2.0)", 2, 0, "inner")
    assert not a.labels_hit and not b.labels_hit     # no false sharing
    assert a.labels_path != b.labels_path
    assert a.batch_path != b.batch_path
    assert a.fingerprint != b.fingerprint
    # same spec -> hit on its own entry
    again = store.load_or_compute(g, "lpa(balance_cap=2.0)", 2, 0, "inner")
    assert again.labels_hit and again.labels_path == b.labels_path
    # equivalent spellings of one config share one entry
    spaced = store.load_or_compute(g, "lpa ( balance_cap = 2.0 )", 2, 0,
                                   "inner")
    assert spaced.labels_hit and spaced.labels_path == b.labels_path


def test_store_accepts_parsed_specs(karate, store):
    g = karate.graph
    spec = PartitionerSpec.parse("metis+f(alpha=0.2)")
    a = store.load_or_compute(g, spec, 2, 0, "inner")
    b = store.load_or_compute(g, "metis+f(alpha=0.2)", 2, 0, "inner")
    assert b.labels_hit and a.labels_path == b.labels_path
    assert a.spec == b.spec == "metis+f(alpha=0.2)"
    assert a.fingerprint == spec.fingerprint()


def test_corrupt_artifact_is_a_miss(karate, store):
    g = karate.graph
    a = store.load_or_compute(g, "random", 2, 0, "inner")
    with open(os.path.join(a.labels_path, "meta.json"), "w") as f:
        f.write("not json {")
    b = store.load_or_compute(g, "random", 2, 0, "inner")
    assert not b.labels_hit               # recomputed, not crashed
    np.testing.assert_array_equal(a.labels, b.labels)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------
def test_pipeline_end_to_end_with_cache(tmp_path, karate):
    cfg = PipelineConfig(dataset="karate", method="leiden_fusion", k=4,
                         mode="local", epochs=3, classifier_epochs=10,
                         hidden_dim=16, embed_dim=16, num_layers=2,
                         dropout=0.0, cache_dir=str(tmp_path / "c"),
                         collect_hlo=True)
    rep1 = Pipeline(cfg).run(karate)
    assert not rep1.partition_cache_hit
    assert set(rep1.accuracy) == {"train", "val", "test"}
    assert rep1.partition["total_isolated"] == 0
    assert rep1.collectives["total"] == 0      # the paper's claim
    assert rep1.shapes["k"] == 4
    assert rep1.timings["total"] > 0

    rep2 = Pipeline(cfg).run(karate)
    assert rep2.partition_cache_hit and rep2.batch_cache_hit
    # deterministic end-to-end given identical config + cached partition
    assert rep1.accuracy == rep2.accuracy
    # report serializes
    json.dumps(rep2.as_dict())
    assert "cache HIT" in rep2.summary()


def test_pipeline_use_kernel_trains_and_matches_jnp_path(tmp_path, karate):
    """`--use-kernel` is a real training path: the run completes (it used
    to crash forward-only in jax.grad), records the flag, keeps the
    zero-collectives claim, and with dropout=0 lands within noise of the
    jnp path's accuracy."""
    def cfg(use_kernel):
        return PipelineConfig(dataset="karate", method="leiden_fusion", k=4,
                              mode="local", epochs=5, classifier_epochs=15,
                              hidden_dim=16, embed_dim=16, num_layers=2,
                              dropout=0.0, use_kernel=use_kernel,
                              cache_dir=str(tmp_path / "c"),
                              collect_hlo=use_kernel)
    rep_k = Pipeline(cfg(True)).run(karate)
    rep_j = Pipeline(cfg(False)).run(karate)
    assert rep_k.config["use_kernel"] is True
    # the summary names the resolved per-width strategies (DESIGN.md §14),
    # "xla" as itself — never as a kernel
    assert re.search(r"aggregation=f\d+:xla(,f\d+:xla)* ", rep_k.summary())
    assert rep_k.kernel, "resolved KernelConfigs must land in the report"
    for entry in rep_k.kernel.values():
        assert entry["strategy"] in ("pallas_fused", "pallas", "xla")
    assert "aggregation=jnp" in rep_j.summary()
    assert rep_j.kernel is None
    assert rep_k.collectives["total"] == 0    # kernel path stays local-only
    assert abs(rep_k.accuracy["test"] - rep_j.accuracy["test"]) <= 0.35
    for split in ("train", "val", "test"):
        assert 0.0 <= rep_k.accuracy[split] <= 1.0


def test_pipeline_centralized_reference(tmp_path, karate):
    cfg = PipelineConfig(dataset="karate", method="single", k=1,
                         scheme="inner", epochs=2, classifier_epochs=5,
                         hidden_dim=8, embed_dim=8, num_layers=2,
                         dropout=0.0, cache_dir=None, collect_hlo=False)
    rep = Pipeline(cfg).run(karate)
    assert rep.shapes["k"] == 1
    assert rep.collectives == {}


def test_pipeline_rejects_bad_mode(karate):
    cfg = PipelineConfig(dataset="karate", mode="nope")
    with pytest.raises(ValueError, match="mode"):
        Pipeline(cfg).run(karate)


def test_pipeline_rejects_bad_spec(karate):
    with pytest.raises(ValueError, match="unknown partitioner"):
        Pipeline(PipelineConfig(dataset="karate",
                                method="wat")).run(karate)
    with pytest.raises(ValueError, match="unknown field"):
        Pipeline(PipelineConfig(dataset="karate",
                                method="lpa(gamma=1)")).run(karate)


def test_pipeline_spec_string_end_to_end(tmp_path, karate):
    """The acceptance path: a configured +f spec runs end-to-end, the
    report records the canonical spec + fingerprint, re-running the same
    spec is a cache hit, and a different alpha is a miss."""
    def cfg(method):
        return PipelineConfig(dataset="karate", method=method, k=4,
                              mode="local", epochs=2, classifier_epochs=5,
                              hidden_dim=8, embed_dim=8, num_layers=2,
                              dropout=0.0, cache_dir=str(tmp_path / "c"),
                              collect_hlo=False)

    rep1 = Pipeline(cfg("lpa +f( alpha = 0.1 )")).run(karate)
    assert not rep1.partition_cache_hit
    assert rep1.config["method"] == "lpa+f(alpha=0.1)"   # canonical
    assert rep1.partition_fingerprint == \
        PartitionerSpec.parse("lpa+f(alpha=0.1)").fingerprint()
    assert rep1.partition["total_isolated"] == 0          # +f guarantee

    rep2 = Pipeline(cfg("lpa+f(alpha=0.1)")).run(karate)
    assert rep2.partition_cache_hit and rep2.batch_cache_hit

    rep3 = Pipeline(cfg("lpa+f(alpha=0.4)")).run(karate)
    assert not rep3.partition_cache_hit                   # config matters
    assert rep3.partition_fingerprint != rep1.partition_fingerprint
    assert "fp=" in rep3.summary()


# ---------------------------------------------------------------------------
# CLI smoke test (subprocess, as users invoke it)
# ---------------------------------------------------------------------------
def _run_cli(args, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    out = subprocess.run(
        [sys.executable, "-m", "repro.pipeline"] + args,
        capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout + out.stderr


def test_cli_sync_mode_reports_collectives(tmp_path):
    """Sync mode (one partition per fake device) must report nonzero
    collective bytes — the traffic LF eliminates."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    out = subprocess.run(
        [sys.executable, "-m", "repro.pipeline", "run", "--dataset",
         "karate", "--method", "leiden_fusion", "--k", "4", "--mode",
         "sync", "--epochs", "3", "--classifier-epochs", "5",
         "--hidden-dim", "8", "--embed-dim", "8",
         "--cache-dir", str(tmp_path / "cache")],
        capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-4000:]
    text = out.stdout + out.stderr
    m = re.search(r"collectives\s+(\d+) bytes/step", text)
    assert m, text
    assert int(m.group(1)) > 0


def test_cli_smoke_karate(tmp_path):
    args = ["run", "--dataset", "karate", "--method", "leiden_fusion",
            "--k", "4", "--mode", "local", "--epochs", "3",
            "--classifier-epochs", "10", "--hidden-dim", "16",
            "--embed-dim", "16", "--no-hlo",
            "--cache-dir", str(tmp_path / "cache")]
    out1 = _run_cli(args, tmp_path)
    assert "PipelineReport" in out1
    assert "accuracy" in out1
    assert "cache MISS" in out1
    out2 = _run_cli(args, tmp_path)
    assert "partition cache HIT" in out2
    assert "skipping re-partition" in out2

    listing = _run_cli(["cache", "--cache-dir", str(tmp_path / "cache")],
                       tmp_path)
    assert "labels-leiden_fusion-k4" in listing


def test_cli_accepts_spec_strings(tmp_path):
    """`run --method "lpa+f(alpha=0.1)"` works from the real CLI and caches
    under the spec fingerprint."""
    args = ["run", "--dataset", "karate", "--method", "lpa+f(alpha=0.1)",
            "--k", "4", "--mode", "local", "--epochs", "2",
            "--classifier-epochs", "5", "--hidden-dim", "8",
            "--embed-dim", "8", "--no-hlo",
            "--cache-dir", str(tmp_path / "cache")]
    out1 = _run_cli(args, tmp_path)
    assert "lpa+f(alpha=0.1)" in out1 and "cache MISS" in out1
    out2 = _run_cli(args, tmp_path)
    assert "partition cache HIT" in out2
    listing = _run_cli(["cache", "--cache-dir", str(tmp_path / "cache")],
                       tmp_path)
    assert "labels-lpa+f_alpha=0.1-k4" in listing


def test_cli_partitioners_lists_registry(tmp_path):
    out = _run_cli(["partitioners"], tmp_path)
    for name in ("leiden_fusion", "lpa", "metis", "random", "single"):
        assert name in out
    assert "connectivity|balanced" in out       # capability flags
    assert "resolution: float = 1.0" in out     # config schema + defaults
    assert "+f" in out and "spec grammar" in out

    js = _run_cli(["partitioners", "--json"], tmp_path)
    schema = json.loads(js[js.index("{"):])
    assert schema["lpa"]["fields"]["balance_cap"]["default"] == 1.1
    assert schema["leiden_fusion"]["capabilities"]["connectivity_guaranteed"]
    assert schema["+f"]["fields"]["alpha"]["default"] == 0.05
