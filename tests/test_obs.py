"""Tests for repro.obs — tracing + metrics (DESIGN.md §16).

Covers the contracts the rest of the stack leans on: span
nesting/exception-safety, trace JSON schema validity, byte-identical
pipeline results in no-op mode, deterministic counter snapshots across
processes, the ``PipelineReport.timings``-is-a-view-over-spans pin, and
the profiler sink (the training entry's spans in a profiler trace).
"""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry, pow2_bucket_index
from repro.obs.summarize import (format_summary, load_trace,
                                 summarize_trace, validate_trace)


@pytest.fixture(autouse=True)
def _obs_isolation():
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# spans: no-op fast path, nesting, exception safety
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_singleton():
    assert not obs.enabled()
    s1 = obs.span("a.b", x=1)
    s2 = obs.span("c.d")
    assert s1 is s2                      # one shared object, no allocation
    with s1 as sp:
        sp.set(anything=True)            # must be accepted and dropped
    assert sp.duration is None
    assert obs.tracer().event_count() == 0


def test_span_nesting_records_depth_and_containment():
    obs.enable()
    with obs.span("outer.stage") as outer:
        with obs.span("inner.step", i=0) as inner:
            pass
        with obs.span("inner.step", i=1):
            pass
    spans = obs.tracer().spans()
    assert [s.name for s in spans] == \
        ["inner.step", "inner.step", "outer.stage"]
    assert outer.depth == 0 and inner.depth == 1
    assert all(s.duration is not None and s.duration >= 0 for s in spans)
    # children close before the parent and fit inside it
    assert outer.duration >= inner.duration


def test_span_exception_safety_stamps_error_and_unwinds():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom.outer"):
            with obs.span("boom.inner"):
                raise ValueError("expected")
    spans = {s.name: s for s in obs.tracer().spans()}
    assert set(spans) == {"boom.outer", "boom.inner"}
    assert spans["boom.inner"].attrs["error"] == "ValueError"
    assert spans["boom.outer"].attrs["error"] == "ValueError"
    assert all(s.duration is not None for s in spans.values())
    # the stack fully unwound: a fresh span is depth 0 again
    with obs.span("after.exc") as sp:
        pass
    assert sp.depth == 0


def test_generator_abandonment_closes_orphaned_spans():
    obs.enable()

    def gen():
        with obs.span("gen.chunk"):
            yield 1
            yield 2

    with obs.span("consumer.loop"):
        for _ in gen():
            break                        # abandon mid-span
    names = [s.name for s in obs.tracer().spans()]
    assert "gen.chunk" in names and "consumer.loop" in names
    assert all(s.duration is not None for s in obs.tracer().spans())


# ---------------------------------------------------------------------------
# trace document: schema validity, export round-trip, summarize
# ---------------------------------------------------------------------------
def test_trace_document_is_valid_chrome_trace(tmp_path):
    obs.enable()
    with obs.span("pipeline.total"):
        with obs.span("pipeline.dataset", n=34):
            pass
    obs.counter("graphstore.chunks").inc(3)
    path = obs.export_trace(str(tmp_path / "t.json"))
    doc = load_trace(path)
    assert validate_trace(doc) == []
    assert doc["schema"] == "repro-obs-trace"
    assert doc["version"] == obs.SCHEMA_VERSION
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"pipeline.total", "pipeline.dataset"}
    for e in xs:
        assert e["dur"] >= 0 and "ts" in e and "pid" in e and "tid" in e
        assert e["cat"] == "pipeline"
        assert "depth" in e["args"]
    assert doc["metrics"]["graphstore.chunks"]["value"] == 3


def test_validate_trace_require_matching():
    obs.enable()
    with obs.span("pipeline.dataset"):
        pass
    doc = obs.trace_document()
    # exact, category, prefix, and suffix forms all match
    for req in ("pipeline.dataset", "pipeline", "dataset"):
        assert validate_trace(doc, require=[req]) == [], req
    assert validate_trace(doc, require=["train"]) != []


def test_validate_trace_flags_malformed_documents():
    assert validate_trace({}) != []
    assert validate_trace({"schema": "wrong", "version": 1,
                           "traceEvents": []}) != []
    bad_event = {"schema": "repro-obs-trace", "version": 1,
                 "traceEvents": [{"ph": "X", "name": "a", "ts": 0.0,
                                  "dur": -5.0, "pid": 1, "tid": 1}]}
    assert any("dur" in p for p in validate_trace(bad_event))


def test_summarize_aggregates_per_name(tmp_path):
    obs.enable()
    for i in range(3):
        with obs.span("engine.sweep", i=i):
            pass
    doc = obs.trace_document()
    rows = summarize_trace(doc)
    row = next(r for r in rows if r["name"] == "engine.sweep")
    assert row["count"] == 3
    text = format_summary(doc)
    assert "engine.sweep" in text


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for v in (1, 2, 3, 900):
        reg.histogram("h").record(v)
    snap = reg.snapshot()
    assert snap["c"] == {"kind": "counter", "value": 5}
    assert snap["g"]["value"] == 2.5
    h = snap["h"]["value"]
    assert h["count"] == 4 and h["min"] == 1 and h["max"] == 900
    assert reg.total_ops() == 7
    with pytest.raises(TypeError):
        reg.gauge("c")                   # kind mismatch is a hard error


def test_pow2_bucket_index():
    assert pow2_bucket_index(0) == 0
    assert pow2_bucket_index(1) == 0
    assert pow2_bucket_index(2) == 1
    assert pow2_bucket_index(3) == 2
    assert pow2_bucket_index(1024) == 10
    assert pow2_bucket_index(1025) == 11


_SNAPSHOT_SCRIPT = """
import json
from repro.obs.metrics import MetricsRegistry
reg = MetricsRegistry()
for i in range(100):
    reg.counter("a.ops").inc()
    if i % 3 == 0:
        reg.counter("b.ops").inc(2)
reg.gauge("ignored.gauge").set(1.0)      # filtered out by kinds=
print(json.dumps(reg.snapshot(kinds=("counter",)), sort_keys=True))
"""


def test_counter_snapshot_deterministic_across_processes():
    """Two fresh interpreters doing the same work emit identical counter
    snapshots — the property that makes registry counters usable as
    primary storage for cross-process comparisons."""
    outs = [subprocess.run([sys.executable, "-c", _SNAPSHOT_SCRIPT],
                           capture_output=True, text=True, check=True,
                           env=_child_env()).stdout
            for _ in range(2)]
    assert outs[0] == outs[1]
    snap = json.loads(outs[0])
    assert snap == {"a.ops": {"kind": "counter", "value": 100},
                    "b.ops": {"kind": "counter", "value": 68}}


def _child_env():
    import os
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# pipeline integration: no-op byte-identity + timings-as-span-view pin
# ---------------------------------------------------------------------------
def _tiny_report():
    from repro.pipeline import Pipeline, PipelineConfig
    cfg = PipelineConfig(dataset="karate", method="leiden_fusion", k=2,
                         mode="local", epochs=2, classifier_epochs=4,
                         collect_hlo=False, cache_dir=None)
    return Pipeline(cfg).run()


def test_noop_mode_byte_identical_and_timings_pin():
    # run 1: tracing disabled (the default production path)
    assert not obs.enabled()
    plain = _tiny_report().as_dict()

    # run 2: tracing enabled
    obs.reset()
    obs.enable()
    traced_report = _tiny_report()
    traced = traced_report.as_dict()

    # byte-identity: tracing must not perturb any pipeline output —
    # only the wall-clock timings may differ between the two runs
    plain.pop("timings")
    timings = traced.pop("timings")
    assert json.dumps(plain, sort_keys=True, default=str) == \
        json.dumps(traced, sort_keys=True, default=str)

    # timings pin: the report's timings dict is a view over the spans
    durations = {s.name: s.duration for s in obs.tracer().spans()}
    for key, span_name in [("total", "pipeline.total"),
                           ("dataset", "pipeline.dataset"),
                           ("partition_stage", "pipeline.partition"),
                           ("train", "pipeline.train"),
                           ("classifier", "pipeline.classifier")]:
        assert timings[key] == round(durations[span_name], 4), key

    # the acceptance span set is present in the trace document
    doc = obs.trace_document()
    assert validate_trace(doc, require=["dataset", "partition", "train",
                                        "classifier"]) == []
    names = {s.name for s in obs.tracer().spans()}
    assert "engine.sweep" in names          # engine frontier sweeps
    assert "graphstore.chunk" in names      # chunk I/O spans
    assert "train.epoch" in names           # per-epoch training spans


# ---------------------------------------------------------------------------
# jax.profiler session: a requested profile either lands or raises
# ---------------------------------------------------------------------------
def test_profiler_session_writes_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    with obs.profiler_session(str(tmp_path)):
        jax.block_until_ready(jnp.arange(8.0) * 2)
    assert list(tmp_path.rglob("*.xplane.pb"))
    assert _profiler_failures() == 0


def _profiler_failures() -> int:
    snap = obs.registry().snapshot(kinds=("counter",))
    return snap.get("jax.profiler.failed", {"value": 0})["value"]


@pytest.mark.parametrize("phase", ["start_trace", "stop_trace"])
def test_profiler_session_failure_raises_and_counts(tmp_path, monkeypatch,
                                                    phase):
    import jax

    def broken(*args, **kwargs):
        raise RuntimeError(f"{phase} refused")

    stops = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stops.append(1))
    monkeypatch.setattr(jax.profiler, phase, broken)
    with pytest.raises(RuntimeError, match="refused"):
        with obs.profiler_session(str(tmp_path)):
            pass
    assert _profiler_failures() == 1
    # no directory: no profiler call at all, so nothing can fail
    with obs.profiler_session(None):
        pass
    assert _profiler_failures() == 1


# ---------------------------------------------------------------------------
# the profiler sink: spans on the profiler's clock, no sync, no change to
# the Chrome export
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_job():
    from repro.core import (build_partition_batch, leiden_fusion,
                            make_arxiv_like)
    from repro.gnn import GNNConfig
    ds = make_arxiv_like(n=200, feature_dim=8, num_classes=4, seed=3)
    batch = build_partition_batch(ds.graph, leiden_fusion(ds.graph, 2),
                                  scheme="repli")
    cfg = GNNConfig(kind="gcn", feature_dim=8, hidden_dim=8, embed_dim=8,
                    num_layers=2, dropout=0.5)
    return ds, batch, cfg


def _bench_trace():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import trace
    return trace


def test_profiler_trace_holds_the_training_entrys_spans(tmp_path, tiny_job):
    import jax
    from repro.gnn import train_local
    trace = _bench_trace()
    epochs = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.call"):
                train_local(*tiny_job, epochs=epochs)
    finally:
        jax.profiler.stop_trace()
    assert obs.tracer().event_count() == 0      # obs itself stayed off
    tr = trace.load(trace.find_xplane(str(tmp_path)),
                    device_plane="^/host:CPU$", op_line="^tf_XLA",
                    op_stat="hlo_op")
    (_, c0, c1), = [s for s in tr.spans if s[0] == "bench.call"]
    train = [h for h in tr.host if h[0].startswith("train.")]
    (_, t0, t1), = [h for h in train if h[0] == "train.call"]
    assert c0 <= t0 < t1 <= c1
    # the direct children of train.call, in order
    children = [h for h in train if h[0] != "train.call"
                and not any(o is not h and o[0] != "train.call"
                            and o[1] <= h[1] and h[2] <= o[2]
                            for o in train)]
    assert all(t0 <= s and e <= t1 for _, s, e in children)
    assert [n for n, _, _ in children] == (
        ["train.gather", "train.upload", "train.lower", "train.compile"]
        + ["train.epoch"] * epochs
        + ["train.embed", "train.fetch", "train.pool"])
    # the embedding pass is built inside train.embed
    (_, e0, e1), = [h for h in children if h[0] == "train.embed"]
    assert [n for n, s, e in train if e0 < s and e <= e1] == \
        ["train.lower", "train.compile"]


def test_span_is_the_noop_singleton_again_after_a_profile(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        live = obs.span("a.b", x=1)
        with obs.step_span("a.step", 0, x=1) as sp:
            sp.set(loss=1.0)                     # dropped: obs is off
    finally:
        jax.profiler.stop_trace()
    assert live is not obs.span("a.b", x=1)
    assert obs.span("a.b", x=1) is obs.span("c.d") is \
        obs.step_span("a.step", 3)
    assert obs.tracer().event_count() == 0


def _epochs_and_export(tiny_job, profile_dir=None):
    import jax
    from repro.gnn import train_local
    obs.reset()
    obs.enable()
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        train_local(*tiny_job, epochs=3)
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    doc = obs.trace_document()
    losses = [(s.attrs["epoch"], s.attrs["loss"])
              for s in obs.tracer().spans() if s.name == "train.epoch"]
    events = [(e["name"], e["args"]) for e in doc["traceEvents"]
              if e.get("ph") == "X"]
    return losses, events, doc


def test_enabled_export_and_losses_unchanged_under_the_profiler(tmp_path,
                                                                tiny_job):
    plain = _epochs_and_export(tiny_job)
    profiled = _epochs_and_export(tiny_job, str(tmp_path))
    assert [e for e, _ in plain[0]] == [0, 1, 2]
    assert profiled[0] == plain[0]
    assert profiled[1] == plain[1]
    assert validate_trace(profiled[2]) == []
    first = next(args for name, args in plain[1] if name == "train.epoch")
    assert first["epoch"] == 0 and first["mode"] == "local"


@pytest.mark.parametrize("enabled,strategy", [(True, "pallas_fused"),
                                              (False, "pallas_fused"),
                                              (True, "xla")])
def test_stream_share_gauge_only_with_obs_on_and_a_pallas_config(
        tiny_job, enabled, strategy):
    """The training entry records ``kernels.stream_share`` from the host
    batch while span collection is on and the resolved config runs the
    Pallas kernels; the timed path (obs off) computes nothing."""
    import dataclasses
    from repro.gnn.train import _gather_and_upload
    from repro.kernels import streamed_pairs
    from repro.kernels.autotune import KernelConfig, override
    ds, batch, cfg = tiny_job
    cfg = dataclasses.replace(cfg, use_kernel=True)
    kc = KernelConfig(strategy=strategy, node_tile=64, edge_block=32,
                      stream=2)
    if enabled:
        obs.enable()
    with override(kc):
        _gather_and_upload(ds, batch, cfg, seed=0)
    share = obs.gauge("kernels.stream_share").value
    if not (enabled and kc.uses_pallas):
        assert share is None
        return
    streamed, dense = streamed_pairs(batch.edge_dst, batch.node_ids.shape[1],
                                     kc)
    assert 0 < streamed <= dense
    assert share == pytest.approx(streamed / dense)
