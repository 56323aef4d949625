"""The benchmark's harness: finds a cell's parts by name, sets the job up,
drives the timed window through the program's own training entry, and
checks what it produced against the plain reference.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix. Each is a data file found by name:

- ``configs[].file``: the model and the graph, with their source, cuts,
  assumptions and precision;
- ``bench/models/<model>.py``, by the configuration's ``model`` key: the
  program's configuration of that model, the plain reference's parameters
  and forward pass, and its work counts (``bench/models/gcn.py`` says what
  such a module holds);
- ``bench/traffic/<traffic>.json``: how the job is laid out and driven
  (k, mode, scheme, partitioner, the partition seed, the epochs of one
  call, the steps the check follows);
- ``bench/limits/<cell>.json``: the limit of each number compared, with the
  readings it was set from;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(ctx)`` that returns a number, or None where the cell has
  nothing for it to read.

One window call is one job: train k GNNs for the traffic's
``epochs_per_call`` epochs from the seed and assemble the embedding table,
exactly as the pipeline's training stage calls ``train_local``/``train_sync``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's parts by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: List[Dict[str, Any]], cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_read_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench, "limits", name + ".json")),
        end_to_end=_for_cell(spec["end_to_end"], name),
        per_layer=_for_cell(spec["per_layer"], name), root=root)


def _load_module(kind: str, path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(cell: Cell, metric: str) -> Callable[[Any], Optional[float]]:
    path = os.path.join(cell.root, "bench", "metrics", metric + ".py")
    return _load_module("metric", path, metric).read


def load_model(cell: Cell) -> ModuleType:
    """The model module that the cell's configuration names."""
    name = cell.config["model"]
    path = os.path.join(cell.root, "bench", "models", name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no model module {path} for the configuration's "
                       f"model {name!r}")
    return _load_module("model", path, name)


def peaks_for(kind: str) -> Dict[str, Any]:
    table = _read_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; the table has "
                       f"{sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# the device and the caches
# ---------------------------------------------------------------------------
def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


def use_compile_cache(cache: str) -> str:
    """JAX's persistent compile cache, every program in it, so that only a
    checkout's first run compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set JAX keeps it there; otherwise at the fixed ``<cache>/jax`` inside
    the checkout. Returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(cache, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Job:
    cell: Cell
    model: ModuleType             # bench/models/<model>.py
    seed: int
    ds: Any
    bundle: Any
    mesh: Any
    call: Callable[..., Any]      # call(epochs=...) -> (params, embeddings)


def prepare(cell: Cell, seed: int, cache: str = CACHE) -> Job:
    """The dataset from the config's data seed, the partitions from the
    artifact store, and the training entry bound to this run's seed."""
    from repro.gnn import train_local, train_sync
    from repro.launch.mesh import make_local_mesh
    from repro.pipeline import PartitionArtifactStore, get_dataset
    c, t = cell.config, cell.traffic
    ds = get_dataset(c["dataset"], **c["dataset_kwargs"])
    sync = t["mode"] == "sync"
    bundle = PartitionArtifactStore(os.path.join(cache, "parts")) \
        .load_or_compute(ds.graph, t["partitioner"], t["k"],
                         t["partition_seed"], t["scheme"], with_halo=sync)
    model = load_model(cell)
    gcfg = model.program_config(c, int(ds.features.shape[1]))
    mesh = make_local_mesh()
    if sync:
        call = functools.partial(train_sync, ds, bundle.batch, bundle.halo,
                                 gcfg, mesh, lr=c["lr"], seed=seed)
    elif t["mode"] == "local":
        call = functools.partial(train_local, ds, bundle.batch, gcfg,
                                 lr=c["lr"], seed=seed, mesh=mesh)
    else:
        raise ValueError(f"traffic mode {t['mode']!r} is not driven here")
    return Job(cell=cell, model=model, seed=seed, ds=ds, bundle=bundle,
               mesh=mesh, call=call)


@dataclasses.dataclass
class Program:
    """What the timed entry produced over the check's first steps, and its
    table at the initial parameters."""
    losses: List[float]
    params: Any
    embeddings: np.ndarray
    embeddings0: np.ndarray


def first_steps(job: Job, steps: int) -> Program:
    """The window's own call, ``steps`` epochs from the seed, with the
    program's epoch spans on so that each step's loss is recorded; then the
    same call with no epoch, whose table passes through the embedding
    pass alone. This is also the warm-up: it compiles the step and the
    embedding pass."""
    import jax
    from repro import obs
    obs.reset()
    obs.enable()
    try:
        params, emb = job.call(epochs=steps)
        spans = [s for s in obs.tracer().spans() if s.name == "train.epoch"]
        losses = [float(s.attrs["loss"])
                  for s in sorted(spans, key=lambda s: s.attrs["epoch"])]
    finally:
        obs.reset()
    _, emb0 = job.call(epochs=0)
    return Program(losses=losses, params=jax.tree.map(np.asarray, params),
                   embeddings=np.asarray(emb), embeddings0=np.asarray(emb0))


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    epochs: int
    seconds: float           # first call's start to last call's end
    calls: List[float]       # each call's seconds


def run_window(job: Job, seconds: float, epochs: int) -> Window:
    """Calls back to back until ``seconds`` have passed; the call in
    flight then runs to its end and counts."""
    import jax
    attempted = failed = done = 0
    calls = []
    t_first = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        attempted += 1
        with jax.profiler.TraceAnnotation("bench.call"):
            try:
                _, emb = job.call(epochs=epochs)
                ok = bool(np.isfinite(emb).all())
            except Exception:
                traceback.print_exc()
                ok = False
        t1 = time.perf_counter()
        calls.append(t1 - t0)
        failed += not ok
        done += epochs if ok else 0
        if t1 - t_first >= seconds:
            return Window(attempted, failed, done, t1 - t_first, calls)


def memory_peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def reference_layout(job: Job):
    from bench import reference
    g = job.ds.graph
    return reference.build_layout(np.asarray(g.indptr), np.asarray(g.indices),
                                  np.asarray(g.edge_weight, np.float32),
                                  np.asarray(job.bundle.labels))


def with_seed(job: Job, seed: int) -> Job:
    """The same job bound to another seed (data and partitions reused)."""
    call = functools.partial(job.call.func, *job.call.args,
                             **{**job.call.keywords, "seed": seed})
    return dataclasses.replace(job, seed=seed, call=call)


def reference_run(job: Job, layout, steps: int, **control):
    """The plain reference over the same job, after the window, in the
    arithmetic the configuration states; ``control`` overrides fields of
    :class:`bench.reference.Arithmetic` (a control below it)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import reference
    c, t = job.cell.config, job.cell.traffic
    ds = job.ds
    sharding = None
    if t["mode"] == "sync" and len(jax.devices()) == layout.k:
        sharding = NamedSharding(job.mesh, P("data"))
    tensors = reference.device_tensors(layout, np.asarray(ds.features),
                                       np.asarray(ds.labels),
                                       np.asarray(ds.train_mask), sharding)
    m = reference.Model(module=job.model, config=c,
                        feature_dim=int(ds.features.shape[1]),
                        num_classes=int(ds.num_classes),
                        sync=t["mode"] == "sync")
    ar = dataclasses.replace(reference.Arithmetic.of(c["precision"]),
                             **control)
    return reference.train(layout, tensors, m, job.seed, steps, ds.graph.n,
                           ar=ar, sharding=sharding)


def compare(prog: Program, ref) -> Dict[str, Any]:
    """The numbers compared, each a gap against the reference:

    - ``loss_gap``: the relative gap of the first step's loss (mean over
      the partitions). The later steps' losses are reported beside it
      (``loss_gaps``) and not judged: the optimizer turns rounding in the
      first gradients into sign changes of single updates, and those move
      the later losses from seed to seed by more than a lower precision
      does;
    - ``change_gap``: the worst leaf's gap between the norms of the
      parameters' change after those steps, over the larger of the
      reference leaf's norm and the median leaf's. Leaves whose first
      reference gradient is under a thousandth of the median leaf's are
      left out (they move by round-off alone);
    - ``flip_share``: over the elements of those leaves, the share whose
      change after the steps has another sign than the reference's. A
      gradient taken over other rows, or not taken, turns many signs;
      rounding turns those of the few elements whose gradient is near 0;
    - ``emb_gap``: the worst row of the embedding table, its distance from
      the reference row over the larger of that row's norm and the median
      row's;
    - ``emb0_gap``: the table at the initial parameters, the norm of its
      difference from the reference's over the norm of the reference's. It
      passes through the body's products and the aggregation alone, with
      no optimizer to turn their rounding into signs.
    """
    import jax
    losses_ref = np.asarray(ref.losses, np.float64)
    losses = np.asarray(prog.losses, np.float64)
    if losses.shape != losses_ref.shape:
        gaps_l = [math.inf]
    else:
        gaps_l = (np.abs(losses - losses_ref) / np.abs(losses_ref)).tolist()
    loss_gap = gaps_l[0]

    def by_path(tree):
        return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
                for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    p0, pr, pp = by_path(ref.params0), by_path(ref.params), by_path(
        prog.params)
    names = list(p0)
    if sorted(names) != sorted(pp):
        return {"loss_gap": loss_gap, "change_gap": math.inf,
                "flip_share": math.inf, "emb_gap": math.inf,
                "emb0_gap": math.inf, "worst_leaf": "structure differs",
                "loss_gaps": gaps_l}

    def change(tree):
        return np.stack([np.sqrt(np.sum(
            np.square(tree[n] - p0[n]).reshape(p0[n].shape[0], -1), axis=1))
            for n in names], axis=1)                        # [k, leaves]
    dr, dp = change(pr), change(pp)
    g1 = ref.grad1_norms
    moved = g1 >= 1e-3 * np.median(g1)
    denom = np.maximum(dr, np.median(dr[moved]))
    gaps = np.where(moved, np.abs(dp - dr) / denom, 0.0)
    worst = np.unravel_index(int(np.argmax(gaps)), gaps.shape)
    flips = total = 0
    for j, n in enumerate(names):
        sr = np.sign(pr[n] - p0[n]).reshape(p0[n].shape[0], -1)
        sp = np.sign(pp[n] - p0[n]).reshape(p0[n].shape[0], -1)
        kept = moved[:, j]
        flips += int((sr[kept] != sp[kept]).sum())
        total += int(sr[kept].size)

    er = np.asarray(ref.embeddings, np.float64)
    ep = np.asarray(prog.embeddings, np.float64)
    rn = np.linalg.norm(er, axis=1)
    rows = (np.linalg.norm(ep - er, axis=1)
            / np.maximum(rn, np.median(rn)))
    e0r = np.asarray(ref.embeddings0, np.float64)
    e0p = np.asarray(prog.embeddings0, np.float64)
    return {"loss_gap": loss_gap, "change_gap": float(gaps.max()),
            "flip_share": flips / max(total, 1),
            "emb_gap": float(rows.max()),
            "emb0_gap": float(np.linalg.norm(e0p - e0r)
                              / np.linalg.norm(e0r)),
            "worst_leaf": f"partition {worst[0]} {names[worst[1]]}",
            "leaves_left_out": int((~moved).sum()), "loss_gaps": gaps_l}


NUMBERS = ("loss_gap", "change_gap", "flip_share", "emb_gap", "emb0_gap")


def judge(numbers: Dict[str, Any], limits: Dict[str, Any]):
    """(correct, {name: {"value", "limit"}}) for every number compared."""
    checks, ok = {}, True
    for name in NUMBERS:
        value, limit = numbers[name], limits[name]["limit"]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a per-layer reader can read."""
    cell: Cell
    model: ModuleType             # the work counts of bench/models/<model>.py
    peaks: Dict[str, Any]
    chips: int
    window: Window
    layout: Any                   # the reference's partition layout
    trace: Any                    # bench/trace.py Trace
    lo: float                     # the traced window, trace clock (ns)
    hi: float
    hlo_text: Optional[str]       # the compiled train step (sync cells)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, cache: str = CACHE, devices=None) -> Dict[str, Any]:
    """One run of ``cell``: set-up, window, check, and the result line.
    ``devices`` stands in for the look for a chip and the compile cache
    (tests on the CPU)."""
    import jax
    sys.path.insert(0, os.path.join(cell.root, "src"))
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(cache, "autotune.json")
    if devices is None:
        devices = require_chips(cell.chips)
        use_compile_cache(cache)
    d0 = devices[0]
    t = cell.traffic
    job = prepare(cell, seed, cache)
    prog = first_steps(job, t["check_steps"])
    hlo_text = None
    if trace and t["mode"] == "sync":
        hlo = {}
        job.call(epochs=1, hlo_out=hlo)
        hlo_text = hlo["hlo"]
    trace_dir = os.path.join(cache, "trace", cell.name)
    setup_s = time.perf_counter() - t_start

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win = run_window(job, seconds, t["epochs_per_call"])
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak = memory_peak_bytes()

    layout = reference_layout(job)
    ref = reference_run(job, layout, t["check_steps"])
    numbers = compare(prog, ref)
    correct, checks = judge(numbers, cell.limits)
    correct = correct and win.failed == 0
    del ref, prog

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out: Dict[str, Any] = {"correct": correct, "attempted": win.attempted,
                           "failed": win.failed}
    if trace:
        from bench import trace as trace_mod
        tr = trace_mod.load(trace_mod.find_xplane(trace_dir))
        lo, hi = trace_mod.window(tr)
        ctx = Context(cell=cell, model=job.model,
                      peaks=peaks_for(d0.device_kind),
                      chips=cell.chips, window=win, layout=layout, trace=tr,
                      lo=lo, hi=hi, hlo_text=hlo_text)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_seconds(tr, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": trace_mod.top_ops(tr, lo, hi),
                            "idle_gaps": trace_mod.idle_gaps(tr, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        # ``<quantity>.<part>`` reports the quantity in the cells that the
        # entry lists, under a bound of its own
        values = {"epochs_per_s": win.epochs / win.seconds,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"].split(".")[0]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["window"] = {"seconds": win.seconds, "epochs": win.epochs,
                     "calls_s": win.calls, "loss_gaps": numbers["loss_gaps"],
                     "worst_leaf": numbers["worst_leaf"],
                     "leaves_left_out": numbers.get("leaves_left_out")}
    out["checks"] = checks
    return out
