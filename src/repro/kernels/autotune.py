"""Autotuned kernel configs: per-(backend, shape-bucket) tiling + strategy.

The module constants ``NODE_TILE=512 / EDGE_BLOCK=256 / FEAT_TILE=128``
are one point in a search space whose optimum moves with the backend and
the partition shape. This module owns that choice (DESIGN.md §14):

* **KernelConfig** — the tunable contract: a *strategy* plus tile sizes.
  Strategies:

  - ``"pallas_fused"`` — the fused GNN-layer kernel (aggregate + dense +
    bias + relu in ONE ``pallas_call``, :mod:`repro.kernels.fused_layer`);
    the TPU default — it amortizes kernel-launch overhead and keeps the
    aggregate tile in VMEM through the dense epilogue.
  - ``"pallas"`` — the unfused aggregation kernel with tuned tiles;
    the dense transform stays an XLA matmul.
  - ``"xla"`` — the same fused-layer math lowered directly through XLA
    (gather + segment-sum + dense epilogue under one jit). On backends
    where Pallas executes in *interpret mode* (CPU — a correctness
    emulator, not a perf path) this is the only sane choice: the one-hot
    scatter matmul costs O(N·E·F) dense FLOPs, which only an MXU makes
    affordable. Interpret-mode candidates are therefore never measured by
    default — they lose by ~15x before the tuner starts.

* **shape buckets** — configs are keyed by ``(backend, bucket)`` where the
  bucket rounds N and E up to powers of two and F up to the lane multiple,
  so one tuning run covers every partition that pads into the same bucket
  (the partition-fingerprint discipline applied to kernel shapes).

* **disk cache** — tuning is paid once: results land in a JSON cache
  (``REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune_cache.json``,
  atomic rewrite), consulted before the packaged factory table
  (``autotune_defaults.json``) and the per-backend fallback. A second
  process sees the first one's tuned configs — determinism across
  processes is pinned by ``tests/test_fused_layer.py``.

Resolution order for :func:`get_config`:
``override() > in-memory memo > user cache > factory defaults > fallback``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Tuple

from repro import obs

__all__ = [
    "KernelConfig", "ShapeBucket", "shape_bucket", "get_config", "autotune",
    "override", "candidate_space", "vmem_bytes", "cache_path",
    "clear_memory_cache", "interpret_mode", "VMEM_BUDGET",
]

# Pallas TPU VMEM working-set ceiling the candidate filter enforces
# (per-core VMEM is ~16 MB; leave headroom for the runtime).
VMEM_BUDGET = 14 * 1024 * 1024

STRATEGIES = ("pallas_fused", "pallas", "xla")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the kernel search space (hashable — usable as a jit
    static argument). Tile fields are meaningful for the pallas strategies;
    the ``xla`` strategy keeps them for bookkeeping only."""
    strategy: str = "pallas"
    node_tile: int = 512
    edge_block: int = 256
    feat_tile: int = 128
    stream: int = 2          # edge blocks streamed per grid step (the DMA
                             # granule is edge_block*stream; sub-blocks are
                             # skipped per-tile via the dst-range fast path)

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"strategy must be one of {STRATEGIES}, got {self.strategy!r}")

    @property
    def uses_pallas(self) -> bool:
        return self.strategy in ("pallas_fused", "pallas")

    @property
    def edge_granule(self) -> int:
        return self.edge_block * self.stream

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "KernelConfig":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


@dataclasses.dataclass(frozen=True)
class ShapeBucket:
    """Power-of-two shape bucket a concrete (n, e, f) pads into."""
    n: int
    e: int
    f: int

    @property
    def key(self) -> str:
        return f"n{self.n}_e{self.e}_f{self.f}"


def _pow2_ceil(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def shape_bucket(n: int, e: int, f: int) -> ShapeBucket:
    """Bucket: N and E to the next power of two (min 8 nodes / 128 edges),
    F to the next lane multiple (128)."""
    return ShapeBucket(n=max(_pow2_ceil(n), 8),
                       e=max(_pow2_ceil(e), 128),
                       f=((max(int(f), 1) + 127) // 128) * 128)


def vmem_bytes(bucket: ShapeBucket, cfg: KernelConfig,
               f_out: Optional[int] = None) -> int:
    """f32 VMEM working set of one fused-layer grid step (DESIGN.md §14).

    Every streamed or blocked operand has two buffers — the kernel's own
    two slots for the ``[granule, FT]`` block of source rows (gathered by
    XLA before the call) and the granule's dst and weight rows
    (``[1, granule]``, padded to 8 sublanes), Pallas's pipeline for the
    ``[NT, 1]`` inverse-degree column (padded to 128 lanes),
    the ``[FT, FO]`` weight block and ``[1, FO]`` bias, and the aggregate
    and output tiles. Single copies: the ``[NT, FO]`` dense accumulator and
    the ``[NT, EB]`` one-hot scatter with its iota. No term grows with N."""
    fo = f_out if f_out is not None else bucket.f
    ft = min(cfg.feat_tile, bucket.f)
    nt = min(cfg.node_tile, bucket.n)
    granule = cfg.edge_granule
    blocked = (granule * ft              # pre-gathered source rows
               + 2 * 8 * granule         # dst, weight rows
               + nt * 128                # inverse-degree column
               + ft * fo + 8 * fo        # weight block, bias row
               + nt * ft + nt * fo)      # aggregate, output tiles
    single = nt * fo + 2 * nt * cfg.edge_block
    return 4 * (2 * blocked + single)


# ---------------------------------------------------------------------------
# Cache: user file + packaged factory defaults + in-memory memo
# ---------------------------------------------------------------------------
_DEFAULTS_PATH = os.path.join(os.path.dirname(__file__),
                              "autotune_defaults.json")
_memo: Dict[Tuple[str, str], KernelConfig] = {}
_user_cache_loaded: Optional[str] = None   # path the memo was seeded from
_override_stack: List[KernelConfig] = []


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune_cache.json"))


def clear_memory_cache() -> None:
    """Drop the in-process memo (tests; forces a re-read of the files)."""
    global _user_cache_loaded
    _memo.clear()
    _user_cache_loaded = None


@contextlib.contextmanager
def override(config: KernelConfig):
    """Force every resolution to ``config`` inside the context (tests, and
    the roofline benchmark's forced-strategy rows)."""
    _override_stack.append(config)
    try:
        yield config
    finally:
        _override_stack.pop()


def _read_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _configs_from_file(path: str) -> Dict[Tuple[str, str], KernelConfig]:
    out = {}
    for backend, buckets in _read_json(path).get("configs", {}).items():
        for key, entry in buckets.items():
            try:
                out[(backend, key)] = KernelConfig.from_dict(entry["config"])
            except (KeyError, TypeError, ValueError):
                continue
    return out


def _seed_memo() -> None:
    """Load factory defaults then the user cache (user wins) into the memo."""
    global _user_cache_loaded
    path = cache_path()
    if _user_cache_loaded == path:
        return
    fresh = {}
    fresh.update(_configs_from_file(_DEFAULTS_PATH))
    fresh.update(_configs_from_file(path))
    _memo.clear()
    _memo.update(fresh)
    _user_cache_loaded = path


def _persist(backend: str, bucket: ShapeBucket, config: KernelConfig,
             measurements: Dict[str, float]) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    data = _read_json(path)
    data.setdefault("version", 1)
    entry = {
        "config": config.as_dict(),
        "source": "tuned",
        "measured_ms": {k: round(v, 4) for k, v in measurements.items()},
        "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    data.setdefault("configs", {}).setdefault(backend, {})[bucket.key] = entry
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _backend() -> str:
    import jax
    return jax.default_backend()


def interpret_mode() -> bool:
    """Whether Pallas calls run in the interpreter: everywhere but a TPU.

    Decided from the backend alone, at trace time — no caller chooses, so
    no TPU run can fall back to the interpreter."""
    return _backend() != "tpu"


def fallback_config(bucket: ShapeBucket,
                    backend: Optional[str] = None) -> KernelConfig:
    """Untuned default: the first candidate of :func:`candidate_space` —
    on TPU the default fused-kernel tiling, or the first that passes the
    VMEM filter when it does not; the XLA lowering where no tiling fits
    and wherever Pallas would run in interpret mode. So the untuned path
    never picks a config the tuner would refuse."""
    return candidate_space(bucket, backend)[0]


def get_config(n: int, e: int, f: int,
               backend: Optional[str] = None) -> KernelConfig:
    """Resolve the kernel config for a concrete shape (trace-time python:
    cheap dict lookups; the result is passed into jits as a static arg)."""
    if _override_stack:
        return _override_stack[-1]
    backend = backend or _backend()
    _seed_memo()
    bucket = shape_bucket(n, e, f)
    hit = _memo.get((backend, bucket.key))
    if hit is not None:
        return hit
    return fallback_config(bucket, backend)


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------
def candidate_space(bucket: ShapeBucket,
                    backend: Optional[str] = None) -> List[KernelConfig]:
    """Deterministically-ordered candidates for one (backend, bucket).

    TPU: the pallas strategies over a tile sweep, VMEM-filtered, with the
    untuned default point (``KernelConfig(strategy="pallas_fused")``) first
    when it fits. Other backends: the XLA strategy, plus the interpret-mode
    pallas points only when ``REPRO_AUTOTUNE_EXHAUSTIVE=1`` (they are
    emulation, ~15x off — measuring them by default just burns CI
    minutes)."""
    backend = backend or _backend()
    if backend != "tpu":
        cands = [KernelConfig(strategy="xla")]
        if os.environ.get("REPRO_AUTOTUNE_EXHAUSTIVE") == "1":
            cands += [KernelConfig(strategy="pallas_fused"),
                      KernelConfig(strategy="pallas")]
        return cands
    cands = []
    for strategy in ("pallas_fused", "pallas"):
        for nt in (256, 512, 1024):
            if nt > bucket.n and nt != 256:   # small buckets: one node tile
                continue
            for eb in (256, 512, 1024):
                for ft in (128, 256):
                    if ft > bucket.f:
                        continue
                    for stream in (1, 2, 4):
                        cfg = KernelConfig(strategy=strategy, node_tile=nt,
                                           edge_block=eb, feat_tile=ft,
                                           stream=stream)
                        if cfg.edge_granule > max(bucket.e, 256):
                            continue
                        if vmem_bytes(bucket, cfg) > VMEM_BUDGET:
                            continue
                        cands.append(cfg)
    if not cands:
        # layers so wide that even the smallest tiles' [FT, FO] weight
        # block and [NT, FO] accumulator overflow VMEM: the XLA lowering
        return [KernelConfig(strategy="xla")]
    default = KernelConfig(strategy="pallas_fused")
    return sorted(cands, key=lambda c: c != default)     # stable


def _measure(cfg: KernelConfig, bucket: ShapeBucket,
             repeats: int = 3) -> float:
    """Median wall ms of one fused-layer fwd+bwd at the bucket shape.

    The probe is the training hot path: ``value_and_grad`` w.r.t. (h, W, b)
    of a scalar loss over the fused GCN layer, jitted with ``cfg`` static.
    The first call (compile) is excluded; the median over ``repeats`` is
    returned."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import fused_gcn_layer

    rng = np.random.default_rng(0)
    n, e, f = bucket.n, bucket.e, bucket.f
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(np.sort(rng.integers(0, n, e)), jnp.int32)
    w_edge = jnp.asarray(rng.random(e), jnp.float32)
    deg = jnp.asarray(np.bincount(np.asarray(dst), minlength=n)[:n],
                      jnp.float32)
    w = jnp.asarray(rng.normal(size=(f, f)) * 0.1, jnp.float32)
    b = jnp.zeros((f,), jnp.float32)

    def loss(h, w, b):
        out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b,
                              activate=True, config=cfg)
        return (out * out).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(step(h, w, b))        # compile, excluded
    walls = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(step(h, w, b))
        walls.append((time.perf_counter() - t0) * 1e3)
    walls.sort()
    return walls[len(walls) // 2]


def autotune(n: int, e: int, f: int, backend: Optional[str] = None,
             force: bool = False, repeats: int = 3
             ) -> Tuple[KernelConfig, Dict[str, float]]:
    """Tune the (backend, bucket) of a concrete shape and cache the winner.

    Returns ``(config, measured_ms_per_candidate)``; a cache hit returns
    the cached config with an empty measurement table unless ``force``.
    Candidates are measured in deterministic order and the winner is the
    strict argmin (first wins ties), so re-tuning is reproducible up to
    measurement noise — and the disk cache makes every later process see
    the same choice without re-measuring."""
    backend = backend or _backend()
    bucket = shape_bucket(n, e, f)
    if not force:
        _seed_memo()
        hit = _memo.get((backend, bucket.key))
        if hit is not None:
            obs.counter("autotune.cache_hits").inc()
            return hit, {}
    cands = candidate_space(bucket, backend)
    measurements: Dict[str, float] = {}
    with obs.span("autotune.bucket", bucket=bucket.key, backend=backend,
                  candidates=len(cands)) as bsp:
        best, best_ms = cands[0], float("inf")
        if len(cands) == 1:
            best_ms = 0.0     # single candidate: nothing to measure
        else:
            for cfg in cands:
                with obs.span("autotune.candidate",
                              candidate=_cand_key(cfg)) as csp:
                    ms = _measure(cfg, bucket, repeats=repeats)
                    csp.set(measured_ms=round(ms, 4))
                obs.counter("autotune.candidates_measured").inc()
                measurements[_cand_key(cfg)] = ms
                if ms < best_ms:
                    best, best_ms = cfg, ms
        bsp.set(winner=_cand_key(best))
    _persist(backend, bucket, best, measurements)
    _memo[(backend, bucket.key)] = best
    return best, measurements


def _cand_key(cfg: KernelConfig) -> str:
    return (f"{cfg.strategy}/nt{cfg.node_tile}/eb{cfg.edge_block}/"
            f"ft{cfg.feat_tile}/s{cfg.stream}")
