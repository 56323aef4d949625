"""Fused GNN-layer kernel + autotuner tests (DESIGN.md §14).

Pins: (1) forward/backward parity of every KernelConfig strategy against
the jnp composition (`fused_gcn_reference`), including non-multiple-of-tile
shapes, duplicate destinations, and zero-degree nodes; (2) a finite-
difference probe of the fused custom VJP; (3) the layer entry points
(`gcn_layer` / `sage_layer` / `gnn_forward`) matching the jnp path under a
forced pallas config — the surface sync/stale/local training all consume;
(4) autotune cache determinism across processes; (5) the structured shape-
contract error; (6) the VMEM-filtered candidate space.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fused_gcn_layer
from repro.kernels.autotune import (VMEM_BUDGET, KernelConfig, ShapeBucket,
                                    autotune, candidate_space,
                                    clear_memory_cache, fallback_config,
                                    get_config, override, shape_bucket,
                                    vmem_bytes)
from repro.kernels.csr_aggregate import (ShapeContractError,
                                         csr_aggregate_pallas)
from repro.kernels.fused_layer import fused_gcn_reference

STRATEGIES = ("pallas_fused", "pallas", "xla")


def _star_graph(seed, n, f, e, fo):
    """Random graph with duplicate destinations AND zero-degree nodes
    (dst drawn from the first half of the rows only)."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(np.sort(rng.integers(0, max(n // 2, 1), e)), jnp.int32)
    w_edge = jnp.asarray(rng.random(e), jnp.float32)
    deg = jnp.asarray(np.bincount(np.asarray(dst), minlength=n)[:n],
                      jnp.float32)
    w = jnp.asarray(rng.normal(size=(f, fo)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(fo,)) * 0.1, jnp.float32)
    return h, src, dst, w_edge, deg, w, b


def _reference(h, src, dst, w_edge, deg, w, b, activate):
    inv = 1.0 / jnp.maximum(deg, 1.0)
    return fused_gcn_reference(h, src, dst, w_edge, inv, w, b,
                               activate=activate)


# ---------------------------------------------------------------------------
# strategy parity: forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,f,e,fo", [
    (8, 16, 32, 16),        # tiny, aligned-ish
    (100, 24, 700, 50),     # unaligned everything
    (600, 40, 1500, 24),    # node-tiled (n > default tile when forced small)
])
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("activate", [True, False])
def test_fused_layer_strategy_forward_parity(n, f, e, fo, strategy, activate):
    h, src, dst, w_edge, deg, w, b = _star_graph(n * 3 + fo, n, f, e, fo)
    cfg = KernelConfig(strategy=strategy)
    out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b,
                          activate=activate, config=cfg)
    ref = _reference(h, src, dst, w_edge, deg, w, b, activate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_fused_layer_streamed_config_parity():
    """stream > 1 changes the DMA granule, never the result."""
    h, src, dst, w_edge, deg, w, b = _star_graph(7, 100, 24, 700, 16)
    ref = _reference(h, src, dst, w_edge, deg, w, b, True)
    for stream in (1, 2, 4):
        cfg = KernelConfig(strategy="pallas_fused", node_tile=64,
                           edge_block=128, feat_tile=128, stream=stream)
        out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b, config=cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# strategy parity: gradients
# ---------------------------------------------------------------------------
def _grads(cfg, h, src, dst, w_edge, deg, w, b):
    def loss(h, w_edge, w, b):
        out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b,
                              activate=True, config=cfg)
        return jnp.sum(out * out)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(h, w_edge, w, b)


@pytest.mark.parametrize("n,f,e,fo", [
    (8, 16, 32, 16),
    (100, 24, 700, 50),
])
@pytest.mark.parametrize("strategy", ["pallas_fused", "pallas"])
def test_fused_layer_strategy_grad_parity(n, f, e, fo, strategy):
    h, src, dst, w_edge, deg, w, b = _star_graph(n + fo, n, f, e, fo)
    val, grads = _grads(KernelConfig(strategy=strategy),
                        h, src, dst, w_edge, deg, w, b)
    ref_val, ref_grads = _grads(KernelConfig(strategy="xla"),
                                h, src, dst, w_edge, deg, w, b)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-4)
    for name, g, rg in zip(("dh", "dw_edge", "dW", "db"), grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


def test_fused_layer_finite_difference_probe():
    """The custom VJP agrees with a central finite difference (directional
    derivative w.r.t. every differentiable argument)."""
    h, src, dst, w_edge, deg, w, b = _star_graph(11, 8, 8, 16, 8)
    cfg = KernelConfig(strategy="pallas_fused")
    rng = np.random.default_rng(3)

    def loss(h, w_edge, w, b):
        out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b,
                              activate=True, config=cfg)
        return float(jnp.sum(out * out))

    args = [h, w_edge, w, b]
    _, grads = _grads(cfg, h, src, dst, w_edge, deg, w, b)
    eps = 1e-3
    for i, (arg, g) in enumerate(zip(args, grads)):
        d = jnp.asarray(rng.normal(size=arg.shape), jnp.float32)
        plus = list(args)
        minus = list(args)
        plus[i] = arg + eps * d
        minus[i] = arg - eps * d
        fd = (loss(*plus) - loss(*minus)) / (2 * eps)
        analytic = float(jnp.vdot(g, d))
        np.testing.assert_allclose(analytic, fd, rtol=5e-2, atol=5e-2)


def test_fused_layer_zero_degree_rows_are_bias_only():
    """A node with no in-edges aggregates to 0 → out = act(b) exactly, on
    every strategy (the relu grad-at-zero convention depends on this row
    class existing)."""
    h = jnp.ones((16, 8), jnp.float32)
    src = jnp.zeros((8,), jnp.int32)
    dst = jnp.zeros((8,), jnp.int32)            # rows 1.. have degree 0
    w_edge = jnp.ones((8,), jnp.float32)
    deg = jnp.zeros((16,), jnp.float32).at[0].set(8.0)
    w = jnp.eye(8, dtype=jnp.float32)
    b = jnp.asarray(np.linspace(-1, 1, 8), jnp.float32)
    for strategy in STRATEGIES:
        out = fused_gcn_layer(h, src, dst, w_edge, deg, w, b,
                              activate=True,
                              config=KernelConfig(strategy=strategy))
        np.testing.assert_allclose(np.asarray(out[1:]),
                                   np.tile(np.maximum(np.asarray(b), 0.0),
                                           (15, 1)),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# layer entry points under a forced pallas config (the training surface)
# ---------------------------------------------------------------------------
def test_gcn_and_sage_layer_match_jnp_under_forced_pallas():
    from repro.gnn.layers import (gcn_layer, init_gcn_layer, init_sage_layer,
                                  sage_layer)
    h, src, dst, w_edge, deg, _, _ = _star_graph(5, 60, 12, 200, 12)
    key = jax.random.PRNGKey(0)
    for layer, init in ((gcn_layer, init_gcn_layer),
                        (sage_layer, init_sage_layer)):
        params = init(key, 12, 20)
        ref = layer(params, h, src, dst, w_edge, deg, use_kernel=False)
        with override(KernelConfig(strategy="pallas_fused")):
            out = layer(params, h, src, dst, w_edge, deg, use_kernel=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


def test_gnn_forward_grads_match_jnp_under_forced_pallas():
    """Full multi-layer body (what local/sync/stale steps differentiate):
    values AND grads match the jnp path under a forced fused config."""
    from repro.gnn import GNNConfig, init_gnn
    from repro.gnn.model import gnn_forward
    h, src, dst, w_edge, deg, _, _ = _star_graph(9, 50, 8, 180, 8)
    mk = lambda uk: GNNConfig(kind="gcn", feature_dim=8, hidden_dim=16,
                              embed_dim=16, num_layers=2, dropout=0.0,
                              use_kernel=uk)
    params = init_gnn(jax.random.PRNGKey(1), mk(False))

    def loss(params, cfg):
        emb = gnn_forward(params, cfg, h, src, dst, w_edge, deg)
        return jnp.sum(emb * emb)

    ref_val, ref_g = jax.value_and_grad(loss)(params, mk(False))
    with override(KernelConfig(strategy="pallas_fused")):
        val, g = jax.value_and_grad(loss)(params, mk(True))
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten(
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), g, ref_g))
    assert max(flat) < 3e-4, flat


# ---------------------------------------------------------------------------
# per-tile granule ranges: each node tile streams only the granules whose
# dst range meets it, so every arc layout must still land every arc
# ---------------------------------------------------------------------------
def _range_config(strategy):
    """Small tiles: several node tiles and granules at a test's size."""
    return KernelConfig(strategy=strategy, node_tile=64, edge_block=32,
                        feat_tile=128, stream=2)


def _arc_layout(kind, seed, n=300, f=20, e=700, fo=24):
    """A graph whose arc list has the layout ``kind``; e is no multiple of
    the granule, so the wrapper's alignment padding is always there.

    - ``k8_like``: real arcs only into the first third of the rows,
      sorted, then assemble's weight-0 padding parked at row N-1;
    - ``empty_tiles``: sorted arcs into rows 0-39 and 200-229 only, so
      node tiles 1 and 2 have no arcs;
    - ``unsorted``: arcs in random order, the first and the last granule
      each meeting every node tile."""
    rng = np.random.default_rng(seed)
    w_edge = rng.random(e).astype(np.float32)
    if kind == "k8_like":
        dst = np.full(e, n - 1)
        dst[:e - 90] = np.sort(rng.integers(0, n // 3, e - 90))
        w_edge[e - 90:] = 0.0
    elif kind == "empty_tiles":
        dst = np.sort(np.concatenate([rng.integers(0, 40, e // 2),
                                      rng.integers(200, 230, e - e // 2)]))
    else:
        spread = np.arange(0, n, 64)
        middle = rng.integers(0, n, e - 2 * spread.size)
        dst = np.concatenate([spread, middle, spread[::-1]])
    src = rng.integers(0, n, e)
    deg = np.bincount(dst, weights=w_edge > 0, minlength=n)
    params = {"w": jnp.asarray(rng.normal(size=(f, fo)) * 0.3, jnp.float32),
              "b": jnp.asarray(rng.normal(size=(fo,)) * 0.1, jnp.float32)}
    arrays = (rng.normal(size=(n, f)), src, dst, w_edge, deg)
    dtypes = (jnp.float32, jnp.int32, jnp.int32, jnp.float32, jnp.float32)
    return params, tuple(jnp.asarray(a, d) for a, d in zip(arrays, dtypes))


def _layer_loss(use_kernel):
    from repro.gnn.layers import gcn_layer

    def loss(params, h, src, dst, w_edge, deg):
        out = gcn_layer(params, h, src, dst, w_edge, deg,
                        use_kernel=use_kernel)
        return jnp.sum(out * jnp.cos(out)), out
    return jax.value_and_grad(loss, argnums=(0, 1, 4), has_aux=True)


def _assert_close_tree(got, want, tol):
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=tol, atol=tol), got, want)


@pytest.mark.parametrize("kind", ["k8_like", "empty_tiles", "unsorted"])
@pytest.mark.parametrize("strategy", ["pallas_fused", "pallas"])
def test_granule_ranges_layout_value_and_grad(kind, strategy):
    """Value, and the gradient w.r.t. the parameters, h and the edge
    weights (the transposed pass and the edge dot), of a GCN layer
    through the kernels match the segment-sum path on each layout."""
    params, args = _arc_layout(kind, 3)
    (_, ref), ref_g = _layer_loss(False)(params, *args)
    with override(_range_config(strategy)):
        (_, out), g = _layer_loss(True)(params, *args)
    _assert_close_tree(out, ref, 3e-5)
    _assert_close_tree(g, ref_g, 3e-4)


@pytest.mark.parametrize("shared_graph", [False, True])
@pytest.mark.parametrize("strategy", ["pallas_fused", "pallas"])
def test_granule_ranges_vmap_over_partitions(strategy, shared_graph):
    """vmap over three partitions whose granule ranges differ (one of
    each layout) — the training step's batching — matches each partition
    through the segment-sum path, in value and gradient. With a shared
    graph only the parameters and features are mapped, and the kernels
    take the arc list once per partition."""
    parts = [_arc_layout(kind, 7 + i) for i, kind in
             enumerate(("k8_like", "empty_tiles", "unsorted"))]
    if shared_graph:
        graph = parts[0][1][1:]
        parts = [(p, (a[0], *graph)) for p, a in parts]
    stack = lambda *xs: jnp.stack(xs)
    params = jax.tree.map(stack, *[p for p, _ in parts])
    args = jax.tree.map(stack, *[a for _, a in parts])
    in_axes = (0, 0) + ((None,) * 4 if shared_graph else (0,) * 4)
    if shared_graph:
        args = (args[0], *graph)
    with override(_range_config(strategy)):
        (_, out), g = jax.vmap(_layer_loss(True), in_axes=in_axes)(params,
                                                                   *args)
    for i, (p, a) in enumerate(parts):
        (_, ref), ref_g = _layer_loss(False)(p, *a)
        _assert_close_tree(out[i], ref, 3e-5)
        _assert_close_tree(jax.tree.map(lambda x: x[i], g), ref_g, 3e-4)


# ---------------------------------------------------------------------------
# autotune: resolution, candidates, cross-process cache determinism
# ---------------------------------------------------------------------------
def test_get_config_fallback_and_override():
    clear_memory_cache()
    cfg = get_config(100, 700, 24, backend="cpu")
    assert cfg.strategy == "xla"
    tpu = get_config(100, 700, 24, backend="tpu")
    assert tpu.uses_pallas
    forced = KernelConfig(strategy="pallas", node_tile=256)
    with override(forced):
        assert get_config(100, 700, 24, backend="cpu") is forced


def test_shape_bucket_is_stable_within_pow2_ranges():
    assert shape_bucket(100, 700, 24) == shape_bucket(128, 1024, 128)
    assert shape_bucket(100, 700, 24).key == "n128_e1024_f128"
    assert shape_bucket(129, 1025, 129).key == "n256_e2048_f256"


def test_candidate_space_respects_vmem_budget():
    bucket = ShapeBucket(n=8192, e=65536, f=128)
    cands = candidate_space(bucket, backend="tpu")
    assert cands, "tile sweep must not be empty for a mid-size bucket"
    for cfg in cands:
        assert cfg.uses_pallas
        assert vmem_bytes(bucket, cfg) <= VMEM_BUDGET
        assert cfg.edge_granule <= bucket.e


def test_candidate_space_past_gather_cliff_falls_back_to_xla():
    # The source rows are gathered by XLA before the kernel, so no VMEM term
    # grows with N: a 1M-node bucket keeps its Pallas candidates. Only a
    # layer too wide for the smallest [FT, FO] weight block and [NT, FO]
    # accumulator falls back to XLA (DESIGN.md §14).
    big = candidate_space(ShapeBucket(n=1 << 20, e=1 << 22, f=128),
                          backend="tpu")
    assert big and all(c.uses_pallas for c in big)
    assert big == candidate_space(ShapeBucket(n=1 << 13, e=1 << 22, f=128),
                                  backend="tpu")
    wide = candidate_space(ShapeBucket(n=1024, e=4096, f=8192),
                           backend="tpu")
    assert [c.strategy for c in wide] == ["xla"]


@pytest.mark.parametrize("bucket", [
    ShapeBucket(8, 128, 128), ShapeBucket(128, 1024, 128),
    ShapeBucket(131072, 524288, 128), ShapeBucket(1 << 20, 1 << 22, 256),
    ShapeBucket(1024, 4096, 2048), ShapeBucket(1024, 4096, 8192),
])
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_fallback_config_is_a_candidate_the_filter_accepts(bucket, backend):
    """The untuned path resolves to a config the tuner would accept: on
    TPU a Pallas tiling under the VMEM budget (or XLA when none fits),
    elsewhere XLA — never the interpreter."""
    cfg = fallback_config(bucket, backend)
    assert cfg in candidate_space(bucket, backend)
    if cfg.uses_pallas:
        assert backend == "tpu"
        assert vmem_bytes(bucket, cfg) <= VMEM_BUDGET


def test_factory_defaults_are_candidates_of_their_bucket():
    """Every packaged TPU entry passes the same filter as a tuned one."""
    from repro.kernels.autotune import _DEFAULTS_PATH, _configs_from_file
    entries = _configs_from_file(_DEFAULTS_PATH)
    assert entries
    for (backend, key), cfg in entries.items():
        n, e, f = (int(part[1:]) for part in key.split("_"))
        assert cfg in candidate_space(ShapeBucket(n, e, f), backend), key


def test_tpu_backend_never_selects_the_interpreter(monkeypatch):
    """With the backend reporting a TPU, every pallas_call the layer traces
    is compiled (interpret=False); nothing a caller passes can change it."""
    h, src, dst, w_edge, deg, w, b = _star_graph(3, 100, 24, 700, 16)
    # the mode is fixed when a jitted wrapper is traced: drop the traces
    # other tests made on the CPU, and the TPU ones made here afterwards
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        for strategy in ("pallas_fused", "pallas"):
            cfg = KernelConfig(strategy=strategy)
            jaxpr = str(jax.make_jaxpr(jax.grad(lambda h: fused_gcn_layer(
                h, src, dst, w_edge, deg, w, b, config=cfg).sum()))(h))
            flags = re.findall(r"interpret=(\w+)", jaxpr)
            assert len(flags) == jaxpr.count("pallas_call") == 3, strategy
            assert set(flags) == {"False"}, (strategy, flags)
    finally:
        jax.clear_caches()


def test_candidate_space_cpu_default_is_xla_only():
    env = os.environ.pop("REPRO_AUTOTUNE_EXHAUSTIVE", None)
    try:
        cands = candidate_space(ShapeBucket(512, 2048, 128), backend="cpu")
        assert [c.strategy for c in cands] == ["xla"]
    finally:
        if env is not None:
            os.environ["REPRO_AUTOTUNE_EXHAUSTIVE"] = env


_TUNE_SNIPPET = """
import json, sys
from repro.kernels.autotune import autotune, get_config
cfg, measured = autotune(600, 1500, 40)
print(json.dumps({"config": cfg.as_dict(), "measured": bool(measured),
                  "resolved": get_config(600, 1500, 40).as_dict()}))
"""


def test_autotune_cache_is_deterministic_across_processes(tmp_path):
    """Two fresh processes sharing REPRO_AUTOTUNE_CACHE resolve the same
    config; the second is a pure cache hit (no re-measurement)."""
    cache = tmp_path / "autotune_cache.json"
    env = dict(os.environ, REPRO_AUTOTUNE_CACHE=str(cache),
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _TUNE_SNIPPET], env=env,
                           capture_output=True, text=True, check=True)
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert outs[0]["config"] == outs[1]["config"]
    assert outs[0]["resolved"] == outs[0]["config"]
    assert not outs[1]["measured"], "second process must hit the disk cache"
    data = json.loads(cache.read_text())
    entries = data["configs"][jax.default_backend()]
    (key,) = entries.keys()
    assert key == shape_bucket(600, 1500, 40).key
    assert entries[key]["source"] == "tuned"


def test_autotune_in_process_cache_hit_returns_no_measurements(tmp_path,
                                                               monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "cache.json"))
    clear_memory_cache()
    try:
        cfg1, _ = autotune(100, 700, 24)
        cfg2, measured = autotune(100, 700, 24)
        assert cfg1 == cfg2
        assert measured == {}
    finally:
        clear_memory_cache()


# ---------------------------------------------------------------------------
# structured shape-contract error (S6)
# ---------------------------------------------------------------------------
def test_shape_contract_error_names_constraint_and_nearest_shape():
    h = jnp.ones((100, 50), jnp.float32)     # F=50 violates feat_tile=128
    src = jnp.zeros((700,), jnp.int32)       # E=700 violates granule
    dst = jnp.zeros((700,), jnp.int32)
    w = jnp.ones((700,), jnp.float32)
    with pytest.raises(ShapeContractError) as ei:
        csr_aggregate_pallas(h, src, dst, w, num_nodes=100)
    err = ei.value
    assert any("F=50" in f for f in err.failures)
    assert any("E=700" in f for f in err.failures)
    assert any("N=100" in f for f in err.failures)   # not a multiple of 8
    assert err.valid == (104, 128, 768)
    assert "repro.kernels.ops.csr_aggregate" in str(err)


def test_shape_contract_error_fused_output_lanes():
    from repro.kernels.fused_layer import fused_gcn_pallas
    h = jnp.ones((8, 128), jnp.float32)
    src = jnp.zeros((256,), jnp.int32)
    dst = jnp.zeros((256,), jnp.int32)
    w_edge = jnp.ones((256,), jnp.float32)
    wmat = jnp.ones((128, 60), jnp.float32)  # FO=60: not a lane multiple
    b = jnp.zeros((60,), jnp.float32)
    with pytest.raises(ShapeContractError, match="FO=60"):
        fused_gcn_pallas(h, src, dst, w_edge, num_nodes=8, wmat=wmat, b=b,
                         config=KernelConfig(strategy="pallas_fused",
                                             stream=1))


# ---------------------------------------------------------------------------
# serving integration (S2): engine config resolution
# ---------------------------------------------------------------------------
def test_inductive_engine_resolves_kernel_config():
    from repro.serving.inductive import InductiveEngine

    class _Store:
        embed_dim = 16
        partition_of = np.zeros(8, np.int64)

    eng = InductiveEngine(_Store(), max_neighbors=4, use_kernel=True)
    cfg = eng.kernel_config(8)
    assert isinstance(cfg, KernelConfig)
    assert cfg == get_config(8 * 5, 8 * 4, 16)
    assert InductiveEngine(_Store(), max_neighbors=4,
                           use_kernel=False).kernel_config(8) is None
