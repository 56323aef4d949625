"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the kernel bodies on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (csr_aggregate, csr_aggregate_ref, flash_decode,
                           flash_decode_ref)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# csr_aggregate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,f,e", [
    (8, 16, 32),          # tiny
    (100, 50, 700),       # unaligned everything
    (256, 128, 1024),     # exactly aligned
    (513, 130, 1500),     # off-by-one over tiles
    (64, 384, 256),       # multiple feature tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csr_aggregate_sweep(n, f, e, dtype):
    rng = np.random.default_rng(n * 7 + f)
    h = jnp.asarray(rng.normal(size=(n, f)), dtype)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(np.sort(rng.integers(0, n, e)), jnp.int32)
    w = jnp.asarray(rng.random(e), jnp.float32)
    out = csr_aggregate(h, src, dst, w, num_nodes=n)
    ref = csr_aggregate_ref(h, src, dst, w, num_nodes=n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_csr_aggregate_zero_weight_edges_are_noops():
    h = jnp.ones((16, 8))
    src = jnp.zeros((10,), jnp.int32)
    dst = jnp.arange(10, dtype=jnp.int32)
    w = jnp.zeros((10,))
    out = csr_aggregate(h, src, dst, w, num_nodes=16)
    assert float(jnp.abs(out).max()) == 0.0


def test_csr_aggregate_duplicate_destinations_accumulate():
    h = jnp.eye(4, 8)
    src = jnp.asarray([0, 1, 2, 3], jnp.int32)
    dst = jnp.zeros((4,), jnp.int32)     # everything lands on row 0
    w = jnp.ones((4,))
    out = csr_aggregate(h, src, dst, w, num_nodes=4)
    np.testing.assert_allclose(np.asarray(out[0, :4]), np.ones(4), rtol=1e-6)
    assert float(jnp.abs(out[1:]).max()) == 0.0


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.integers(4, 80), f=st.integers(1, 70), e=st.integers(1, 300))
def test_csr_aggregate_property(seed, n, f, e):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)  # unsorted is fine
    w = jnp.asarray(rng.random(e), jnp.float32)
    out = csr_aggregate(h, src, dst, w, num_nodes=n)
    ref = csr_aggregate_ref(h, src, dst, w, num_nodes=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def _random_csr(seed, n, f, e, sorted_dst=False, dst_range=None):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    d = rng.integers(0, dst_range or n, e)
    dst = jnp.asarray(np.sort(d) if sorted_dst else d, jnp.int32)
    w = jnp.asarray(rng.random(e), jnp.float32)
    return h, src, dst, w


# ---------------------------------------------------------------------------
# csr_aggregate: custom VJP (the kernel is a real training path now)
# ---------------------------------------------------------------------------
def _grad_pair(h, src, dst, w, n):
    """(d/dh, d/dw) of a non-trivial scalar loss, kernel vs segment-sum."""
    def loss(agg_fn, h, w):
        out = agg_fn(h, src, dst, w, num_nodes=n)
        return (out * jnp.cos(h)).sum() + (out ** 2).sum()
    gk = jax.grad(lambda h, w: loss(csr_aggregate, h, w), (0, 1))(h, w)
    gr = jax.grad(lambda h, w: loss(csr_aggregate_ref, h, w), (0, 1))(h, w)
    return gk, gr


@pytest.mark.parametrize("n,f,e,sorted_dst", [
    (8, 16, 32, True),        # tiny
    (100, 50, 700, False),    # unaligned everything, unsorted dst
    (256, 128, 1024, True),   # exactly aligned
    (600, 30, 1500, False),   # node-tiled (> NODE_TILE after padding)
])
def test_csr_aggregate_grads_match_segment_sum(n, f, e, sorted_dst):
    h, src, dst, w = _random_csr(n * 3 + f, n, f, e, sorted_dst)
    (dh_k, dw_k), (dh_r, dw_r) = _grad_pair(h, src, dst, w, n)
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=3e-4, atol=3e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.integers(4, 90), f=st.integers(1, 80), e=st.integers(1, 400))
def test_csr_aggregate_grad_property(seed, n, f, e):
    """Hypothesis sweep for the custom VJP: arbitrary shapes (incl.
    non-multiples of every tile size), duplicate destinations, zero-degree
    nodes (dst restricted to the first half guarantees in-degree-0 nodes),
    unsorted dst — grads must match the segment-sum path."""
    h, src, dst, w = _random_csr(seed, n, f, e,
                                 dst_range=max(1, n // 2))
    (dh_k, dw_k), (dh_r, dw_r) = _grad_pair(h, src, dst, w, n)
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=5e-4, atol=5e-4)


def test_node_tiled_kernel_beyond_vmem_cap():
    """A partition with > 8192 nodes (the old whole-node-dimension VMEM cap)
    must aggregate correctly through the node-tiled grid, forward and
    backward."""
    n, f, e = 8700, 8, 4096
    h, src, dst, w = _random_csr(11, n, f, e, sorted_dst=True)
    out = csr_aggregate(h, src, dst, w, num_nodes=n)
    ref = csr_aggregate_ref(h, src, dst, w, num_nodes=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    dh_k = jax.grad(lambda h: csr_aggregate(
        h, src, dst, w, num_nodes=n).sum())(h)
    dh_r = jax.grad(lambda h: csr_aggregate_ref(
        h, src, dst, w, num_nodes=n).sum())(h)
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r),
                               rtol=3e-5, atol=3e-5)


def test_padding_contract_zero_weight_arcs_noop_on_both_paths():
    """THE padding contract (repro.kernels.ops): arcs with weight 0 are
    no-ops wherever they point — row 0 (the kernel wrapper's alignment
    padding), row N-1 (assemble's parked arcs), or anywhere else — on both
    the jnp and kernel paths, with unsorted dst, in value AND gradient."""
    from repro.gnn.layers import aggregate_mean
    rng = np.random.default_rng(5)
    n, f, e = 33, 7, 90
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)     # unsorted
    w = jnp.asarray(rng.random(e), jnp.float32)
    deg = jnp.asarray(np.bincount(np.asarray(dst), weights=np.asarray(w) > 0,
                                  minlength=n), jnp.float32)
    # junk arcs: parked at row 0, at row N-1, and scattered — all weight 0
    junk_dst = np.concatenate([np.zeros(4), np.full(4, n - 1),
                               rng.integers(0, n, 4)]).astype(np.int32)
    junk_src = rng.integers(0, n, junk_dst.size).astype(np.int32)
    src2 = jnp.concatenate([src, jnp.asarray(junk_src)])
    dst2 = jnp.concatenate([dst, jnp.asarray(junk_dst)])
    w2 = jnp.concatenate([w, jnp.zeros(junk_dst.size, jnp.float32)])
    for use_kernel in (False, True):
        base = aggregate_mean(h, src, dst, w, deg, use_kernel)
        padded = aggregate_mean(h, src2, dst2, w2, deg, use_kernel)
        np.testing.assert_allclose(np.asarray(base), np.asarray(padded),
                                   rtol=1e-5, atol=1e-5)
        g_base = jax.grad(lambda h: aggregate_mean(
            h, src, dst, w, deg, use_kernel).var())(h)
        g_padded = jax.grad(lambda h: aggregate_mean(
            h, src2, dst2, w2, deg, use_kernel).var())(h)
        np.testing.assert_allclose(np.asarray(g_base), np.asarray(g_padded),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# streamed_pairs: the (node tile, edge granule) pairs the kernels stream
# ---------------------------------------------------------------------------
def _stream_cfg():
    from repro.kernels.autotune import KernelConfig
    return KernelConfig(strategy="pallas", node_tile=64, edge_block=32,
                        stream=2)


def _kernel_ranges(dst, n, cfg):
    """The per-tile ranges the kernel computes, after the wrapper's
    padding: ``(g0, g1, G)``."""
    from repro.kernels.csr_aggregate import (edge_block_ranges,
                                             tile_granule_ranges)
    from repro.kernels.ops import _pad_graph
    e = dst.shape[0]
    h = jnp.zeros((n, 1), jnp.float32)
    _, _, ed, _, _, n_pad = _pad_graph(h, jnp.zeros(e, jnp.int32),
                                       jnp.asarray(dst, jnp.int32),
                                       jnp.zeros(e), None, cfg)
    tiles = n_pad // min(n_pad, cfg.node_tile)
    lo, hi = edge_block_ranges(ed, cfg.edge_block)
    g0, g1 = (np.asarray(r).reshape(-1)[:tiles]
              for r in tile_granule_ranges(lo, hi, n_pad, ed.shape[0], cfg))
    return g0, g1, ed.shape[0] // cfg.edge_granule


def test_streamed_pairs_sorted_is_at_most_granules_plus_tiles():
    """On a sorted list the ranges visit at most G + T - 1 pairs, and the
    host count agrees with the ranges the kernel computes."""
    from repro.kernels import streamed_pairs
    cfg = _stream_cfg()
    n, e = 1000, 5000
    dst = np.sort(np.random.default_rng(0).integers(0, n, e))
    streamed, dense = streamed_pairs(dst, n, cfg)
    g0, g1, granules = _kernel_ranges(dst, n, cfg)
    tiles = g0.size
    assert dense == tiles * granules
    assert streamed == int(np.maximum(g1 - g0 + 1, 0).sum())
    assert streamed <= granules + tiles - 1


def test_streamed_pairs_alignment_granule_spans_one_tile():
    """Arcs into the first third of the rows, then assemble's padding
    parked at row N-1, with E no multiple of the granule: the wrapper's
    alignment arcs repeat the last destination, so the last granule meets
    the last tile only (padding with row 0 made it span every tile)."""
    from repro.kernels import streamed_pairs
    cfg = _stream_cfg()
    n, e = 1000, 5000 + 17
    dst = np.full(e, n - 1)
    dst[:4000] = np.sort(np.random.default_rng(1).integers(0, n // 3, 4000))
    g0, g1, granules = _kernel_ranges(dst, n, cfg)
    meets_last = np.flatnonzero((g0 <= granules - 1) & (g1 >= granules - 1))
    assert meets_last.tolist() == [g0.size - 1]
    # tiles past the arcs' third and before the last one stream at most
    # the granule where the real arcs end and the parked ones begin
    lonely = np.arange((n // 3) // 64 + 1, g0.size - 1)
    assert np.all(g1[lonely] - g0[lonely] + 1 <= 1)
    assert np.all(g0[lonely] >= 4000 // cfg.edge_granule)
    streamed, _ = streamed_pairs(dst, n, cfg)
    assert streamed <= granules + g0.size - 1


def test_streamed_pairs_unsorted_matches_dense_coverage():
    """An unsorted list whose first and last granules meet every tile
    streams every (tile, granule) pair — today's coverage, so results
    never depend on the order (values pinned in test_fused_layer.py)."""
    from repro.kernels import streamed_pairs
    cfg = _stream_cfg()
    n, e = 1000, 79 * 64
    spread = np.arange(0, n, 64)
    rng = np.random.default_rng(2)
    dst = np.concatenate([spread, rng.integers(0, n, e - 2 * spread.size),
                          spread[::-1]])
    streamed, dense = streamed_pairs(dst, n, cfg)
    assert streamed == dense
    # [k, E] lists count per partition and sum
    both = streamed_pairs(np.stack([dst, np.sort(dst)]), n, cfg)
    assert both == (dense + streamed_pairs(np.sort(dst), n, cfg)[0],
                    2 * dense)


def test_aggregate_mean_kernel_path_is_one_fused_call():
    """Degree normalization is fused into the kernel epilogue: the kernel
    path's jaxpr contains exactly one pallas_call (pallas strategy forced —
    on interpret-mode backends the autotuner resolves to "xla")."""
    from repro.gnn.layers import aggregate_mean
    from repro.kernels.autotune import KernelConfig, override
    h, src, dst, w = _random_csr(0, 16, 8, 24)
    deg = jnp.ones((16,))
    with override(KernelConfig(strategy="pallas")):
        jaxpr = str(jax.make_jaxpr(
            lambda h: aggregate_mean(h, src, dst, w, deg,
                                     use_kernel=True))(h))
    assert jaxpr.count("pallas_call") == 1


def test_aggregate_mean_kernel_path_xla_strategy_has_no_pallas_call():
    """On backends where the autotuner resolves to the "xla" strategy the
    kernel path must lower with NO interpret-mode pallas_call — same math,
    no emulator (DESIGN.md §14)."""
    from repro.gnn.layers import aggregate_mean
    from repro.kernels.autotune import KernelConfig, override
    h, src, dst, w = _random_csr(0, 16, 8, 24)
    deg = jnp.ones((16,))
    with override(KernelConfig(strategy="xla")):
        jaxpr = str(jax.make_jaxpr(
            lambda h: aggregate_mean(h, src, dst, w, deg,
                                     use_kernel=True))(h))
        out = aggregate_mean(h, src, dst, w, deg, use_kernel=True)
    assert jaxpr.count("pallas_call") == 0
    ref = aggregate_mean(h, src, dst, w, deg, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv,d,s,length", [
    (8, 8, 64, 600, 600),      # MHA, full cache
    (8, 2, 64, 1000, 777),     # GQA 4:1, partial
    (16, 1, 128, 2048, 1),     # MQA, single valid token
    (4, 4, 128, 512, 512),     # aligned block boundary
    (32, 8, 128, 1537, 1111),  # odd cache length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(hq, hkv, d, s, length, dtype):
    rng = np.random.default_rng(hq * 131 + s)
    q = jnp.asarray(rng.normal(size=(hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(s, hkv, d)), dtype)
    out = flash_decode(q, k, v, jnp.asarray(length))
    ref = flash_decode_ref(q, k, v, jnp.asarray(length))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_decode_ignores_stale_cache():
    """Rows past `length` must not influence the result."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(256, 2, 64)), jnp.float32)
    out1 = flash_decode(q, k, v, jnp.asarray(100))
    k2 = k.at[100:].set(999.0)
    v2 = v.at[100:].set(-999.0)
    out2 = flash_decode(q, k2, v2, jnp.asarray(100))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_flash_decode_is_softmax_weighted_average():
    """With identical V rows the output equals that row, any mask."""
    q = jnp.ones((2, 32))
    k = jnp.asarray(np.random.default_rng(1).normal(size=(128, 1, 32)),
                    jnp.float32)
    v = jnp.broadcast_to(jnp.arange(32, dtype=jnp.float32), (128, 1, 32))
    out = flash_decode(q, k, v, jnp.asarray(77))
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.arange(32), (2, 32)),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# kernel-in-model integration: GNN layer with use_kernel=True
# ---------------------------------------------------------------------------
def test_gnn_layer_kernel_path_matches_jnp_path():
    from repro.gnn.layers import aggregate_mean
    rng = np.random.default_rng(3)
    n, f, e = 60, 24, 200
    h = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(np.sort(rng.integers(0, n, e)), jnp.int32)
    w = jnp.asarray(rng.random(e), jnp.float32)
    deg = jnp.asarray(np.bincount(np.asarray(dst), weights=None,
                                  minlength=n), jnp.float32)
    a = aggregate_mean(h, src, dst, w, deg, use_kernel=False)
    b = aggregate_mean(h, src, dst, w, deg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                               atol=3e-5)


def _tiny_partition_setup(use_kernel, dropout=0.0):
    import dataclasses
    from repro.core import (make_arxiv_like, leiden_fusion,
                            build_partition_batch)
    from repro.gnn import GNNConfig, gather_partition_tensors
    ds = make_arxiv_like(n=250, feature_dim=8, num_classes=4, seed=9)
    labels = leiden_fusion(ds.graph, 2, alpha=0.3)
    batch = build_partition_batch(ds.graph, labels, scheme="repli")
    pt = gather_partition_tensors(ds, batch)
    tensors = {k: jnp.asarray(v) for k, v in {
        "features": pt.features, "labels": pt.labels,
        "train_mask": pt.train_mask, "edge_src": pt.edge_src,
        "edge_dst": pt.edge_dst, "edge_weight": pt.edge_weight,
        "in_degree": pt.in_degree, "node_mask": pt.node_mask}.items()}
    cfg = GNNConfig(kind="gcn", feature_dim=8, hidden_dim=16, embed_dim=16,
                    num_layers=2, dropout=dropout, use_kernel=use_kernel)
    return ds, batch, cfg, tensors


def test_local_train_step_with_kernel_runs_and_matches_jnp():
    """Regression anchor: one ``make_local_train_step`` step with
    ``use_kernel=True`` must run (this used to die in a bare AssertionError
    — the kernel had no VJP) and produce the jnp path's loss, grads, and
    updated params. Grads are cross-checked twice: against the segment-sum
    path and against a central finite difference."""
    from repro.gnn import init_partition_models, make_local_train_step
    from repro.gnn.train import _loss_one
    from repro.optim import adamw_init
    results = {}
    for use_kernel in (False, True):
        ds, batch, cfg, tensors = _tiny_partition_setup(use_kernel)
        params = init_partition_models(jax.random.PRNGKey(0), cfg,
                                       ds.num_classes, batch.k)
        opt = jax.vmap(adamw_init)(params)
        step = jax.jit(make_local_train_step(cfg, False, lr=1e-2))
        keys = jax.random.split(jax.random.PRNGKey(1), batch.k)
        new_p, _, loss = step(params, opt, tensors, keys)
        t0 = jax.tree.map(lambda x: x[0], tensors)
        p0 = jax.tree.map(lambda x: x[0], params)
        grads = jax.grad(_loss_one)(p0, cfg, t0, False, None)
        results[use_kernel] = (np.asarray(loss), new_p, grads, p0, t0, cfg)
    loss_j, p_j, g_j = results[False][:3]
    loss_k, p_k, g_k, p0, t0, cfg_k = results[True]
    np.testing.assert_allclose(loss_k, loss_j, rtol=1e-4, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4), g_k, g_j)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4), p_k, p_j)
    # finite-difference probe of the kernel-path gradient: perturb the first
    # GNN layer's weight matrix along a random direction
    rng = np.random.default_rng(2)
    d = rng.normal(size=np.asarray(p0["body"]["layers"][0]["w"]).shape)
    d = jnp.asarray(d / np.linalg.norm(d), jnp.float32)
    eps = 3e-2

    def at(t):
        p = jax.tree.map(lambda x: x, p0)
        p["body"]["layers"][0] = dict(p["body"]["layers"][0],
                                      w=p0["body"]["layers"][0]["w"] + t * d)
        return float(_loss_one(p, cfg_k, t0, False, None))

    fd = (at(eps) - at(-eps)) / (2 * eps)
    analytic = float(jnp.vdot(g_k["body"]["layers"][0]["w"], d))
    np.testing.assert_allclose(fd, analytic, rtol=5e-2, atol=5e-3)


def test_serve_step_flash_decode_matches_jnp_path():
    """cfg.use_flash_decode routes decode attention through the Pallas
    kernel; logits must match the jnp path."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import init_cache, init_model, serve_step
    cfg = get_config("qwen3_4b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    cfgk = dataclasses.replace(cfg, use_flash_decode=True)
    tok = jnp.ones((2, 1), jnp.int32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    cache = init_cache(cfg, 2, 64)
    # fill the cache with noise so the mask matters
    cache = jax.tree.map(
        lambda x: jnp.asarray(np.random.default_rng(0).normal(
            0, 0.1, x.shape), x.dtype), cache)
    l1, _ = serve_step(params, cfg, tok, cache, lengths)
    l2, _ = serve_step(params, cfgk, tok, cache, lengths)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-3, atol=2e-3)
