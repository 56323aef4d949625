"""The GAT layer (arXiv:1710.10903 eq. (1)-(6); DESIGN.md §17) and the
local step's partition groups, at small sizes on the CPU.

The program's layer is compared with the plain reference the benchmark
keeps (``bench/models/gat.py``, which imports nothing of the program) on
seeded random weights, on values and on the gradient of every leaf; the
kernel path (the Pallas aggregation in interpret mode, under a forced
config) with the jnp path; padding arcs and rows with no in-arcs; groups
of partitions against the one vmapped step; the group chooser; and GAT
through the halo modes and the pipeline."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gnn import (GNNConfig, gnn_forward, init_partition_models,
                       make_local_train_step)
from repro.gnn.layers import gat_layer, init_gat_layer, layer_for
from repro.gnn.train import (GROUP_MARGIN, _local_embed,
                             choose_partition_group, step_bytes_estimate)
from repro.kernels.autotune import KernelConfig, override
from repro.optim import adamw_init

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the interpret-mode Pallas aggregation at a tile small enough that the
# test graphs span several node tiles
PALLAS = KernelConfig(strategy="pallas", node_tile=16, edge_block=128,
                      feat_tile=128, stream=1)

# float32 sums in another order (the kernel's one-hot matmul per node tile,
# segment_sum's scatter; a vmap's fused ops against a loop's): a few ulps
# of values of order 1-10, so 2e-5 relative with a 1e-5 floor
TOL = dict(rtol=2e-5, atol=1e-5)


def _graph(n=40, e=150, f=12, pad=10, seed=0):
    """A destination-sorted arc list whose last ``pad`` arcs are padding
    (weight 0, parked at row n-1 as the assembly parks them), and whose
    last three rows have no in-arc."""
    rng = np.random.default_rng(seed)
    dst = np.sort(rng.integers(0, n - 3, e)).astype(np.int32)
    src = rng.integers(0, n, e).astype(np.int32)
    w = np.ones(e, np.float32)
    w[-pad:] = 0.0
    dst[-pad:] = n - 1
    h = rng.normal(size=(n, f)).astype(np.float32)
    return h, src, dst, w


def _layer_loss(params, h, src, dst, w, concat, use_kernel):
    out = gat_layer(params, h, src, dst, w, None, activate=concat,
                    use_kernel=use_kernel)
    return jnp.sum(jnp.sin(out)), out


def _assert_trees_close(a, b, **tol):
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   err_msg=jax.tree_util.keystr(path), **tol)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def test_program_matches_the_reference_on_values_and_every_gradient():
    """Two partitions, three layers, three heads: the program's body and
    head (``gnn_forward`` per partition) against ``bench/models/gat.py``'s
    forward, from the same seed through each side's own init; the loss's
    gradient for every leaf, ``a_l`` and ``a_r`` included."""
    from bench import reference
    from bench.models import gat
    config = {"heads": 3, "head_dim": 6, "embed_dim": 5, "num_layers": 3,
              "dropout": 0.0, "lr": 0.01}
    k, f, classes = 2, 12, 4
    cfg = GNNConfig(kind="gat", feature_dim=f, hidden_dim=6, embed_dim=5,
                    num_layers=3, dropout=0.0, heads=3)
    m = reference.Model(module=gat, config=config, feature_dim=f,
                        num_classes=classes, sync=False)
    graphs = [_graph(seed=s) for s in range(k)]
    t = {"x": jnp.asarray(np.stack([g[0] for g in graphs])),
         "src": jnp.asarray(np.stack([g[1] for g in graphs])),
         "dst": jnp.asarray(np.stack([g[2] for g in graphs])),
         "w": jnp.asarray(np.stack([g[3] for g in graphs])),
         "mask": jnp.ones((k, 40), jnp.float32).at[:, -2:].set(0.0)}
    seed = 2**31 + 5
    params = init_partition_models(jax.random.PRNGKey(seed), cfg, classes, k)
    ref_params = gat.init_params(seed, m, k)
    # the same draws; the reference's jit may fuse a scale into the draw
    # (one ulp)
    _assert_trees_close(params, ref_params, rtol=2.5e-7, atol=0)

    def program(p):
        def one(p1, x, s, d, w, mask):
            emb = gnn_forward(p1["body"], cfg, x, s, d, w, None,
                              node_mask=mask)
            return emb, emb @ p1["head"]["w"] + p1["head"]["b"]
        return jax.vmap(one)(p, t["x"], t["src"], t["dst"], t["w"],
                             t["mask"])

    def loss(forward, p):
        emb, logits = forward(p)
        return jnp.sum(jnp.sin(logits)) + jnp.sum(emb ** 2), (emb, logits)

    ar = reference.Arithmetic(head="highest")
    (v0, (e0, l0)), g0 = jax.value_and_grad(
        lambda p: loss(lambda q: gat.forward(q, m, t, None, ar), p),
        has_aux=True)(ref_params)
    (v1, (e1, l1)), g1 = jax.value_and_grad(
        lambda p: loss(program, p), has_aux=True)(params)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e0), **TOL)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), **TOL)
    assert abs(float(v1) - float(v0)) <= 1e-5 * abs(float(v0))
    _assert_trees_close(g1, g0, **TOL)
    assert float(jnp.abs(g1["body"]["layers"][0]["a_l"]).max()) > 0


@pytest.mark.parametrize("concat", [True, False])
def test_kernel_path_matches_jnp_path(concat):
    """The per-head arc sum through the Pallas aggregation (interpret mode)
    and its VJP, against gather + segment_sum: the output and the
    gradients of the parameters and of the input rows."""
    h, src, dst, w = _graph()
    params = init_gat_layer(jax.random.PRNGKey(1), 12, 6, heads=3,
                            concat=concat)
    grad = jax.value_and_grad(_layer_loss, argnums=(0, 1), has_aux=True)
    (v0, out0), g0 = grad(params, h, src, dst, w, concat, False)
    with override(PALLAS):
        (v1, out1), g1 = grad(params, h, src, dst, w, concat, True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out0), **TOL)
    _assert_trees_close(g1, g0, **TOL)


def test_kernel_path_folds_heads_into_one_aggregation_call():
    """The heads ride the kernel's partition axis: the forward is one
    ``pallas_call`` whose grid's leading axis is the 3 heads."""
    h, src, dst, w = _graph()
    params = init_gat_layer(jax.random.PRNGKey(1), 12, 6, heads=3)
    with override(PALLAS):
        text = str(jax.make_jaxpr(lambda p, x: gat_layer(
            p, x, src, dst, w, None, use_kernel=True))(params, h))
    assert text.count("pallas_call[") == 1
    assert "grid=(3, " in text


@pytest.mark.parametrize("use_kernel", [False, True])
def test_padding_arcs_are_no_ops(use_kernel):
    """Weight-0 arcs, wherever they point, change nothing: the layer on
    the list with them equals the layer on the list without them."""
    h, src, dst, w = _graph(pad=10)
    params = init_gat_layer(jax.random.PRNGKey(2), 12, 6, heads=3)
    moved_src = src.copy()
    moved_src[-10:] = np.arange(10)              # point them elsewhere
    with override(PALLAS):
        full = gat_layer(params, h, moved_src, dst, w, None,
                         use_kernel=use_kernel)
        real = gat_layer(params, h, src[:-10], dst[:-10], w[:-10], None,
                         use_kernel=use_kernel)
    np.testing.assert_allclose(np.asarray(full), np.asarray(real), **TOL)


@pytest.mark.parametrize("concat", [True, False])
def test_row_with_no_in_arcs_reads_its_self_term(concat):
    """The self arc is the whole softmax of a row no arc enters: its
    attention is 1, so the layer gives its own projected row."""
    h, src, dst, w = _graph()
    params = init_gat_layer(jax.random.PRNGKey(3), 12, 6, heads=3,
                            concat=concat)
    params["b"] = params["b"] + 0.25
    out = gat_layer(params, h, src, dst, w, None, activate=concat)
    z = (h @ params["w"]).reshape(40, 3, 6)
    lonely = [37, 38]                             # rows no real arc enters
    want = (jax.nn.elu(z.reshape(40, 18) + params["b"]) if concat
            else z.mean(axis=1) + params["b"])
    np.testing.assert_allclose(np.asarray(out)[lonely],
                               np.asarray(want)[lonely], **TOL)


def test_layer_for_names_each_kind_and_refuses_others():
    from repro.gnn.layers import gcn_layer, sage_layer
    assert layer_for("gcn")[0] is gcn_layer
    assert layer_for("sage")[0] is sage_layer
    assert layer_for("gat")[0] is gat_layer
    with pytest.raises(ValueError, match="unknown GNN kind 'gin'"):
        layer_for("gin")
    with pytest.raises(ValueError, match="only a 'gat' layer"):
        GNNConfig(kind="gcn", heads=2)


# ---------------------------------------------------------------------------
# partition groups
# ---------------------------------------------------------------------------
def _tiny_tensors(k=4, n=40, f=12, classes=4):
    graphs = [_graph(n=n, f=f, seed=10 + p) for p in range(k)]
    rng = np.random.default_rng(7)
    return {"features": jnp.asarray(np.stack([g[0] for g in graphs])),
            "labels": jnp.asarray(rng.integers(0, classes, (k, n)),
                                  jnp.int32),
            "train_mask": jnp.asarray(rng.random((k, n)) < 0.5, jnp.float32),
            "edge_src": jnp.asarray(np.stack([g[1] for g in graphs])),
            "edge_dst": jnp.asarray(np.stack([g[2] for g in graphs])),
            "edge_weight": jnp.asarray(np.stack([g[3] for g in graphs])),
            "in_degree": jnp.ones((k, n), jnp.float32),
            "node_mask": jnp.ones((k, n), jnp.float32)}


@pytest.fixture(scope="module")
def all_at_once():
    """Two steps and the embedding pass of the vmapped step (g = k = 4)."""
    return _train_in_groups(4)


def _train_in_groups(group):
    cfg = GNNConfig(kind="gat", feature_dim=12, hidden_dim=6, embed_dim=5,
                    num_layers=2, dropout=0.3, heads=2)
    tensors = _tiny_tensors()
    params = init_partition_models(jax.random.PRNGKey(0), cfg, 4, 4)
    opt = jax.vmap(adamw_init)(params)
    step = jax.jit(make_local_train_step(cfg, False, 1e-2, group=group))
    losses = []
    for e in range(2):
        keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), e),
                                4)
        params, opt, loss = step(params, opt, tensors, keys)
        losses.append(np.asarray(loss))
    emb = _local_embed(cfg, group)(params, tensors)
    return np.stack(losses), params, np.asarray(emb)


@pytest.mark.parametrize("group", [1, 2])
def test_groups_train_as_the_vmapped_step(all_at_once, group):
    """Partitions never interact in local mode and keep their own dropout
    keys, so groups of 1 or 2 give the losses, parameters and table of
    all 4 at once; only XLA's fusion of the batched ops differs (TOL)."""
    losses, params, emb = _train_in_groups(group)
    np.testing.assert_allclose(losses, all_at_once[0], **TOL)
    _assert_trees_close(params, all_at_once[1], **TOL)
    np.testing.assert_allclose(emb, all_at_once[2], **TOL)


def test_groups_lower_to_a_loop_and_all_at_once_to_the_plain_vmap():
    cfg = GNNConfig(kind="gcn", feature_dim=12, hidden_dim=8, embed_dim=8,
                    num_layers=2, dropout=0.0)
    tensors = _tiny_tensors()
    params = init_partition_models(jax.random.PRNGKey(0), cfg, 4, 4)
    opt = jax.vmap(adamw_init)(params)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def text(group):
        step = make_local_train_step(cfg, False, 1e-2, group=group)
        return jax.jit(step).lower(params, opt, tensors, keys).as_text()
    assert text(4) == text(None)
    assert "while" not in text(4)
    assert "while" in text(2)


CHOOSER = GNNConfig(kind="gat", feature_dim=128, hidden_dim=250,
                    embed_dim=256, num_layers=3, heads=3)
SHAPE = dict(k=8, n_pad=79_344, e_pad=325_288, num_classes=40)


def _fits(g):
    """The least limit under which groups of g fit."""
    est = step_bytes_estimate(CHOOSER, SHAPE["k"], SHAPE["n_pad"],
                              SHAPE["e_pad"], g, SHAPE["num_classes"])
    return int(est / (1 - GROUP_MARGIN)) + 1


@pytest.mark.parametrize("limit,want", [
    (None, 8), ("fits 8", 8), ("fits 4", 4), ("between 2 and 4", 2),
    ("fits 1", 1), ("nothing fits", 1)])
def test_chooser_takes_the_largest_divisor_that_fits(limit, want):
    limits = {None: None, "fits 8": _fits(8), "fits 4": _fits(4),
              "between 2 and 4": (_fits(2) + _fits(4)) // 2,
              "fits 1": _fits(1), "nothing fits": _fits(1) // 2}
    g, est = choose_partition_group(CHOOSER, bytes_limit=limits[limit],
                                    **SHAPE)
    assert g == want
    assert est == step_bytes_estimate(CHOOSER, SHAPE["k"], SHAPE["n_pad"],
                                      SHAPE["e_pad"], g,
                                      SHAPE["num_classes"])


def test_estimate_grows_with_the_group_and_the_heads():
    ests = [step_bytes_estimate(CHOOSER, 8, 79_344, 325_288, g, 40)
            for g in (1, 2, 4, 8)]
    assert ests == sorted(ests) and len(set(ests)) == 4
    one_head = dataclasses.replace(CHOOSER, heads=1)
    assert step_bytes_estimate(one_head, 8, 79_344, 325_288, 1, 40) < ests[0]


# ---------------------------------------------------------------------------
# the halo modes and the pipeline
# ---------------------------------------------------------------------------
HALO = """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core import (make_arxiv_like, leiden_fusion, build_partition_batch,
                        build_halo_exchange)
from repro.gnn import GNNConfig, train_local, train_stale, train_sync
from repro.kernels.autotune import KernelConfig, override

ds = make_arxiv_like(n=300, feature_dim=8, num_classes=4, seed=3)
labels = leiden_fusion(ds.graph, 4, alpha=0.3)
batch = build_partition_batch(ds.graph, labels, scheme="repli")
halo = build_halo_exchange(ds.graph, labels, batch)
mesh = jax.make_mesh((4,), ("data",))
cfg = GNNConfig(kind="gat", feature_dim=8, hidden_dim=6, embed_dim=6,
                num_layers=2, dropout=0.2, heads=2, use_kernel={use_kernel})

def maxdiff(a, b):
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

with override(KernelConfig(strategy="pallas", node_tile=16,
                           edge_block=128, feat_tile=128, stream=1)):
    p_sync, e_sync = train_sync(ds, batch, halo, cfg, mesh, epochs=3, seed=0)
    p_st1, e_st1 = train_stale(ds, batch, halo, cfg, mesh, epochs=3, seed=0,
                               sync_period=1)
    p_loc, e_loc = train_local(ds, batch, cfg, epochs=3, seed=0, mesh=None)
    p_st0, e_st0 = train_stale(ds, batch, halo, cfg, mesh, epochs=3, seed=0,
                               sync_period=0)
print("FINITE", bool(np.isfinite(e_sync).all()))
print("MOVED", maxdiff(p_sync, p_loc))
print("SYNC_VS_STALE1", maxdiff(p_sync, p_st1), float(np.abs(e_sync - e_st1).max()))
print("LOCAL_VS_STALE0", maxdiff(p_loc, p_st0), float(np.abs(e_loc - e_st0).max()))
"""


@pytest.mark.parametrize("use_kernel", [False, True])
def test_gat_trains_through_the_halo_modes(use_kernel):
    """GAT through ``train_sync`` and ``train_stale`` (4 virtual devices):
    finite, the halo refresh moves the result away from local training,
    stale(1) is sync and stale(never) is local (to the tolerance the GCN's
    limits keep, tests/test_stale_mode.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(
            HALO.format(use_kernel=use_kernel))],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = {ln.split()[0]: ln.split()[1:] for ln in out.stdout.splitlines()
             if ln.strip()}
    assert lines["FINITE"] == ["True"]
    assert float(lines["MOVED"][0]) > 1e-4
    assert max(map(float, lines["SYNC_VS_STALE1"])) < 1e-5
    assert max(map(float, lines["LOCAL_VS_STALE0"])) < 1e-5


def test_pipeline_trains_gat_exports_and_serves(tmp_path):
    """``--model gat --heads 2`` through the pipeline: the report prints
    the partition group, and the exported bundle answers a known node and
    an unseen one."""
    from repro.pipeline import (Pipeline, PipelineConfig, graph_fingerprint,
                                make_karate_dataset)
    from repro.serving import ContinuousBatcher, EmbeddingStore
    ds = make_karate_dataset()
    cfg = PipelineConfig(dataset="karate", k=4, mode="local", model="gat",
                         heads=2, epochs=3, classifier_epochs=10,
                         hidden_dim=8, embed_dim=8, num_layers=2,
                         dropout=0.0, cache_dir=str(tmp_path / "cache"),
                         collect_hlo=False,
                         serving_dir=str(tmp_path / "srv"))
    report = Pipeline(cfg).run(ds)
    assert report.shapes["partition_group"] == 4
    assert report.shapes["step_bytes_estimate"] > 0
    assert "partition_group=4" in report.summary()
    store = EmbeddingStore.load(report.serving_path,
                                expect_fingerprint=report.partition_fingerprint,
                                expect_graph=graph_fingerprint(ds.graph))
    assert store.embed_dim == 8
    b = ContinuousBatcher(store, max_batch=8, max_wait_ms=0.1)
    b.submit(5)
    b.submit(store.n + 3, neighbors=[0, 1, 2])
    known, unseen = sorted(b.drain(), key=lambda a: a.qid)
    assert known.source != "inductive" and known.label == int(
        store.predictions[5])
    assert unseen.source == "inductive"
    assert 0 <= unseen.label < ds.num_classes
