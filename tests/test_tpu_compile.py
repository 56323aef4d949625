"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts — a kernel slice the
tiling cannot hold, a 1-D block laid out differently from XLA, a program
over the device's memory — so these tests compile the kernels and the
local train step at the ogbn-arxiv k=8 ``repli`` shape (ROADMAP W1; n_pad
79,344, e_pad 325,288, F=128; the fused layer also at the hidden width
F=256; the GAT step at the ``arxiv-gat`` cell's 3 heads of 250) for one
chip of a described ``v5e:2x2``.

The topology is described inside a module fixture, never while modules
are imported: only the worker that runs this file loads the TPU library.
Code that asks ``jax.default_backend()`` would still see the CPU and take
its interpret-mode branch, so each test reports a TPU backend through
``monkeypatch``. The persistent compilation cache is off here: a program
compiled for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.gnn import GNNConfig, init_partition_models, make_local_train_step
from repro.gnn.train import choose_partition_group
from repro.kernels.autotune import (KernelConfig, fallback_config, override,
                                    shape_bucket)
from repro.kernels.csr_aggregate import _edge_dot
from repro.kernels.ops import fused_gcn_layer
from repro.optim import adamw_init

K, N_PAD, E_PAD, F = 8, 79_344, 325_288, 128
NUM_CLASSES = 40
HBM_BYTES = 16 * 2**30          # one v5e chip
USABLE_BYTES = int(15.75 * 2**30)   # what its compiler lets a program use


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # jitted wrappers fix the interpret mode when traced: no CPU trace in,
    # no TPU trace out to the worker's next module
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """Steer backend-dependent code (interpret mode, kernel configs) to
    its TPU branch while compiling for the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_args(sharding, f):
    return (_sds((N_PAD, f), sharding),
            _sds((E_PAD,), sharding, jnp.int32),
            _sds((E_PAD,), sharding, jnp.int32),
            _sds((E_PAD,), sharding),
            _sds((N_PAD,), sharding),
            _sds((f, f), sharding),
            _sds((f,), sharding))


@pytest.mark.parametrize("f", [F, 256])
@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("strategy", ["pallas_fused", "pallas"])
def test_fused_gcn_layer_compiles_for_v5e(one_chip, tpu_backend, strategy,
                                          direction, f):
    cfg = KernelConfig(strategy=strategy)

    def layer(h, src, dst, w_edge, deg, w, b):
        return fused_gcn_layer(h, src, dst, w_edge, deg, w, b, config=cfg)

    fn = layer
    if direction == "backward":
        fn = jax.grad(lambda *a: jnp.sum(layer(*a) ** 2),
                      argnums=(0, 3, 5, 6))
    compiled = jax.jit(fn).lower(*_layer_args(one_chip, f)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_edge_dot_kernel_compiles_for_v5e(one_chip, tpu_backend):
    cfg = KernelConfig()
    e = E_PAD - E_PAD % cfg.edge_block
    a = _sds((e, F), one_chip)
    compiled = jax.jit(lambda a, b: _edge_dot(
        a, b, interpret=False, config=cfg)).lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_total(one_chip, cfg, group=None):
    """The compiler's device bytes of the k=8 local step (arguments,
    outputs and temporaries, less what they alias), and the program."""
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, one_chip, x.dtype), tree)
    params = jax.eval_shape(lambda: init_partition_models(
        jax.random.PRNGKey(0), cfg, NUM_CLASSES, K))
    opt = jax.eval_shape(jax.vmap(adamw_init), params)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), K))
    node = lambda dtype=jnp.float32: _sds((K, N_PAD), one_chip, dtype)
    arc = lambda dtype=jnp.float32: _sds((K, E_PAD), one_chip, dtype)
    tensors = {"features": _sds((K, N_PAD, F), one_chip),
               "labels": node(jnp.int32), "train_mask": node(),
               "edge_src": arc(jnp.int32), "edge_dst": arc(jnp.int32),
               "edge_weight": arc(), "in_degree": node(), "node_mask": node()}
    step = make_local_train_step(cfg, False, group=group)
    compiled = jax.jit(step).lower(on_chip(params), on_chip(opt), tensors,
                                   on_chip(keys)).compile()
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes), compiled


@pytest.mark.parametrize("use_kernel", [False, True])
def test_local_train_step_fits_one_v5e_chip(one_chip, tpu_backend,
                                            use_kernel):
    """The vmapped k=8 step holds every partition's [E_pad, F] gathers at
    once; the compiler's own count must leave it inside 16 GiB of HBM. The
    chooser keeps all 8 partitions in one group on a v5e (the plain
    ``vmap``), from an estimate within 1.5x of the compiler's count."""
    cfg = GNNConfig(kind="gcn", feature_dim=F, hidden_dim=128,
                    embed_dim=128, num_layers=3, dropout=0.3,
                    use_kernel=use_kernel)
    group, estimate = choose_partition_group(cfg, K, N_PAD, E_PAD,
                                             NUM_CLASSES, USABLE_BYTES)
    assert group == K
    # what a TPU run resolves with no tuned entry for this bucket
    resolved = fallback_config(shape_bucket(N_PAD, E_PAD, F), "tpu")
    assert resolved.strategy == "pallas_fused"
    with override(resolved):
        total, compiled = _compiled_total(one_chip, cfg)
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    assert 0 < total < HBM_BYTES, total
    assert 1 / 1.5 < estimate / total < 1.5, (estimate, total)


def test_gat_local_step_in_chosen_groups_fits_one_v5e_chip(one_chip,
                                                           tpu_backend):
    """The ``arxiv-gat.k8-local`` step (3 heads of 250, the kernel path as
    a TPU resolves it) in the groups the chooser takes for a v5e: all 8
    at once would need ~45 GiB. The compiler's count fits in 16 GiB, and
    the chooser's estimate is within 1.5x of it (the factor
    ``step_bytes_estimate`` states)."""
    cfg = GNNConfig(kind="gat", feature_dim=F, hidden_dim=250,
                    embed_dim=256, num_layers=3, dropout=0.75,
                    use_kernel=True, heads=3)
    group, estimate = choose_partition_group(cfg, K, N_PAD, E_PAD,
                                             NUM_CLASSES, USABLE_BYTES)
    assert group == 1
    total, compiled = _compiled_total(one_chip, cfg, group)
    assert compiled.as_text().count("tpu_custom_call") >= 9   # 3 per layer
    assert 0 < total < HBM_BYTES, total
    assert 1 / 1.5 < estimate / total < 1.5, (estimate, total)
