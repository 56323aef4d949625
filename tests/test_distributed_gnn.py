"""Distributed-runtime tests: run in a subprocess with 4 fake host devices
(XLA_FLAGS must be set before jax initializes, so these can't run in-process
— the main test session keeps 1 device per the project convention)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


PREAMBLE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import (make_arxiv_like, leiden_fusion, build_partition_batch,
                        build_halo_exchange)
from repro.gnn import (GNNConfig, gather_partition_tensors,
                       init_partition_models, make_local_train_step,
                       make_sync_train_step)
from repro.optim import adamw_init

ds = make_arxiv_like(n=400, feature_dim=8, num_classes=4, seed=3)
labels = leiden_fusion(ds.graph, 4, alpha=0.3)
batch = build_partition_batch(ds.graph, labels, scheme="repli")
pt = gather_partition_tensors(ds, batch)
cfg = GNNConfig(kind="gcn", feature_dim=8, hidden_dim=16, embed_dim=16,
                num_layers=2, dropout=0.0)
params = init_partition_models(jax.random.PRNGKey(0), cfg, 4, 4)
opt = jax.vmap(adamw_init)(params)
tensors = {k: jnp.asarray(v) for k, v in {
    'features': pt.features, 'labels': pt.labels,
    'train_mask': pt.train_mask, 'edge_src': pt.edge_src,
    'edge_dst': pt.edge_dst, 'edge_weight': pt.edge_weight,
    'in_degree': pt.in_degree, 'node_mask': pt.node_mask}.items()}
mesh = jax.make_mesh((4,), ("data",))
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
"""


def test_local_step_has_zero_collectives():
    """THE paper claim, checked mechanically: LF local training lowers to an
    HLO with no communication ops at all."""
    out = run_with_devices(PREAMBLE + """
shard = NamedSharding(mesh, P("data"))
step = jax.jit(make_local_train_step(cfg, False, lr=1e-2),
               in_shardings=(shard, shard, shard, shard),
               out_shardings=(shard, shard, shard))
keys = jax.random.split(jax.random.PRNGKey(1), 4)
lowered = step.lower(params, opt, tensors, keys)
hlo = lowered.compile().as_text()
found = [c for c in COLLECTIVES if c in hlo]
print("COLLECTIVES:", found)
p2, o2, loss = step(params, opt, tensors, keys)
print("LOSS_FINITE:", bool(jnp.isfinite(loss).all()))
""")
    assert "COLLECTIVES: []" in out
    assert "LOSS_FINITE: True" in out


def test_sync_step_communicates_and_trains():
    """The synchronized baseline must contain an all-gather (halo exchange)
    and still reduce the loss."""
    out = run_with_devices(PREAMBLE + """
halo = build_halo_exchange(ds.graph, labels, batch)
step = make_sync_train_step(cfg, halo, False, mesh, lr=1e-2)
keys = jax.random.split(jax.random.PRNGKey(1), 4)
hlo = step.lower(params, opt, tensors, keys).compile().as_text()
has_comm = any(c in hlo for c in COLLECTIVES)
print("HAS_COMM:", has_comm)
p, o = params, opt
for i in range(15):
    p, o, loss = step(p, o, tensors, keys)
    if i == 0:
        first = float(loss.mean())
print("IMPROVED:", float(loss.mean()) < first)
print("FINITE:", bool(jnp.isfinite(loss).all()))
""")
    assert "HAS_COMM: True" in out
    assert "IMPROVED: True" in out
    assert "FINITE: True" in out


def test_sync_step_consumes_dropout_like_local():
    """Both modes must consume the training config identically: with
    cfg.dropout > 0 the sync step's loss depends on the dropout key (the
    old code silently trained the baseline with no dropout), and with
    dropout == 0 the key is inert."""
    out = run_with_devices(PREAMBLE + """
import dataclasses
halo = build_halo_exchange(ds.graph, labels, batch)
ka = jax.random.split(jax.random.PRNGKey(1), 4)
kb = jax.random.split(jax.random.PRNGKey(2), 4)
cfg_d = dataclasses.replace(cfg, dropout=0.5)
step_d = make_sync_train_step(cfg_d, halo, False, mesh, lr=1e-2)
_, _, la = step_d(params, opt, tensors, ka)
_, _, la2 = step_d(params, opt, tensors, ka)
_, _, lb = step_d(params, opt, tensors, kb)
print("KEY_MATTERS:", bool(jnp.abs(la - lb).max() > 1e-6))
print("DETERMINISTIC:", bool(jnp.abs(la - la2).max() == 0.0))
step_0 = make_sync_train_step(cfg, halo, False, mesh, lr=1e-2)
_, _, za = step_0(params, opt, tensors, ka)
_, _, zb = step_0(params, opt, tensors, kb)
print("INERT_AT_ZERO:", bool(jnp.abs(za - zb).max() == 0.0))
""")
    assert "KEY_MATTERS: True" in out
    assert "DETERMINISTIC: True" in out
    assert "INERT_AT_ZERO: True" in out


def test_sync_step_trains_through_pallas_kernel():
    """use_kernel=True is a real path in sync mode too: the shard_map step
    (check_vma=False — pallas_call has no varying-axes rule) lowers, still
    contains the halo all_gather, and at dropout=0 matches the jnp path's
    loss."""
    out = run_with_devices(PREAMBLE + """
import dataclasses
halo = build_halo_exchange(ds.graph, labels, batch)
keys = jax.random.split(jax.random.PRNGKey(1), 4)
cfg_k = dataclasses.replace(cfg, use_kernel=True)
step_k = make_sync_train_step(cfg_k, halo, False, mesh, lr=1e-2)
hlo = step_k.lower(params, opt, tensors, keys).compile().as_text()
print("HAS_COMM:", any(c in hlo for c in COLLECTIVES))
step_j = make_sync_train_step(cfg, halo, False, mesh, lr=1e-2)
_, _, lj = step_j(params, opt, tensors, keys)
_, _, lk = step_k(params, opt, tensors, keys)
print("MAXDIFF:", float(jnp.abs(lj - lk).max()))
""")
    assert "HAS_COMM: True" in out
    maxdiff = float(out.split("MAXDIFF:")[1].strip())
    assert maxdiff < 1e-4


def test_stale_steps_consume_dropout_like_sync():
    """The stale-mode steps thread the per-epoch dropout keys exactly like
    the other modes: with dropout > 0 both the exchange and the
    between-exchange (cached) step depend on the key and are deterministic
    under it; with dropout == 0 the key is inert."""
    out = run_with_devices(PREAMBLE + """
import dataclasses
from repro.gnn import make_stale_train_steps
halo = build_halo_exchange(ds.graph, labels, batch)
ka = jax.random.split(jax.random.PRNGKey(1), 4)
kb = jax.random.split(jax.random.PRNGKey(2), 4)
cfg_d = dataclasses.replace(cfg, dropout=0.5)
steps = make_stale_train_steps(cfg_d, halo, False, mesh, lr=1e-2)
_, _, la, caches = steps["exchange"](params, opt, tensors, ka)
_, _, lb, _ = steps["exchange"](params, opt, tensors, kb)
print("EX_KEY_MATTERS:", bool(jnp.abs(la - lb).max() > 1e-6))
_, _, sa = steps["stale"](params, opt, tensors, ka, caches)
_, _, sa2 = steps["stale"](params, opt, tensors, ka, caches)
_, _, sb = steps["stale"](params, opt, tensors, kb, caches)
print("ST_KEY_MATTERS:", bool(jnp.abs(sa - sb).max() > 1e-6))
print("ST_DETERMINISTIC:", bool(jnp.abs(sa - sa2).max() == 0.0))
steps0 = make_stale_train_steps(cfg, halo, False, mesh, lr=1e-2)
_, _, za, c0 = steps0["exchange"](params, opt, tensors, ka)
_, _, zb, _ = steps0["exchange"](params, opt, tensors, kb)
print("INERT_AT_ZERO:", bool(jnp.abs(za - zb).max() == 0.0))
""")
    assert "EX_KEY_MATTERS: True" in out
    assert "ST_KEY_MATTERS: True" in out
    assert "ST_DETERMINISTIC: True" in out
    assert "INERT_AT_ZERO: True" in out


def test_local_matches_single_device_numerics():
    """Sharding over 4 devices must be bit-compatible (up to float noise)
    with the unsharded vmap execution."""
    out = run_with_devices(PREAMBLE + """
step_fn = make_local_train_step(cfg, False, lr=1e-2)
keys = jax.random.split(jax.random.PRNGKey(1), 4)
shard = NamedSharding(mesh, P("data"))
step_sharded = jax.jit(step_fn, in_shardings=(shard, shard, shard, shard),
                       out_shardings=(shard, shard, shard))
step_plain = jax.jit(step_fn)
_, _, l1 = step_sharded(params, opt, tensors, keys)
_, _, l2 = step_plain(params, opt, tensors, keys)
print("MAXDIFF:", float(jnp.abs(l1 - l2).max()))
""")
    maxdiff = float(out.split("MAXDIFF:")[1].strip())
    assert maxdiff < 1e-5
