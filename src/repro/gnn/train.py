"""Distributed GNN training — the paper's pipeline, SPMD-native.

Three training modes over the k partition subgraphs:

* **local** (the paper's contribution): every partition trains its own GNN
  replica with NO inter-partition communication. Implemented as a vmap over
  the stacked partition axis; under `jit` with the partition axis sharded
  over the mesh `data` axis this is embarrassingly parallel — the lowered
  HLO contains zero collectives (asserted in tests / measured in §Roofline).

* **sync** (the DGL-style baseline the paper argues against): identical
  model, but before every GNN layer the halo rows are refreshed from their
  owner partitions via an `all_gather` over the `data` axis inside
  `shard_map`. The collective bytes this injects are exactly the paper's
  "continuous communication".

* **stale** (the middle ground, DESIGN.md §12): the same `shard_map` halo
  plumbing as sync, but boundary activations are exchanged only every
  ``sync_period`` epochs; in between, layers read the *frozen* halo rows
  cached at the last exchange — zero collectives on those epochs. The two
  limit cases reduce exactly to the modes above (``period=1`` ≡ sync,
  ``period=∞`` ≡ local) and are pinned by `tests/test_stale_mode.py`.

After training, per-partition embeddings of *owned* nodes are scattered back
into a global [n, embed] table and an MLP classifier is trained on it
(paper §5.2). An optional *integration* step (`repro.core.assemble.
integrate_models`) parameter-averages or ensembles the k replicas first."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import NodeDataset, PartitionBatch, HaloExchangeSpec
from repro.optim import OptState, adamw_init, adamw_update
from .model import (GNNConfig, gnn_forward, head_logits, init_gnn, init_mlp,
                    layer_widths, mlp_forward, sigmoid_bce, softmax_xent)

PyTree = Any


def _finish_epoch_span(sp, loss) -> None:
    """Tracing-enabled epoch bookkeeping: block on the dispatched step so
    the span covers the actual device compute (JAX dispatch is async — an
    unblocked epoch span would time only the Python enqueue), then record
    the realized mean loss on the span and the registry gauge. Only called
    under ``obs.enabled()`` — the ``float()`` forces a device sync the
    disabled path must never pay."""
    val = float(jnp.mean(jax.block_until_ready(loss)))
    sp.set(loss=round(val, 6))
    obs.gauge("train.loss").set(val)


# ---------------------------------------------------------------------------
# The training entry's phases, shared by the three modes, each under its
# own span inside ``train.call`` (DESIGN.md §16): with a profiler running
# the spans land on its clock, beside the device ops they wait for or feed.
# ---------------------------------------------------------------------------
def _gather_and_upload(ds: NodeDataset, batch: PartitionBatch,
                       cfg: GNNConfig, seed: int):
    """The host gather, then the tensors, parameters and optimizer state
    on the device: ``(pt, key, params, opt, tensors)``."""
    with obs.span("train.gather"):
        pt = gather_partition_tensors(ds, batch)
    if obs.enabled() and cfg.use_kernel:
        _record_stream_share(pt, cfg)
    with obs.span("train.upload"):
        key = jax.random.PRNGKey(seed)
        params = init_partition_models(key, cfg, ds.num_classes, batch.k)
        opt = jax.vmap(adamw_init)(params)  # per-partition state (step: [k])
        tensors = {n: jnp.asarray(v) for n, v in _tensors_dict(pt).items()}
    return pt, key, params, opt, tensors


def _record_stream_share(pt: "PartitionTensors", cfg: GNNConfig) -> None:
    """Gauge ``kernels.stream_share``: the share of (node tile, edge
    granule) pairs the Pallas aggregation streams for the forward arc
    lists, under the kernel config the first layer resolves. Host-side,
    from the gathered batch; nothing when that config is not Pallas."""
    from repro.kernels import get_config, streamed_pairs
    _, n_pad, f = pt.features.shape
    config = get_config(n_pad, pt.edge_dst.shape[1], f)
    if config.uses_pallas:
        streamed, dense = streamed_pairs(pt.edge_dst, n_pad, config)
        obs.gauge("kernels.stream_share").set(streamed / max(dense, 1))


def _build(fn, *args):
    """Ahead-of-time build of the jitted ``fn`` for ``args``: tracing and
    lowering under ``train.lower``, then compiling (or loading from the
    persistent compile cache) under ``train.compile``. The executable
    lives for one call of the entry."""
    with obs.span("train.lower"):
        lowered = fn.lower(*args)
    with obs.span("train.compile"):
        return lowered.compile()


def _epoch_keys(key, e: int, k: int):
    """Epoch ``e``'s dropout keys, one per partition: every mode's
    schedule."""
    return jax.random.split(jax.random.fold_in(key, e), k)


def _run_epochs(epochs: int, key, k: int, mode: str,
                run_epoch: Callable[[int, Any], Any],
                kind_of: Optional[Callable[[int], str]] = None) -> None:
    """Dispatch ``run_epoch(e, keys) -> loss`` for each epoch under a
    ``train.epoch`` span (a profiler step). Only with span collection on
    does the span wait for the step and record its loss."""
    for e in range(epochs):
        attrs = {"kind": kind_of(e)} if kind_of else {}
        with obs.step_span("train.epoch", e, epoch=e, mode=mode,
                           **attrs) as sp:
            loss = run_epoch(e, _epoch_keys(key, e, k))
            if obs.enabled():
                _finish_epoch_span(sp, loss)


def _embed_and_pool(params, integrate: str, embed, tensors, k: int,
                    pt: "PartitionTensors", n: int, embed_dim: int
                    ) -> Tuple[PyTree, np.ndarray]:
    """The embedding pass (``embed(params, tensors)``, built at its first
    call), the fetch of the ``[k, N_pad, E]`` table and its pooling into
    the global ``[n, E]`` table."""
    exe = None

    def emb_fn(p):
        nonlocal exe
        if exe is None:
            exe = _build(embed, p, tensors)
        return exe(p, tensors)

    with obs.span("train.embed"):
        params, emb = apply_integration(params, integrate, emb_fn, k)
    with obs.span("train.fetch"):
        emb = np.asarray(emb)
    with obs.span("train.pool"):
        return params, pool_embeddings(emb, pt, n, embed_dim)


# ---------------------------------------------------------------------------
# Per-partition tensors (host-side assembly)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PartitionTensors:
    """Stacked per-partition training arrays, leading axis k."""
    features: np.ndarray      # [k, N_pad, F]
    labels: np.ndarray        # [k, N_pad] int32 or [k, N_pad, T] f32
    train_mask: np.ndarray    # [k, N_pad] f32 (owned & train & valid)
    edge_src: np.ndarray      # [k, E_pad]
    edge_dst: np.ndarray
    edge_weight: np.ndarray
    in_degree: np.ndarray
    node_mask: np.ndarray     # [k, N_pad] f32
    owned_mask: np.ndarray    # [k, N_pad] bool
    node_ids: np.ndarray      # [k, N_pad] int32


def gather_partition_tensors(ds: NodeDataset, batch: PartitionBatch
                             ) -> PartitionTensors:
    ids = np.maximum(batch.node_ids, 0)
    feats = ds.features[ids] * batch.node_mask[..., None]
    labels = ds.labels[ids]
    if not ds.multilabel:
        labels = labels.astype(np.int32)
    train = ds.train_mask[ids] & batch.owned_mask & batch.node_mask
    return PartitionTensors(
        features=feats.astype(np.float32),
        labels=labels,
        train_mask=train.astype(np.float32),
        edge_src=batch.edge_src, edge_dst=batch.edge_dst,
        edge_weight=batch.edge_weight, in_degree=batch.in_degree,
        node_mask=batch.node_mask.astype(np.float32),
        owned_mask=batch.owned_mask, node_ids=batch.node_ids)


# ---------------------------------------------------------------------------
# Model+head params
# ---------------------------------------------------------------------------
def init_partition_models(key, cfg: GNNConfig, num_classes: int, k: int
                          ) -> PyTree:
    """k independent GNN+head replicas, stacked on axis 0."""
    def one(subkey):
        kb, kh = jax.random.split(subkey)
        body = init_gnn(kb, cfg)
        s = jnp.sqrt(2.0 / cfg.embed_dim)
        head = {"w": jax.random.normal(kh, (cfg.embed_dim, num_classes)) * s,
                "b": jnp.zeros((num_classes,))}
        return {"body": body, "head": head}
    return jax.vmap(one)(jax.random.split(key, k))


def _forward_one(params, cfg: GNNConfig, t: Dict[str, jnp.ndarray],
                 dropout_key=None, halo_refresh: Optional[Callable] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward for ONE partition. Returns (embeddings, logits)."""
    feats = t["features"]
    if halo_refresh is not None:
        feats = halo_refresh(feats, layer_idx=0)
    emb = gnn_forward(params["body"], cfg, feats, t["edge_src"],
                      t["edge_dst"], t["edge_weight"], t["in_degree"],
                      node_mask=t["node_mask"], dropout_key=dropout_key)
    return emb, head_logits(params["head"], emb)


def _loss_one(params, cfg: GNNConfig, t, multilabel: bool, dropout_key):
    _, logits = _forward_one(params, cfg, t, dropout_key)
    if multilabel:
        return sigmoid_bce(logits, t["labels"], t["train_mask"])
    return softmax_xent(logits, t["labels"], t["train_mask"])


def _tensors_dict(pt: PartitionTensors) -> Dict[str, np.ndarray]:
    return {"features": pt.features, "labels": pt.labels,
            "train_mask": pt.train_mask, "edge_src": pt.edge_src,
            "edge_dst": pt.edge_dst, "edge_weight": pt.edge_weight,
            "in_degree": pt.in_degree, "node_mask": pt.node_mask}


# ---------------------------------------------------------------------------
# Partition groups: how many partitions the local step holds at once
# ---------------------------------------------------------------------------
# Share of the device's ``bytes_limit`` the chooser leaves free: room for
# what the estimate does not count (the executables' code, the runtime's
# own buffers, the embedding pass's table) and for its error against the
# compiler's count (tests/test_tpu_compile.py bounds that error).
GROUP_MARGIN = 0.15


# Buffers the step's backward keeps live at its peak, per partition in
# flight, as the TPU compiler's buffer assignment for a v5e counts them at
# the ogbn-arxiv k=8 shapes: [N, heads * width] activations per layer, and
# [E, aggregated width] row gathers. A GAT layer keeps more of both: its
# arc weights are learned, so its backward gathers the rows twice more for
# the attention's row dot (``edge_dot``), and the heads' padded and
# transposed copies of z sit beside z.
_LIVE = {"gat": (4, 5)}       # (activations, gathers); others (2, 2)


def step_bytes_estimate(cfg: GNNConfig, k: int, n_pad: int, e_pad: int,
                        g: int, num_classes: int) -> int:
    """Device bytes of the local step that trains k partitions g at a
    time: what all k keep (their tensors, parameters and AdamW state, each
    an input and an output of the step) and what g partitions in flight
    hold (each layer's input and live activations, and the live row
    gathers of the widest aggregation, :data:`_LIVE`). Float32; the
    aggregated width is padded to the kernels' 128 lanes. Within 1.5x of
    the TPU compiler's count for the ogbn-arxiv k=8 GCN and GAT steps
    (tests/test_tpu_compile.py)."""
    lanes = lambda f: -(-f // 128) * 128
    acts, gathers = _LIVE.get(cfg.kind, (2, 2))
    widths = layer_widths(cfg)
    n_params = sum(f_in * cfg.heads * f_out for f_in, f_out in widths)
    n_params += cfg.embed_dim * num_classes
    keep = n_pad * (cfg.feature_dim + 4) + 3 * e_pad + 6 * n_params
    flight = sum(n_pad * (f_in + acts * cfg.heads * f_out)
                 for f_in, f_out in widths)
    agg = max(cfg.heads * lanes(f_out) if cfg.kind == "gat" else lanes(f_in)
              for f_in, f_out in widths)
    flight += gathers * e_pad * agg
    return 4 * (k * keep + g * flight)


def choose_partition_group(cfg: GNNConfig, k: int, n_pad: int, e_pad: int,
                           num_classes: int, bytes_limit: Optional[int]
                           ) -> Tuple[int, int]:
    """``(g, estimate)``: the largest divisor g of k whose
    :func:`step_bytes_estimate` fits under ``bytes_limit`` less
    :data:`GROUP_MARGIN`, and that estimate. With no limit known (a CPU
    backend) g is k; where no divisor fits, 1."""
    divisors = [g for g in range(k, 0, -1) if k % g == 0]
    if bytes_limit is not None:
        budget = bytes_limit * (1.0 - GROUP_MARGIN)
        for g in divisors:
            est = step_bytes_estimate(cfg, k, n_pad, e_pad, g, num_classes)
            if est <= budget:
                return g, est
    g = k if bytes_limit is None else 1
    return g, step_bytes_estimate(cfg, k, n_pad, e_pad, g, num_classes)


def _bytes_limit(mesh: Optional[Mesh]) -> Optional[int]:
    """The memory the local step's device may use, where it runs on one
    device and the backend reports it; None otherwise (the CPU reports
    none, and a k axis sharded over several devices keeps its vmap)."""
    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.local_devices()[:1])
    if len(devices) != 1:
        return None
    stats = devices[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _over_partitions(fn: Callable, group: Optional[int]) -> Callable:
    """``fn`` of one partition mapped over a leading partition axis of k,
    ``group`` partitions at a time: a ``vmap`` inside a ``lax.map`` over
    k / group groups, and the plain ``vmap`` where the group is all k (or
    None). The mapped function keeps ``fn``'s name and signature, which
    name the jitted program and its parameters."""
    @functools.wraps(fn)
    def mapped(*args):
        k = jax.tree.leaves(args)[0].shape[0]
        if group is None or group >= k:
            return jax.vmap(fn)(*args)
        return jax.lax.map(lambda a: fn(*a), args, batch_size=group)
    return mapped


# ---------------------------------------------------------------------------
# LOCAL training (the paper's scheme — zero collectives)
# ---------------------------------------------------------------------------
def make_local_train_step(cfg: GNNConfig, multilabel: bool, lr: float = 1e-2,
                          per_partition: bool = False,
                          group: Optional[int] = None) -> Callable:
    """Returns jit-able step(params, opt, tensors, key) -> (params, opt, loss).

    All arrays carry a leading k axis; the step is a pure vmap — sharding the
    k axis over `data` makes it fully local per device. With ``group`` < k
    it takes the partitions ``group`` at a time (:func:`_over_partitions`;
    :func:`choose_partition_group` sizes it to the device). With
    ``per_partition=True`` the un-vmapped single-partition step is returned
    instead (no leading k axis) — the low-memory sequential path trains one
    partition at a time with it, and since local-mode partitions never
    interact, the math per partition is the same either way."""
    def one_step(params, opt, t, key):
        loss, grads = jax.value_and_grad(_loss_one)(params, cfg, t,
                                                    multilabel, key)
        params, opt = adamw_update(grads, opt, params, lr, weight_decay=0.0)
        return params, opt, loss

    if per_partition:
        return one_step

    def step(params, opt, tensors, keys):
        return _over_partitions(one_step, group)(params, opt, tensors, keys)
    return step


def train_local(ds: NodeDataset, batch: PartitionBatch, cfg: GNNConfig,
                epochs: int = 60, lr: float = 1e-2, seed: int = 0,
                mesh: Optional[Mesh] = None,
                hlo_out: Optional[Dict[str, str]] = None,
                integrate: str = "none", sequential: bool = False
                ) -> Tuple[PyTree, np.ndarray]:
    """Paper's local training. Returns (params, global_embeddings [n, E]).

    When ``hlo_out`` is given, the optimized (post-SPMD) HLO of the train
    step is stored under ``hlo_out["hlo"]`` so callers (the pipeline report,
    the roofline benchmark) can count collective bytes — for this mode the
    count is zero, which is the paper's claim.

    ``sequential=True`` (the pipeline's ``low_memory`` flag, DESIGN.md §15)
    trains the k partitions one at a time through the un-vmapped step
    instead of all at once: the vmapped step materializes every partition's
    ``[E_pad, F]`` edge gathers simultaneously (~k times the transient
    footprint — ~18 GB at n=1e6, k=8, F=128 measured), the sequential loop
    only ever one. Partitions never interact in local mode and the
    per-epoch dropout keys are the same ``keys[p]``, so the trained
    parameters and embeddings are identical to the vmapped path
    (pinned in tests/test_graphstore.py). Requires an unsharded run
    (``mesh is None``) and no ``hlo_out``."""
    if sequential and mesh is None and hlo_out is None:
        with obs.span("train.call", mode="local_sequential", k=batch.k,
                      epochs=epochs):
            return _train_local_sequential(ds, batch, cfg, epochs=epochs,
                                           lr=lr, seed=seed,
                                           integrate=integrate)
    k = batch.k
    with obs.span("train.call", mode="local", k=k, epochs=epochs):
        pt, key, params, opt, tensors = _gather_and_upload(ds, batch, cfg,
                                                           seed)
        group, estimate = choose_partition_group(
            cfg, k, batch.n_pad, batch.e_pad, ds.num_classes,
            _bytes_limit(mesh))
        obs.gauge("train.partition_group").set(group)
        obs.gauge("train.step_bytes_estimate").set(estimate)
        step = make_local_train_step(cfg, ds.multilabel, lr, group=group)
        if mesh is not None:
            shard = NamedSharding(mesh, P("data"))
            step = jax.jit(step, in_shardings=(shard, shard, shard, shard),
                           out_shardings=(shard, shard, shard))
        else:
            step = jax.jit(step)
        step = _build(step, params, opt, tensors, _epoch_keys(key, 0, k))
        if hlo_out is not None:
            hlo_out["hlo"] = step.as_text()

        def run_epoch(e, keys):
            nonlocal params, opt
            params, opt, loss = step(params, opt, tensors, keys)
            return loss

        _run_epochs(epochs, key, k, "local", run_epoch)
        return _embed_and_pool(params, integrate, _local_embed(cfg, group),
                               tensors, k, pt, ds.graph.n, cfg.embed_dim)


def _train_local_sequential(ds: NodeDataset, batch: PartitionBatch,
                            cfg: GNNConfig, epochs: int, lr: float,
                            seed: int, integrate: str
                            ) -> Tuple[PyTree, np.ndarray]:
    """Low-memory local training: one partition at a time (see train_local).

    The epoch/partition loops are swapped relative to the vmapped path —
    partition p runs all its epochs before p+1 starts — which is legal
    exactly because local training has no cross-partition dataflow. Only
    one partition's tensors are resident on device at a time; the jitted
    single-partition step compiles once (padding makes every partition the
    same shape)."""
    with obs.span("train.gather"):
        pt = gather_partition_tensors(ds, batch)
    k = batch.k
    obs.gauge("train.partition_group").set(1)
    obs.gauge("train.step_bytes_estimate").set(step_bytes_estimate(
        cfg, 1, batch.n_pad, batch.e_pad, 1, ds.num_classes))
    np_tensors = _tensors_dict(pt)
    key = jax.random.PRNGKey(seed)
    params = init_partition_models(key, cfg, ds.num_classes, k)
    # per-epoch key schedule, identical to the vmapped path's
    ep_keys = [_epoch_keys(key, e, k) for e in range(epochs)]
    step1 = jax.jit(make_local_train_step(cfg, ds.multilabel, lr,
                                          per_partition=True))
    traced = obs.enabled()
    trained: List[PyTree] = []
    for p in range(k):
        t_p = {name: jnp.asarray(v[p]) for name, v in np_tensors.items()}
        params_p = jax.tree.map(lambda x: x[p], params)
        opt_p = adamw_init(params_p)
        with obs.span("train.partition", partition=p, epochs=epochs,
                      mode="local_sequential") as psp:
            loss = None
            for e in range(epochs):
                params_p, opt_p, loss = step1(params_p, opt_p, t_p,
                                              ep_keys[e][p])
            if traced and loss is not None:
                _finish_epoch_span(psp, loss)
        trained.append(jax.tree.map(np.asarray, params_p))
        del t_p, params_p, opt_p
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *trained)

    fwd1 = jax.jit(lambda pp, t: _forward_one(pp, cfg, t)[0])

    def emb_fn(ps):
        out = []
        for p in range(k):
            t_p = {name: jnp.asarray(v[p]) for name, v in np_tensors.items()}
            out.append(np.asarray(fwd1(jax.tree.map(lambda x: x[p], ps),
                                       t_p)))
            del t_p
        return jnp.asarray(np.stack(out))

    params, emb = apply_integration(params, integrate, emb_fn, k)
    with obs.span("train.pool"):
        return params, pool_embeddings(np.asarray(emb), pt, ds.graph.n,
                                       cfg.embed_dim)


def _local_embed(cfg: GNNConfig, group: Optional[int] = None):
    """The local embedding pass, jitted: ``(params, tensors) -> [k, N_pad,
    E]``, ``group`` partitions at a time."""
    return jax.jit(_over_partitions(lambda p, t: _forward_one(p, cfg, t)[0],
                                    group))


def compute_embeddings(params, cfg: GNNConfig, tensors) -> jnp.ndarray:
    return _local_embed(cfg)(params, tensors)


def apply_integration(params, integrate: Optional[str],
                      emb_fn: Callable[[Any], jnp.ndarray], k: int
                      ) -> Tuple[PyTree, jnp.ndarray]:
    """Integrate the k per-partition models before embedding assembly.

    ``emb_fn(params) -> [k, N_pad, E]`` is the mode's own embedding forward
    (plain vmap for local, halo-refreshing shard_map for sync/stale), so the
    integration step composes with every training mode.

    - ``"none"``      — k independent models, as trained (the paper).
    - ``"model_avg"`` — parameter-average the replicas
      (:func:`repro.core.assemble.average_partition_params`; randomized-
      partition model aggregation, arxiv 2305.09887) and embed with the
      averaged model everywhere.
    - ``"ensemble"``  — keep the k models but embed each subgraph with ALL
      of them and average the embeddings (prediction-level aggregation).
    """
    from repro.core.assemble import average_partition_params
    if integrate in (None, "none"):
        return params, emb_fn(params)
    if integrate == "model_avg":
        params = average_partition_params(params)
        return params, emb_fn(params)
    if integrate == "ensemble":
        acc = None
        for m in range(k):
            pm = jax.tree.map(
                lambda x: jnp.broadcast_to(x[m:m + 1], x.shape), params)
            emb = emb_fn(pm)
            acc = emb if acc is None else acc + emb
        return params, acc / float(k)
    raise ValueError(
        f"integrate must be none|model_avg|ensemble, got {integrate!r}")


def pool_embeddings(emb: np.ndarray, pt: PartitionTensors, n: int,
                    embed_dim: int) -> np.ndarray:
    """Scatter owned-node embeddings back to a global [n, E] table."""
    out = np.zeros((n, embed_dim), dtype=np.float32)
    for p in range(emb.shape[0]):
        owned = pt.owned_mask[p]
        ids = pt.node_ids[p][owned]
        out[ids] = emb[p][owned]
    return out


# ---------------------------------------------------------------------------
# Halo-refreshing forward, shared by the SYNC baseline and STALE mode.
#
# Three refresh disciplines over the same `shard_map` halo plumbing:
#   "exchange" — live all_gather before every layer (sync semantics); the
#                post-refresh activations are returned as per-layer caches
#   "cached"   — halo rows overwritten from the caches of the last exchange
#                epoch (zero collectives; the staleness of DESIGN.md §12)
#   "frozen"   — no refresh at all: halo rows stay whatever local compute
#                produces, which is exactly `gnn_forward` (local semantics)
# ---------------------------------------------------------------------------
def make_halo_forward(cfg: GNNConfig, halo: HaloExchangeSpec,
                      axis: str = "data"):
    """Build ``forward(params, t, my_idx, dropout_key, caches, refresh_mode)``
    for use inside shard_map (one partition per ``axis`` device).

    Returns ``(embeddings, logits, new_caches)`` where ``new_caches`` is the
    tuple of post-refresh layer inputs under ``refresh_mode="exchange"`` and
    ``None`` otherwise.

    ``dropout_key`` mirrors :func:`repro.gnn.model.gnn_forward` exactly
    (dropout after every non-final layer at rate ``cfg.dropout``), so every
    halo mode consumes the training config identically to local mode —
    earlier revisions silently trained the sync baseline without dropout, an
    unfair comparison in the paper's favor. Pass ``None`` for inference."""
    send_rows = jnp.asarray(halo.send_rows)   # [k, k, H]
    recv_rows = jnp.asarray(halo.recv_rows)   # [k, k, H]

    @jax.named_scope("halo_exchange")
    def refresh(h: jnp.ndarray, my_idx: jnp.ndarray) -> jnp.ndarray:
        # Build what I send to every peer: rows of my h.  [k, H, F]
        mine_send = send_rows[my_idx]                       # [k, H]
        buf = h[jnp.maximum(mine_send, 0)] * (mine_send >= 0)[..., None]
        allbuf = jax.lax.all_gather(buf, axis)              # [k, k, H, F]
        # What peer q sent to me sits at allbuf[q, my_idx]
        incoming = allbuf[:, my_idx]                        # [k, H, F]
        rows = recv_rows[my_idx]                            # [k, H]
        flat_rows = rows.reshape(-1)
        flat_in = incoming.reshape(-1, h.shape[-1])
        valid = (flat_rows >= 0)[:, None]
        h = h.at[jnp.maximum(flat_rows, 0)].set(
            jnp.where(valid, flat_in, h[jnp.maximum(flat_rows, 0)]))
        return h

    def apply_cache(h: jnp.ndarray, my_idx: jnp.ndarray,
                    cache: jnp.ndarray) -> jnp.ndarray:
        # Overwrite exactly the rows a live exchange would refresh, but from
        # the frozen snapshot instead of the wire — no collective lowered.
        rows = recv_rows[my_idx].reshape(-1)
        safe = jnp.maximum(rows, 0)
        valid = (rows >= 0)[:, None]
        h = h.at[safe].set(jnp.where(valid, cache[safe], h[safe]))
        return h

    from .layers import layer_for
    layer_fn, _ = layer_for(cfg.kind)

    def forward(params, t, my_idx, dropout_key=None, caches=None,
                refresh_mode: str = "exchange"):
        assert refresh_mode in ("exchange", "cached", "frozen"), refresh_mode
        h = t["features"] * t["node_mask"][:, None]
        n_layers = len(params["body"]["layers"])
        new_caches = []
        for i, lp in enumerate(params["body"]["layers"]):
            last = i == n_layers - 1
            if refresh_mode == "exchange":
                h = refresh(h, my_idx)    # fetch fresh halo activations
                new_caches.append(h)      # snapshot for the stale epochs
            elif refresh_mode == "cached":
                h = apply_cache(h, my_idx, caches[i])
            h = layer_fn(lp, h, t["edge_src"], t["edge_dst"],
                         t["edge_weight"], t["in_degree"],
                         activate=not last, use_kernel=cfg.use_kernel)
            h = h * t["node_mask"][:, None]
            if dropout_key is not None and cfg.dropout > 0 and not last:
                dropout_key, sub = jax.random.split(dropout_key)
                keep = jax.random.bernoulli(sub, 1 - cfg.dropout, h.shape)
                h = jnp.where(keep, h / (1 - cfg.dropout), 0.0)
        logits = head_logits(params["head"], h)
        caches_out = tuple(new_caches) if refresh_mode == "exchange" else None
        return h, logits, caches_out
    return forward


# ---------------------------------------------------------------------------
# SYNC baseline (halo exchange every layer — the traffic LF eliminates)
# ---------------------------------------------------------------------------
def make_sync_forward(cfg: GNNConfig, halo: HaloExchangeSpec, axis: str = "data"):
    """Forward with live halo refresh before every layer (sync semantics).

    Thin wrapper over :func:`make_halo_forward` with
    ``refresh_mode="exchange"``, kept for API stability — returns
    ``(embeddings, logits)``."""
    halo_forward = make_halo_forward(cfg, halo, axis)

    def forward(params, t, my_idx, dropout_key=None):
        h, logits, _ = halo_forward(params, t, my_idx, dropout_key,
                                    refresh_mode="exchange")
        return h, logits
    return forward


def make_sync_train_step(cfg: GNNConfig, halo: HaloExchangeSpec,
                         multilabel: bool, mesh: Mesh, lr: float = 1e-2):
    """shard_map train step: one partition per `data` device."""
    forward = make_sync_forward(cfg, halo)

    def loss_fn(params, t, my_idx, dropout_key):
        _, logits = forward(params, t, my_idx, dropout_key)
        if multilabel:
            loss = sigmoid_bce(logits, t["labels"], t["train_mask"])
        else:
            loss = softmax_xent(logits, t["labels"], t["train_mask"])
        return loss

    def local_step(params, opt, t, keys):
        # leading axis is the local shard of k: size 1 per device
        params1 = jax.tree.map(lambda x: x[0], params)
        opt1 = jax.tree.map(lambda x: x[0], opt)
        t1 = jax.tree.map(lambda x: x[0], t)
        my_idx = jax.lax.axis_index("data")
        loss, grads = jax.value_and_grad(loss_fn)(params1, t1, my_idx,
                                                  keys[0])
        new_p, new_o = adamw_update(grads, opt1, params1, lr)
        expand = lambda x: x[None]
        return (jax.tree.map(expand, new_p), jax.tree.map(expand, new_o),
                loss[None])

    pspec = P("data")
    # check_vma=False: pallas_call (the use_kernel aggregation path) has no
    # varying-axes rule; all inputs/outputs are explicitly sharded over
    # `data`, so the check is vacuous here anyway
    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=(pspec, pspec, pspec, pspec),
                         out_specs=(pspec, pspec, pspec), check_vma=False)
    return jax.jit(step)


def train_sync(ds: NodeDataset, batch: PartitionBatch,
               halo: HaloExchangeSpec, cfg: GNNConfig, mesh: Mesh,
               epochs: int = 60, lr: float = 1e-2, seed: int = 0,
               hlo_out: Optional[Dict[str, str]] = None,
               integrate: str = "none"
               ) -> Tuple[PyTree, np.ndarray]:
    """DGL-style synchronized baseline, mirroring :func:`train_local`.

    Requires a mesh whose ``data`` axis size equals the partition count
    (one partition per device); every layer refreshes halo activations via
    an all_gather, which is exactly the traffic Leiden-Fusion eliminates.
    Returns (params, global_embeddings [n, E])."""
    k = batch.k
    data_size = int(mesh.shape["data"])
    if data_size != k:
        raise ValueError(
            f"sync training needs one partition per device: mesh data axis "
            f"is {data_size} but k={k}. On CPU, relaunch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={k}.")
    with obs.span("train.call", mode="sync", k=k, epochs=epochs):
        pt, key, params, opt, tensors = _gather_and_upload(ds, batch, cfg,
                                                           seed)
        step = _build(make_sync_train_step(cfg, halo, ds.multilabel, mesh,
                                           lr),
                      params, opt, tensors, _epoch_keys(key, 0, k))
        if hlo_out is not None:
            hlo_out["hlo"] = step.as_text()

        def run_epoch(e, keys):
            nonlocal params, opt
            params, opt, loss = step(params, opt, tensors, keys)
            return loss

        _run_epochs(epochs, key, k, "sync", run_epoch)
        forward = make_sync_forward(cfg, halo)
        embed = _halo_embed(lambda p, t, i: forward(p, t, i)[0], mesh)
        return _embed_and_pool(params, integrate, embed, tensors, k, pt,
                               ds.graph.n, cfg.embed_dim)


def _halo_embed(embed_one: Callable, mesh: Mesh):
    """A halo mode's embedding pass, jitted: ``(params, tensors) -> [k,
    N_pad, E]``, one partition per ``data`` device, each embedded by
    ``embed_one(params, t, my_idx)``."""
    def eval_one(p, t):
        p1 = jax.tree.map(lambda x: x[0], p)
        t1 = jax.tree.map(lambda x: x[0], t)
        return embed_one(p1, t1, jax.lax.axis_index("data"))[None]

    pspec = P("data")
    return jax.jit(jax.shard_map(eval_one, mesh=mesh, in_specs=(pspec, pspec),
                                 out_specs=pspec, check_vma=False))


# ---------------------------------------------------------------------------
# STALE mode (periodic halo exchange — the comm-vs-accuracy middle ground)
# ---------------------------------------------------------------------------
def stale_exchange_epochs(epochs: int, period: Optional[int]) -> List[int]:
    """Epochs at which stale mode performs a live halo exchange.

    ``period >= 1`` exchanges at every epoch ``e`` with ``e % period == 0``
    (epoch 0 always exchanges); ``period`` in ``{None, 0}`` or negative
    means *never* exchange — the ``stale(∞)`` limit that reduces to local
    training. ``period=1`` exchanges every epoch — the sync limit."""
    if not period or period < 1:
        return []
    return [e for e in range(epochs) if e % period == 0]


def stale_bytes_per_epoch(exchange_bytes: int, epochs: int,
                          period: Optional[int]) -> List[int]:
    """Collective bytes each epoch moves: ``exchange_bytes`` on exchange
    epochs and exactly 0 in between. Summing and dividing by ``epochs``
    gives the amortized bytes/epoch the PipelineReport records; the list is
    monotone non-increasing in ``period`` element-wise summed (pinned by a
    hypothesis sweep in tests/test_stale_mode.py)."""
    on = set(stale_exchange_epochs(epochs, period))
    return [int(exchange_bytes) if e in on else 0 for e in range(epochs)]


def _stale_cache_shapes(cfg: GNNConfig, n_pad: int) -> List[Tuple[int, int]]:
    """Per-layer cache shapes: the layer-i *input* activations [N_pad, F_i]."""
    return [(n_pad, f_in) for f_in, _ in layer_widths(cfg)]


def make_stale_train_steps(cfg: GNNConfig, halo: HaloExchangeSpec,
                           multilabel: bool, mesh: Mesh, lr: float = 1e-2
                           ) -> Dict[str, Callable]:
    """The three shard_map train steps of stale mode, keyed by discipline:

    - ``"exchange"``: ``(params, opt, t, keys) -> (params, opt, loss,
      caches)`` — identical math (and identical collectives) to the sync
      step, plus the per-layer post-refresh activation snapshots.
    - ``"stale"``: ``(params, opt, t, keys, caches) -> (params, opt, loss)``
      — halo rows read the frozen snapshots; lowers to ZERO collectives.
    - ``"frozen"``: ``(params, opt, t, keys) -> (params, opt, loss)`` — no
      halo refresh at all; used before the first exchange (period=∞), where
      it matches the local vmap step partition-for-partition.
    """
    forward = make_halo_forward(cfg, halo)

    def loss_of(refresh_mode):
        def loss_fn(params, t, my_idx, dropout_key, caches):
            _, logits, new_caches = forward(params, t, my_idx, dropout_key,
                                            caches, refresh_mode)
            if multilabel:
                loss = sigmoid_bce(logits, t["labels"], t["train_mask"])
            else:
                loss = softmax_xent(logits, t["labels"], t["train_mask"])
            return loss, new_caches
        return loss_fn

    def local_step_of(refresh_mode):
        loss_fn = loss_of(refresh_mode)

        def local_step(params, opt, t, keys, *maybe_caches):
            # leading axis is the local shard of k: size 1 per device
            params1 = jax.tree.map(lambda x: x[0], params)
            opt1 = jax.tree.map(lambda x: x[0], opt)
            t1 = jax.tree.map(lambda x: x[0], t)
            caches1 = None
            if maybe_caches:
                caches1 = jax.tree.map(lambda x: x[0], maybe_caches[0])
            my_idx = jax.lax.axis_index("data")
            (loss, new_caches), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params1, t1, my_idx, keys[0], caches1)
            new_p, new_o = adamw_update(grads, opt1, params1, lr)
            expand = lambda x: x[None]
            outs = (jax.tree.map(expand, new_p), jax.tree.map(expand, new_o),
                    loss[None])
            if refresh_mode == "exchange":
                outs += (jax.tree.map(expand, new_caches),)
            return outs
        return local_step

    pspec = P("data")
    # check_vma=False: pallas_call (the use_kernel aggregation path) has no
    # varying-axes rule (same rationale as make_sync_train_step)
    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    ex = smap(local_step_of("exchange"),
              in_specs=(pspec, pspec, pspec, pspec),
              out_specs=(pspec, pspec, pspec, pspec))
    st = smap(local_step_of("cached"),
              in_specs=(pspec, pspec, pspec, pspec, pspec),
              out_specs=(pspec, pspec, pspec))
    fz = smap(local_step_of("frozen"),
              in_specs=(pspec, pspec, pspec, pspec),
              out_specs=(pspec, pspec, pspec))
    return {"exchange": jax.jit(ex), "stale": jax.jit(st),
            "frozen": jax.jit(fz)}


def train_stale(ds: NodeDataset, batch: PartitionBatch,
                halo: HaloExchangeSpec, cfg: GNNConfig, mesh: Mesh,
                epochs: int = 60, lr: float = 1e-2, seed: int = 0,
                sync_period: Optional[int] = 4,
                hlo_out: Optional[Dict[str, str]] = None,
                integrate: str = "none"
                ) -> Tuple[PyTree, np.ndarray]:
    """Periodic stale-synchronization training (DESIGN.md §12).

    Mirrors :func:`train_sync` (same mesh contract, same init/key schedule
    as BOTH other modes), but live halo exchange happens only at the epochs
    of :func:`stale_exchange_epochs`; other epochs train against the halo
    activations frozen at the last exchange. ``sync_period=1`` is the sync
    limit; ``sync_period in {0, None}`` never exchanges — the local limit.

    ``hlo_out`` receives ``"hlo"`` (the program that moves bytes: the
    exchange step, or the frozen step when no exchange ever happens) and
    ``"hlo_stale"`` (the between-exchange program — proven collective-free
    in tests). Returns (params, global_embeddings [n, E])."""
    k = batch.k
    data_size = int(mesh.shape["data"])
    if data_size != k:
        raise ValueError(
            f"stale training needs one partition per device: mesh data axis "
            f"is {data_size} but k={k}. On CPU, relaunch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={k}.")
    schedule = set(stale_exchange_epochs(epochs, sync_period))
    with obs.span("train.call", mode="stale", k=k, epochs=epochs):
        pt, key, params, opt, tensors = _gather_and_upload(ds, batch, cfg,
                                                           seed)
        steps = make_stale_train_steps(cfg, halo, ds.multilabel, mesh, lr)
        keys0 = _epoch_keys(key, 0, k)
        if schedule:
            step_ex = _build(steps["exchange"], params, opt, tensors, keys0)
            programs = {"hlo": step_ex}
            if epochs > len(schedule):
                caches0 = tuple(
                    jax.ShapeDtypeStruct((k,) + s, jnp.float32)
                    for s in _stale_cache_shapes(cfg, batch.n_pad))
                step_st = _build(steps["stale"], params, opt, tensors, keys0,
                                 caches0)
                programs["hlo_stale"] = step_st
        else:
            # period=∞ never moves a byte: the frozen step is both the
            # "whole training" program and the between-exchange program
            step_fz = _build(steps["frozen"], params, opt, tensors, keys0)
            programs = {"hlo": step_fz, "hlo_stale": step_fz}
        if hlo_out is not None:
            hlo_out.update({n: c.as_text() for n, c in programs.items()})

        def kind_of(e):
            return ("exchange" if e in schedule
                    else "stale" if schedule else "frozen")

        caches = None

        def run_epoch(e, keys):
            nonlocal params, opt, caches
            kind = kind_of(e)
            if kind == "exchange":
                params, opt, loss, caches = step_ex(params, opt, tensors,
                                                    keys)
            elif kind == "frozen":
                params, opt, loss = step_fz(params, opt, tensors, keys)
            else:
                params, opt, loss = step_st(params, opt, tensors, keys,
                                            caches)
            return loss

        _run_epochs(epochs, key, k, "stale", run_epoch, kind_of)

        # Embedding pass mirrors training: a live refresh when the run ever
        # exchanged (sync limit stays exact), the plain local forward
        # otherwise (local limit stays exact).
        forward = make_halo_forward(cfg, halo)
        eval_mode = "exchange" if schedule else "frozen"
        embed = _halo_embed(
            lambda p, t, i: forward(p, t, i, refresh_mode=eval_mode)[0], mesh)
        return _embed_and_pool(params, integrate, embed, tensors, k, pt,
                               ds.graph.n, cfg.embed_dim)


# ---------------------------------------------------------------------------
# Classifier on pooled embeddings (paper §5.2) + metrics
# ---------------------------------------------------------------------------
def train_classifier(ds: NodeDataset, embeddings: np.ndarray,
                     hidden: int = 256, epochs: int = 150, lr: float = 1e-2,
                     seed: int = 0, return_params: bool = False):
    """Train the MLP on frozen pooled embeddings; report accuracy/ROC-AUC.

    With ``return_params=True`` returns ``(metrics, params)`` — the trained
    MLP pytree the serving bundle exports so online answers reproduce the
    offline evaluation exactly (DESIGN.md §13)."""
    key = jax.random.PRNGKey(seed)
    params = init_mlp(key, embeddings.shape[1], hidden, ds.num_classes)
    opt = adamw_init(params)
    x = jnp.asarray(embeddings)
    y = jnp.asarray(ds.labels if ds.multilabel else ds.labels.astype(np.int32))
    tr = jnp.asarray(ds.train_mask.astype(np.float32))

    def loss_fn(p):
        logits = mlp_forward(p, x)
        if ds.multilabel:
            return sigmoid_bce(logits, y, tr)
        return softmax_xent(logits, y, tr)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, o = adamw_update(g, o, p, lr)
        return p, o, loss

    for _ in range(epochs):
        params, opt, loss = step(params, opt)

    logits = np.asarray(jax.jit(mlp_forward)(params, x))
    out = {}
    for split, mask in (("train", ds.train_mask), ("val", ds.val_mask),
                        ("test", ds.test_mask)):
        if ds.multilabel:
            out[split] = float(mean_rocauc(ds.labels[mask], logits[mask]))
        else:
            pred = logits[mask].argmax(-1)
            out[split] = float((pred == ds.labels[mask]).mean())
    if return_params:
        return out, params
    return out


def mean_rocauc(y: np.ndarray, score: np.ndarray) -> float:
    """Mean ROC-AUC over tasks (rank statistic, ties averaged)."""
    aucs = []
    for t in range(y.shape[1]):
        yt, st = y[:, t], score[:, t]
        pos = yt > 0.5
        n_pos, n_neg = int(pos.sum()), int((~pos).sum())
        if n_pos == 0 or n_neg == 0:
            continue
        order = np.argsort(st, kind="mergesort")
        ranks = np.empty_like(order, dtype=np.float64)
        ranks[order] = np.arange(1, len(st) + 1)
        # average ties
        sorted_s = st[order]
        i = 0
        while i < len(st):
            j = i
            while j + 1 < len(st) and sorted_s[j + 1] == sorted_s[i]:
                j += 1
            if j > i:
                ranks[order[i:j + 1]] = (i + j + 2) / 2.0
            i = j + 1
        auc = (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
        aucs.append(auc)
    return float(np.mean(aucs)) if aucs else 0.5
