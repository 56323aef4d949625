"""GCN, the model of configurations whose ``model`` is ``"gcn"``.

A model module holds what the benchmark knows of one model, and
``bench/harness.py`` finds it by that key (``bench/models/<model>.py``):

- ``program_config(config, feature_dim)``: the program's ``GNNConfig``;
- ``init_params(seed, m, k)`` and ``forward(params, m, t, keys, ar)``: the
  plain reference's parameters and forward pass, in straight
  ``jax.numpy``, importing nothing of the program. ``m`` is a
  :class:`bench.reference.Model` (the configuration and the job's widths),
  ``t`` the stacked partition tensors of ``bench.reference.device_tensors``,
  ``keys`` the epoch's dropout keys (None for the embedding pass) and
  ``ar`` the :class:`bench.reference.Arithmetic` to compute in;
- ``flops_per_epoch`` and ``aggregation_least_work``: the work counted from
  shapes that the per-layer readers divide by time.

What every model shares (the partition layout, the loss, AdamW, the
training loop, the pooling) is in ``bench/reference.py``.

The layer is the paper's eq. (1): ``act(mean_{u -> v} w_uv h_u @ W + b)``
with the mean over the arcs kept (in-degree counted in arcs); padding rows
are held at zero; dropout follows every layer but the last; a linear head
gives the logits. Parameters follow the program's key schedule: each
partition's key splits into the body's and the head's, the body's into one
per layer.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench import reference


def program_config(config: Dict, feature_dim: int):
    from repro.gnn import GNNConfig
    return GNNConfig(kind="gcn", feature_dim=feature_dim,
                     hidden_dim=config["hidden_dim"],
                     embed_dim=config["embed_dim"],
                     num_layers=config["num_layers"],
                     dropout=config["dropout"],
                     use_kernel=config["use_kernel"])


def layer_widths(config: Dict, feature_dim: int) -> List[Tuple[int, int]]:
    """(input width, output width) of each layer."""
    hidden = [config["hidden_dim"]] * (config["num_layers"] - 1)
    dims = [feature_dim] + hidden + [config["embed_dim"]]
    return list(zip(dims[:-1], dims[1:]))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def init_params(seed: int, m: "reference.Model", k: int):
    """k stacked GCN + head parameter sets from ``PRNGKey(seed)``."""
    widths = layer_widths(m.config, m.feature_dim)
    embed = widths[-1][1]

    def one(key):
        kb, kh = jax.random.split(key)
        lkeys = jax.random.split(kb, len(widths))
        layers = [{"w": jax.random.normal(lkeys[i], (fi, fo), jnp.float32)
                   * jnp.sqrt(2.0 / fi),
                   "b": jnp.zeros((fo,), jnp.float32)}
                  for i, (fi, fo) in enumerate(widths)]
        head = {"w": jax.random.normal(kh, (embed, m.num_classes))
                * jnp.sqrt(2.0 / embed),
                "b": jnp.zeros((m.num_classes,))}
        return {"body": {"layers": layers}, "head": head}
    return jax.jit(jax.vmap(one))(jax.random.split(jax.random.PRNGKey(seed),
                                                   k))


def _mean_one(h, s, d, w, deg):
    """One partition's weighted mean over in-arcs."""
    tot = jax.ops.segment_sum(h[s] * w[:, None], d, num_segments=h.shape[0])
    return tot / jnp.maximum(deg, 1.0)[:, None]


def forward(params, m: "reference.Model", t, keys,
            ar: "reference.Arithmetic"):
    """Stacked forward over all k partitions: (embeddings, logits)."""
    mask = t["mask"][..., None]
    h = t["x"] * mask
    n_layers = len(params["body"]["layers"])
    for i, lp in enumerate(params["body"]["layers"]):
        last = i == n_layers - 1
        if m.sync:
            h = reference.refresh(h, t)
        agg = jax.vmap(_mean_one)(h, t["src"], t["dst"], t["w"], t["deg"])
        z = (reference.product("knf,kfo->kno", agg, lp["w"], ar.body, ar)
             + lp["b"][:, None, :])
        h = z if last else jax.nn.relu(z)
        h = h * mask
        if not last:
            h, keys = reference.dropout(h, keys, m.dropout)
    return h, reference.head_logits(params["head"], h, ar)


# ---------------------------------------------------------------------------
# work counted from shapes (each partition's real rows and arcs)
# ---------------------------------------------------------------------------
def flops_per_epoch(config: Dict, nodes: Sequence[int], arcs: Sequence[int],
                    num_classes: int) -> float:
    """Multiply-adds (x2) one training epoch needs over all partitions:
    the aggregations, the dense products and the head, forward and
    backward. The first layer needs no input gradient (the features are
    not trained) and no edge weight is trained, so neither is counted."""
    layers = layer_widths(config, config["feature_dim"])
    total = 0.0
    embed = layers[-1][1]
    for n, e in zip(nodes, arcs):
        for i, (fi, fo) in enumerate(layers):
            total += 2 * e * fi + 2 * n * fi * fo          # forward
            total += 2 * n * fi * fo                       # dW
            if i > 0:
                total += 2 * n * fi * fo + 2 * e * fi      # da, dh
        total += 3 * 2 * n * embed * num_classes           # head f + b
    return total


def aggregation_least_work(config: Dict, nodes: Sequence[int],
                           arcs: Sequence[int], backward: bool
                           ) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each aggregation kernel call one pass makes, summed
    over partitions: the least any implementation must do.

    Forward, per layer: the fused layer reads the input rows once
    (N*F*4 B), the arcs (source, destination, weight: 12 B each) and writes
    the output (N*F_out*4 B); it does 2*E*F for the aggregation and
    2*N*F*F_out for the fused dense product. Backward (``backward``), per
    layer after the first: the transposed aggregation of the input gradient,
    2*E*F, reading and writing N*F*4 B and the arcs."""
    calls = []
    for i, (fi, fo) in enumerate(layer_widths(config, config["feature_dim"])):
        flops = sum(2 * e * fi + 2 * n * fi * fo for n, e in zip(nodes, arcs))
        byts = sum(n * fi * 4 + e * 12 + n * fo * 4
                   for n, e in zip(nodes, arcs))
        calls.append((flops, byts))
        if backward and i > 0:
            calls.append((sum(2 * e * fi for e in arcs),
                          sum(2 * n * fi * 4 + e * 12
                              for n, e in zip(nodes, arcs))))
    return calls
