"""Multi-layer GNN (GCN / GraphSAGE / GAT) + MLP classifier head.

The GNN body produces node *embeddings* (paper: embeddings from local
training are pooled and an MLP classifier is trained on them)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import layer_for

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    kind: str = "gcn"              # "gcn" | "sage" | "gat"
    feature_dim: int = 128
    hidden_dim: int = 256          # per head for "gat"
    embed_dim: int = 256           # output embedding size
    num_layers: int = 3
    dropout: float = 0.5
    use_kernel: bool = False       # route aggregation through Pallas kernel
    heads: int = 1                 # "gat": hidden layers concatenate their
                                   # heads, the last averages them

    def __post_init__(self):
        if self.heads != 1 and self.kind != "gat":
            raise ValueError(f"heads={self.heads}: only a 'gat' layer has "
                             f"more than one head, not {self.kind!r}")


def layer_widths(cfg: GNNConfig) -> List[Tuple[int, int]]:
    """(input width, output width of one head) of each layer; a hidden
    layer's output, the next one's input, is its heads concatenated."""
    hidden = [cfg.hidden_dim] * (cfg.num_layers - 1)
    ins = [cfg.feature_dim] + [cfg.heads * d for d in hidden]
    return list(zip(ins, hidden + [cfg.embed_dim]))


def init_gnn(key, cfg: GNNConfig) -> PyTree:
    keys = jax.random.split(key, cfg.num_layers)
    _, init = layer_for(cfg.kind)
    return {"layers": [init(keys[i], f_in, f_out, heads=cfg.heads,
                            concat=i < cfg.num_layers - 1)
                       for i, (f_in, f_out) in enumerate(layer_widths(cfg))]}


def gnn_forward(params: PyTree, cfg: GNNConfig, features: jnp.ndarray,
                edge_src, edge_dst, edge_weight, in_degree,
                node_mask: Optional[jnp.ndarray] = None,
                dropout_key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Run the GNN body; returns [N, embed_dim] embeddings."""
    layer, _ = layer_for(cfg.kind)
    h = features
    if node_mask is not None:
        h = h * node_mask[:, None]          # zero padded rows
    n_layers = len(params["layers"])
    for i, lp in enumerate(params["layers"]):
        last = i == n_layers - 1
        h = layer(lp, h, edge_src, edge_dst, edge_weight, in_degree,
                  activate=not last, use_kernel=cfg.use_kernel)
        if node_mask is not None:
            h = h * node_mask[:, None]
        if dropout_key is not None and cfg.dropout > 0 and not last:
            dropout_key, sub = jax.random.split(dropout_key)
            keep = jax.random.bernoulli(sub, 1 - cfg.dropout, h.shape)
            h = jnp.where(keep, h / (1 - cfg.dropout), 0.0)
    return h


def head_logits(head: PyTree, emb: jnp.ndarray) -> jnp.ndarray:
    """Per-partition linear head on embeddings: ``emb @ w + b``.

    The single-node inference entry the serving layer shares with training
    (`_forward_one`, `make_halo_forward`): ``head`` is one partition's
    ``{"w": [E, C], "b": [C]}`` slice of the stacked params."""
    return emb @ head["w"] + head["b"]


# ---------------------------------------------------------------------------
# MLP classifier on pooled embeddings
# ---------------------------------------------------------------------------
def init_mlp(key, in_dim: int, hidden: int, out_dim: int) -> PyTree:
    k1, k2 = jax.random.split(key)
    s1, s2 = jnp.sqrt(2.0 / in_dim), jnp.sqrt(2.0 / hidden)
    return {"w1": jax.random.normal(k1, (in_dim, hidden)) * s1,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, out_dim)) * s2,
            "b2": jnp.zeros((out_dim,))}


def mlp_forward(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


def sigmoid_bce(logits: jnp.ndarray, targets: jnp.ndarray,
                mask: jnp.ndarray) -> jnp.ndarray:
    per = jnp.maximum(logits, 0) - logits * targets + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    per = per.mean(axis=-1)
    return jnp.sum(per * mask) / jnp.maximum(mask.sum(), 1.0)
