"""GAT, the model of configurations whose ``model`` is ``"gat"``
(Veličković et al., "Graph Attention Networks", arXiv:1710.10903,
eq. (1)-(6)); what a model module holds is listed in ``bench/models/gcn.py``.

The layer: with ``z = h @ W`` split into H heads of width D, head h scores
arc u -> v as ``LeakyReLU_0.2(<z_u, a_l> + <z_v, a_r>)``, and the self arc
v -> v alike; a softmax over each row's in-arcs and itself weighs the sum
of the sources' rows (the self term included). Hidden layers concatenate
their heads, add the bias and apply ELU; the last averages them and adds
the bias. Padding arcs (weight 0) score -inf; arc weights are not
multiplied in. Padding rows are held at zero; dropout follows every layer
but the last; a linear head gives the logits.

Parameters follow the program's key schedule: each partition's key splits
into the body's and the head's, the body's into one per layer, each
layer's into W's, a_l's and a_r's, all Glorot-uniform (a head's
``[a_l; a_r]`` maps 2*D to 1); biases start at zero; the head is
He-normal.

The forward takes the partitions one at a time (``jax.lax.map`` over a
``jax.checkpoint``-ed forward of one partition), so that one partition's
per-arc rows, not k of them, are live on the chip at the cell's widths.
Halo rows are not refreshed: the reference runs local cells only.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench import reference


def program_config(config: Dict, feature_dim: int):
    from repro.gnn import GNNConfig
    return GNNConfig(kind="gat", feature_dim=feature_dim,
                     hidden_dim=config["head_dim"],
                     embed_dim=config["embed_dim"],
                     num_layers=config["num_layers"],
                     dropout=config["dropout"],
                     use_kernel=config["use_kernel"],
                     heads=config["heads"])


def layer_widths(config: Dict, feature_dim: int) -> List[Tuple[int, int]]:
    """(input width, output width of one head) of each layer."""
    heads, head_dim = config["heads"], config["head_dim"]
    hidden = config["num_layers"] - 1
    ins = [feature_dim] + [heads * head_dim] * hidden
    return list(zip(ins, [head_dim] * hidden + [config["embed_dim"]]))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _glorot(key, shape, fan_in: int, fan_out: int):
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init_params(seed: int, m: "reference.Model", k: int):
    """k stacked GAT + head parameter sets from ``PRNGKey(seed)``."""
    widths = layer_widths(m.config, m.feature_dim)
    heads = m.config["heads"]
    embed = widths[-1][1]

    def one(key):
        kb, kh = jax.random.split(key)
        layers = []
        for i, (lk, (fi, fo)) in enumerate(
                zip(jax.random.split(kb, len(widths)), widths)):
            kw, kl, kr = jax.random.split(lk, 3)
            last = i == len(widths) - 1
            layers.append({
                "w": _glorot(kw, (fi, heads * fo), fi, heads * fo),
                "a_l": _glorot(kl, (heads, fo), 2 * fo, 1),
                "a_r": _glorot(kr, (heads, fo), 2 * fo, 1),
                "b": jnp.zeros((fo if last else heads * fo,), jnp.float32)})
        head = {"w": jax.random.normal(kh, (embed, m.num_classes))
                * jnp.sqrt(2.0 / embed),
                "b": jnp.zeros((m.num_classes,))}
        return {"body": {"layers": layers}, "head": head}
    return jax.jit(jax.vmap(one))(jax.random.split(jax.random.PRNGKey(seed),
                                                   k))


def _layer(lp, h, s, d, w, last: bool, ar: "reference.Arithmetic"):
    """One partition's GAT layer: h [N, F_in] -> [N, H*D] or [N, D]."""
    n = h.shape[0]
    heads, dim = lp["a_l"].shape
    z = reference.product("nf,fo->no", h, lp["w"], ar.body, ar)
    z = z.reshape(n, heads, dim)
    el = reference.product("nhd,hd->nh", z, lp["a_l"], ar.body, ar)
    er = reference.product("nhd,hd->nh", z, lp["a_r"], ar.body, ar)
    score = jax.nn.leaky_relu(el[s] + er[d], 0.2)
    score = jnp.where((w != 0)[:, None], score, -jnp.inf)            # [E, H]
    own = jax.nn.leaky_relu(el + er, 0.2)                            # [N, H]
    top = jnp.maximum(jax.ops.segment_max(score, d, num_segments=n), own)
    top = jax.lax.stop_gradient(top)
    p = jnp.exp(score - top[d])
    p_own = jnp.exp(own - top)
    total = jax.ops.segment_sum(p, d, num_segments=n) + p_own
    out = (jax.ops.segment_sum(z[s] * (p / total[d])[..., None], d,
                               num_segments=n)
           + (p_own / total)[..., None] * z)
    if last:
        return jnp.mean(out, axis=1) + lp["b"]
    return jax.nn.elu(out.reshape(n, heads * dim) + lp["b"])


def forward(params, m: "reference.Model", t, keys,
            ar: "reference.Arithmetic"):
    """Stacked forward over all k partitions, one partition at a time:
    (embeddings, logits)."""
    if m.sync:
        raise ValueError("the GAT reference runs local cells only")
    layers = params["body"]["layers"]
    train = keys is not None

    def one(args):
        lps, x, mask, s, d, w, key = args
        h = x * mask[:, None]
        for i, lp in enumerate(lps):
            last = i == len(lps) - 1
            h = _layer(lp, h, s, d, w, last, ar) * mask[:, None]
            if not last and train:
                h, key = reference.dropout(h[None], key[None], m.dropout)
                h, key = h[0], key[0]
        return h

    per_key = keys if train else jnp.zeros((t["x"].shape[0], 2), jnp.uint32)
    h = jax.lax.map(jax.checkpoint(one),
                    (layers, t["x"], t["mask"], t["src"], t["dst"], t["w"],
                     per_key))
    return h, reference.head_logits(params["head"], h, ar)


# ---------------------------------------------------------------------------
# work counted from shapes (each partition's real rows and arcs)
# ---------------------------------------------------------------------------
def flops_per_epoch(config: Dict, nodes: Sequence[int], arcs: Sequence[int],
                    num_classes: int) -> float:
    """Multiply-adds (x2) one training epoch needs over all partitions,
    forward and backward: per layer the dense product (its weight gradient,
    and its input gradient after the first layer), the two score products
    ``<z, a>`` (and their gradients for z and a), and the arc sum over H
    heads of D (its transposed pass for z and its row dot for the
    attention); then the head. The softmax's elementwise work is not
    counted."""
    heads = config["heads"]
    layers = layer_widths(config, config["feature_dim"])
    embed = layers[-1][1]
    total = 0.0
    for n, e in zip(nodes, arcs):
        for i, (fi, fo) in enumerate(layers):
            dense = 2 * n * fi * heads * fo
            scores = 2 * 2 * n * heads * fo
            arc_sum = 2 * e * heads * fo
            total += dense + scores + arc_sum                  # forward
            total += dense + 2 * scores + 2 * arc_sum          # backward
            if i > 0:
                total += dense                                 # dh
        total += 3 * 2 * n * embed * num_classes               # head f + b
    return total


def aggregation_least_work(config: Dict, nodes: Sequence[int],
                           arcs: Sequence[int], backward: bool
                           ) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each aggregation kernel call one pass makes, summed
    over partitions and heads: the least any implementation must do.

    Forward, per layer: the arc sum, 2*E*H*D FLOPs, reading the projected
    rows (N*H*D*4 B) and the arcs with their attention (source and
    destination, 8 B, and H weights, 4*H B, each) and writing N*H*D*4 B.
    Backward (``backward``), per layer, every layer (the first layer's W
    needs its z's gradient too): the transposed pass, the same work; and
    the attention's gradient, a row dot of 2*E*H*D FLOPs reading the
    cotangent and the projected rows (2*N*H*D*4 B) and the arcs, and
    writing E*H*4 B."""
    heads = config["heads"]
    calls = []
    for _, fo in layer_widths(config, config["feature_dim"]):
        hd = heads * fo
        flops = sum(2 * e * hd for e in arcs)
        byts = sum(2 * n * hd * 4 + e * (8 + 4 * heads)
                   for n, e in zip(nodes, arcs))
        calls.append((flops, byts))
        if backward:
            calls.append((flops, byts))
            calls.append((flops, byts))
    return calls
