"""Plain reference of the timed training job: k GNNs trained on their
partition subgraphs, written from the published semantics in straight
``jax.numpy``. It imports nothing of the program under test.

What it is given is the job's input: the graph (CSR arrays), node features,
labels, the train mask, the node-to-partition assignment and the seed. It
builds each partition's subgraph itself:

- ``repli`` layout: partition p holds its owned nodes (ascending id), then
  its 1-hop halo (ascending id): every node outside p with an arc into an
  owned node. It keeps every arc whose destination it owns. Rows are padded
  to the largest partition, rounded up to 8, so that the dropout masks,
  which are drawn at the padded shape, are the same bits as the program's.
- the model's layers, parameters and head are its model module's
  (``bench/models/<model>.py``, named by the configuration's ``model``);
  this file gives every model the pieces they share: the halo refresh
  (:func:`refresh`), dropout (:func:`dropout`), the dense products in the
  stated arithmetic (:func:`product`) and the linear head
  (:func:`head_logits`).
- the masked softmax cross-entropy over owned train nodes.
- ``sync`` mode refreshes every halo row from its owner partition before
  every layer, and the gradient flows back through that refresh: the loss
  that is differentiated is the sum of the partitions' losses.
- AdamW (b1 0.9, b2 0.999, eps 1e-8, no weight decay) with the gradient
  clipped to global norm 1 per partition.
- the embedding table is the last layer's output without dropout, each node
  taken from the partition that owns it.

Random draws follow the program's published key schedule: parameters from
``PRNGKey(seed)`` split per partition, and at epoch e the dropout keys
``split(fold_in(PRNGKey(seed), e), k)``, each split again before every
layer's mask.

The arithmetic is the one the configuration states (:class:`Arithmetic`):
the storage type, and the precision of the body's dense products and of the
head's. A control computed below it, which a correct comparison must
refuse, changes one of them, or with ``product_dtype`` rounds the dense
products' operands, and the cotangents through them, to that type first.
"""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Dict, List, Mapping, Optional

import numpy as np

import jax
import jax.numpy as jnp

B1, B2, EPS, CLIP = 0.9, 0.999, 1e-8, 1.0


@dataclasses.dataclass(frozen=True)
class Arithmetic:
    """Storage type, and the ``jax.lax.Precision`` of the body's dense
    products and of the head's."""
    body: str = "highest"
    head: str = "highest"
    dtype: str = "float32"
    product_dtype: Optional[str] = None

    @classmethod
    def of(cls, precision: Dict) -> "Arithmetic":
        """As a configuration's ``precision`` entry states it."""
        return cls(body=precision["body_products"],
                   head=precision["head_product"],
                   dtype=precision["storage"])


@dataclasses.dataclass(frozen=True)
class Layout:
    """The k partition subgraphs, stacked and padded: [k, ...] arrays."""
    node_ids: np.ndarray     # [k, n_pad] int64, -1 on padding rows
    owned: np.ndarray        # [k, n_pad] bool
    src: np.ndarray          # [k, e_pad] int32 local rows
    dst: np.ndarray          # [k, e_pad] int32 local rows
    weight: np.ndarray       # [k, e_pad] float32, 0 on padding arcs
    degree: np.ndarray       # [k, n_pad] float32, arcs into the row
    refresh: np.ndarray      # [k, n_pad] int64 flat row of the owner's copy
    n_nodes: np.ndarray      # [k] real rows
    n_arcs: np.ndarray       # [k] real arcs

    @property
    def k(self) -> int:
        return self.node_ids.shape[0]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def build_layout(indptr: np.ndarray, indices: np.ndarray,
                 arc_weight: np.ndarray, parts: np.ndarray) -> Layout:
    """The ``repli`` subgraphs of the assignment ``parts`` ([n] ints)."""
    n = parts.shape[0]
    k = int(parts.max()) + 1
    arc_src = np.repeat(np.arange(n), np.diff(indptr))
    arc_dst = np.asarray(indices, np.int64)
    nodes_of, arcs_of = [], []
    for p in range(k):
        owned = np.flatnonzero(parts == p)
        keep = parts[arc_dst] == p
        s, d = arc_src[keep], arc_dst[keep]
        halo = np.unique(s[parts[s] != p])
        nodes = np.concatenate([owned, halo])
        row = np.full(n, -1, np.int64)
        row[nodes] = np.arange(nodes.shape[0])
        nodes_of.append((nodes, owned.shape[0]))
        arcs_of.append((row[s], row[d], arc_weight[keep]))
    n_pad = _round_up(max(x[0].shape[0] for x in nodes_of), 8)
    e_pad = _round_up(max(a[0].shape[0] for a in arcs_of), 8)
    node_ids = np.full((k, n_pad), -1, np.int64)
    owned = np.zeros((k, n_pad), bool)
    src = np.zeros((k, e_pad), np.int32)
    dst = np.zeros((k, e_pad), np.int32)
    weight = np.zeros((k, e_pad), np.float32)
    degree = np.zeros((k, n_pad), np.float32)
    # owner row of every node: owned nodes come first, in ascending id
    owner_row = np.zeros(n, np.int64)
    for p, (nodes, n_own) in enumerate(nodes_of):
        owner_row[nodes[:n_own]] = p * n_pad + np.arange(n_own)
    refresh = np.arange(k * n_pad, dtype=np.int64).reshape(k, n_pad)
    for p, ((nodes, n_own), (s, d, w)) in enumerate(zip(nodes_of, arcs_of)):
        node_ids[p, :nodes.shape[0]] = nodes
        owned[p, :n_own] = True
        src[p, :s.shape[0]] = s
        dst[p, :d.shape[0]] = d
        weight[p, :w.shape[0]] = w
        degree[p] = np.bincount(d, minlength=n_pad)
        refresh[p, n_own:nodes.shape[0]] = owner_row[nodes[n_own:]]
    return Layout(node_ids=node_ids, owned=owned, src=src, dst=dst,
                  weight=weight, degree=degree, refresh=refresh,
                  n_nodes=np.array([x[0].shape[0] for x in nodes_of]),
                  n_arcs=np.array([a[0].shape[0] for a in arcs_of]))


@dataclasses.dataclass(frozen=True)
class Model:
    """What a model module's reference is built from."""
    module: ModuleType   # bench/models/<model>.py
    config: Mapping      # the configuration file
    feature_dim: int
    num_classes: int
    sync: bool           # halo rows refreshed from their owners

    @property
    def dropout(self) -> float:
        return self.config["dropout"]

    @property
    def lr(self) -> float:
        return self.config["lr"]


def refresh(h, t):
    """Every halo row replaced by its owner partition's copy
    (``h``: [k, n_pad, F])."""
    k, n_pad = h.shape[:2]
    flat = h.reshape(k * n_pad, -1)
    return flat[t["refresh"]].reshape(k, n_pad, -1)


def dropout(h, keys, rate: float):
    """Inverted dropout of every partition's rows, each with its own key;
    returns (h, the keys for the next layer). With no keys (the embedding
    pass) or no rate, ``h`` as it is."""
    if keys is None or rate <= 0:
        return h, keys
    split = jax.vmap(jax.random.split)(keys)
    keys, sub = split[:, 0], split[:, 1]
    keep = jax.vmap(lambda s: jax.random.bernoulli(
        s, 1 - rate, (h.shape[1], h.shape[-1])))(sub)
    return jnp.where(keep, h / (1 - rate), 0.0), keys


def product(spec: str, a, b, precision: str, ar: "Arithmetic"):
    """A dense product at ``precision``, its operands first rounded to
    ``ar.product_dtype`` where a control sets one."""
    if ar.product_dtype is not None:
        a, b = (v.astype(ar.product_dtype).astype(v.dtype) for v in (a, b))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision(precision))


def head_logits(head, h, ar: "Arithmetic"):
    """The per-partition linear head on the embeddings."""
    return product("kne,kec->knc", h, head["w"], ar.head, ar) \
        + head["b"][:, None, :]


def _losses(params, m: Model, t, keys, ar: Arithmetic):
    _, logits = m.module.forward(params, m, t, keys, ar)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, t["y"][..., None], axis=-1)[..., 0]
    tm = t["train"]
    return jnp.sum(nll * tm, axis=1) / jnp.maximum(tm.sum(axis=1), 1.0)


def _adamw(params, grads, mu, nu, step, lr):
    """One AdamW step per partition (leading axis k); returns the new
    (params, mu, nu)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g), axis=tuple(
        range(1, g.ndim))) for g in leaves))             # [k]
    scale = jnp.minimum(1.0, CLIP / (gnorm + 1e-9))

    def clip(g):
        return g * scale.reshape((-1,) + (1,) * (g.ndim - 1))
    grads = jax.tree.map(clip, grads)
    mu = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, nu, grads)
    bc1, bc2 = 1.0 - B1 ** step, 1.0 - B2 ** step
    params = jax.tree.map(
        lambda p, a, v: (p - lr * ((a / bc1) / (jnp.sqrt(v / bc2) + EPS))
                         ).astype(p.dtype),
        params, mu, nu)
    return params, mu, nu


def device_tensors(layout: Layout, features: np.ndarray, labels: np.ndarray,
                   train_mask: np.ndarray, sharding=None) -> Dict:
    """The stacked partition arrays on the device(s)."""
    ids = np.maximum(layout.node_ids, 0)
    valid = layout.node_ids >= 0
    t = {"x": features[ids] * valid[..., None],
         "y": labels[ids].astype(np.int32),
         "train": (train_mask[ids] & layout.owned & valid).astype(np.float32),
         "mask": valid.astype(np.float32),
         "src": layout.src, "dst": layout.dst, "w": layout.weight,
         "deg": layout.degree, "refresh": layout.refresh.reshape(-1)}
    out = {}
    for name, v in t.items():
        v = np.asarray(v)
        if sharding is not None and name != "refresh":
            out[name] = jax.device_put(v, sharding)
        else:
            out[name] = jnp.asarray(v)
    return out


@dataclasses.dataclass
class Readings:
    """What the comparison reads from a reference run."""
    losses: List[float]            # per step, mean over the k partitions
    params0: object                # initial parameters (host arrays)
    params: object                 # parameters after the last step
    grad1_norms: np.ndarray        # [k, leaves] first gradient norm per leaf
    embeddings: np.ndarray         # [n, embed_dim] pooled table
    embeddings0: np.ndarray        # the same at the initial parameters


def train(layout: Layout, t: Dict, m: Model, seed: int, steps: int,
          num_nodes: int, ar: Arithmetic = Arithmetic(),
          sharding=None) -> Readings:
    """The pooled table at the initial parameters, ``steps`` training
    steps from the seed, then the embedding pass and the pooling, in the
    arithmetic ``ar``; with ``sharding`` the partitions are spread over the
    devices along their leading axis."""
    k = layout.k
    params = m.module.init_params(seed, m, k)
    params0 = jax.tree.map(np.asarray, params)
    store = jnp.dtype(ar.dtype)
    if store != jnp.float32:
        params = jax.tree.map(lambda x: x.astype(store), params)
        t = {n: v.astype(store) if v.dtype == jnp.float32 and n != "train"
             else v for n, v in t.items()}
    if sharding is not None:
        params = jax.device_put(params, sharding)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    key = jax.random.PRNGKey(seed)
    embed = jax.jit(lambda p, t: m.module.forward(p, m, t, None, ar)[0])

    def table(p):
        return pool(np.asarray(embed(p, t), np.float32), layout, num_nodes)
    emb0 = table(params)

    @jax.jit
    def step(params, mu, nu, t, keys, i):
        def total(p):
            losses = _losses(p, m, t, keys, ar)
            return jnp.sum(losses), losses
        (_, losses), grads = jax.value_and_grad(total, has_aux=True)(params)
        gn = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g), axis=tuple(
            range(1, g.ndim)))) for g in jax.tree.leaves(grads)], axis=1)
        params, mu, nu = _adamw(params, grads, mu, nu, i, m.lr)
        return params, mu, nu, jnp.mean(losses), gn

    losses, g1 = [], None
    for e in range(steps):
        keys = jax.random.split(jax.random.fold_in(key, e), k)
        params, mu, nu, loss, gn = step(params, mu, nu, t, keys,
                                        jnp.float32(e + 1))
        losses.append(float(loss))
        if g1 is None:
            g1 = np.asarray(gn)
    return Readings(losses=losses, params0=params0,
                    params=jax.tree.map(np.asarray, params),
                    grad1_norms=np.asarray(g1, np.float32),
                    embeddings=table(params), embeddings0=emb0)


def pool(emb: np.ndarray, layout: Layout, num_nodes: int) -> np.ndarray:
    """Each node's row from the partition that owns it."""
    out = np.zeros((num_nodes, emb.shape[-1]), np.float32)
    for p in range(layout.k):
        own = layout.owned[p]
        out[layout.node_ids[p][own]] = emb[p][own]
    return out
