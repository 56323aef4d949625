"""Faults planted in the program under test, to show that the check refuses
them. Each is a context manager that swaps one function of
``repro.gnn.train`` for a broken one while the job runs:

- ``unchanged_state``: the optimizer step returns the parameters and its
  state as they were;
- ``half_batch``: the loss leaves out every second row and takes the mean
  over the rest;
- ``answer_altered``: the assembled embedding table has one row doubled
  where it is produced (the row of largest norm);
- ``no_exchange``: the sync step's halo refresh is left out (the halo rows
  keep what the partition computes itself).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict


@contextlib.contextmanager
def _swap(name: str, make: Callable):
    from repro.gnn import train
    orig = getattr(train, name)
    setattr(train, name, make(orig))
    try:
        yield
    finally:
        setattr(train, name, orig)


def unchanged_state():
    return _swap("adamw_update",
                 lambda orig: lambda grads, state, params, lr, **kw:
                 (params, state))


def half_batch():
    def make(orig):
        import jax.numpy as jnp

        def loss(logits, labels, mask):
            keep = jnp.arange(mask.shape[0]) % 2 == 0
            return orig(logits, labels, mask * keep)
        return loss
    return _swap("softmax_xent", make)


def answer_altered():
    def make(orig):
        import numpy as np

        def pool(*args, **kw):
            out = orig(*args, **kw)
            row = int(np.argmax(np.linalg.norm(out, axis=1)))
            out[row] *= 2.0
            return out
        return pool
    return _swap("pool_embeddings", make)


def no_exchange():
    def make(orig):
        from repro.gnn import train

        def forward_of(cfg, halo, axis="data"):
            halo_forward = train.make_halo_forward(cfg, halo, axis)

            def forward(params, t, my_idx, dropout_key=None):
                h, logits, _ = halo_forward(params, t, my_idx, dropout_key,
                                            refresh_mode="frozen")
                return h, logits
            return forward
        return forward_of
    return _swap("make_sync_forward", make)


FAULTS: Dict[str, Callable] = {
    "unchanged_state": unchanged_state, "half_batch": half_batch,
    "answer_altered": answer_altered, "no_exchange": no_exchange}


def for_mode(mode: str):
    """The faults a cell of this traffic mode can have."""
    names = ["unchanged_state", "half_batch", "answer_altered"]
    return names + (["no_exchange"] if mode == "sync" else [])
