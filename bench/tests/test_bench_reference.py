"""The plain reference computes what it computed before the model's layers
moved from bench/reference.py into bench/models/gcn.py: on the tiny cell,
local and sync, the losses of the three checked steps, each leaf's change,
the first gradient's norms and the table before and after training equal,
bit for bit, the readings recorded from the reference before the move
(fixtures/reference_tiny.npz, seed 2**31 + 3, on the CPU)."""
import os

import numpy as np
import pytest

import tinycell
from bench import harness

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "reference_tiny.npz")


def _readings(ref):
    import jax
    out = {"losses": np.asarray(ref.losses, np.float64),
           "embeddings": ref.embeddings, "embeddings0": ref.embeddings0,
           "grad1_norms": ref.grad1_norms}
    p0 = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_flatten_with_path(ref.params0)[0]}
    for p, v in jax.tree_util.tree_flatten_with_path(ref.params)[0]:
        key = jax.tree_util.keystr(p)
        out["change" + key] = np.asarray(v) - np.asarray(p0[key])
    return out


@pytest.mark.parametrize("mode", ["local", "sync"])
def test_reference_bits_as_recorded(tmp_path, mode):
    tmp = str(tmp_path)
    cell = harness.load_cell(tinycell.write_root(tmp, mode=mode), root=tmp)
    job = harness.prepare(cell, 2**31 + 3, cache=os.path.join(tmp, "c"))
    ref = harness.reference_run(job, harness.reference_layout(job), 3)
    got = _readings(ref)
    recorded = np.load(FIXTURE)
    want = {k[len(mode) + 1:]: recorded[k] for k in recorded.files
            if k.startswith(mode + ".")}
    assert sorted(got) == sorted(want)
    for key, value in want.items():
        np.testing.assert_array_equal(got[key], value, err_msg=key)
