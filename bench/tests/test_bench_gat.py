"""A GAT cell (``bench/models/gat.py``) at a tiny size on the CPU: found by
name, built and run through the harness, and correct; refused with a
fault planted in the program (bench/faults.py) and with the reference put
in the program's place in an arithmetic below the stated one. The limits
are the ``arxiv-gat.k8-local`` cell's."""
import os
import sys
import time

import pytest

import tinycell
from bench import faults, harness

GAT = {"model": "gat", "heads": 2, "head_dim": 8, "embed_dim": 8}
LIMITS = "arxiv-gat.k8-local"


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("gat"))
    name = tinycell.write_root(tmp, use_kernel=True, limits_from=LIMITS,
                               config=GAT)
    return harness.load_cell(name, root=tmp)


def test_gat_cell_found_and_built(cell):
    model = harness.load_model(cell)
    cfg = model.program_config(cell.config, 8)
    assert (cfg.kind, cfg.heads, cfg.hidden_dim, cfg.embed_dim) == (
        "gat", 2, 8, 8)
    assert [m["name"] for m in harness.load_cell(LIMITS).per_layer
            ][-1] == "gat_agg_roofline_pct"


@pytest.mark.parametrize("name", ["sound", "unchanged_state", "half_batch",
                                  "answer_altered"])
def test_gat_run_correct_only_when_sound(cell, monkeypatch, name):
    # harness.run points the program's autotune cache into its cache and
    # puts the checkout's src on sys.path; both are restored after
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    monkeypatch.setattr(sys, "path", list(sys.path))
    import jax
    planted = faults.FAULTS[name]() if name != "sound" else None
    if planted is not None:
        planted.__enter__()
    try:
        res = harness.run(cell, 2**31 + 13, 0.0, False, time.perf_counter(),
                          cache=os.path.join(cell.root, "cache"),
                          devices=jax.devices())
    finally:
        if planted is not None:
            planted.__exit__(None, None, None)
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] == (name == "sound"), res["checks"]


def test_gat_bfloat16_control_is_refused(cell):
    job = harness.prepare(cell, 2**31 + 13,
                          cache=os.path.join(cell.root, "cache"))
    layout = harness.reference_layout(job)
    ref = harness.reference_run(job, layout, 3)
    as_program = lambda r: harness.Program(r.losses, r.params, r.embeddings,
                                           r.embeddings0)
    assert harness.judge(harness.compare(as_program(ref), ref),
                         cell.limits)[0]
    low = harness.reference_run(job, layout, 3, dtype="bfloat16")
    correct, checks = harness.judge(
        harness.compare(as_program(low), ref), cell.limits)
    assert not correct, checks
