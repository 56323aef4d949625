"""Whole runs of the harness on the CPU, at a tiny size, with the look for a
chip skipped: a sound run is correct, and a run with a fault planted in the
timed path underneath (bench/faults.py) is not. The limits are the real
cells' (bench/limits/)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import tinycell

LOCAL = ["sound", "unchanged_state", "half_batch", "answer_altered"]
SYNC = LOCAL + ["no_exchange"]

RUN = """
import json, os, sys, time
sys.path.insert(0, {tests!r})
import tinycell
import jax
from bench import faults, harness

def run_all(tmp, mode, use_kernel, limits_from, names):
    cell = harness.load_cell(
        tinycell.write_root(tmp, mode=mode, use_kernel=use_kernel,
                            limits_from=limits_from), root=tmp)
    out = {{}}
    for name in names:
        ctx = faults.FAULTS[name]() if name != "sound" else None
        if ctx is not None:
            ctx.__enter__()
        try:
            res = harness.run(cell, 2**31 + 7, 0.0, False, time.perf_counter(),
                              cache=os.path.join(tmp, "cache"),
                              devices=jax.devices())
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        out[name] = {{"correct": res["correct"], "checks": res["checks"]}}
    return out

print("RESULT " + json.dumps(run_all({tmp!r}, {mode!r}, {use_kernel!r},
                                     {limits_from!r}, {names!r})))
"""


def _run(tmp, mode, use_kernel, limits_from, names, devices):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([tinycell.SRC, tinycell.ROOT])
    code = RUN.format(tests=os.path.dirname(os.path.abspath(__file__)),
                      tmp=str(tmp), mode=mode, use_kernel=use_kernel,
                      limits_from=limits_from, names=names)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def local_runs(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("local"), "local", True,
                "arxiv-gcn-pallas.k8-local", LOCAL, 1)


@pytest.fixture(scope="module")
def sync_runs(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sync"), "sync", False,
                "arxiv-gcn-pallas.k8-local", SYNC, 4)


@pytest.mark.parametrize("name", LOCAL)
def test_local_run_correct_only_when_sound(local_runs, name):
    res = local_runs[name]
    assert res["correct"] == (name == "sound"), res["checks"]


@pytest.mark.parametrize("name", SYNC)
def test_sync_run_correct_only_when_sound(sync_runs, name):
    res = sync_runs[name]
    assert res["correct"] == (name == "sound"), res["checks"]
