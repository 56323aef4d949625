"""The controls: the reference put in the program's place, in an arithmetic
below the one the configuration states (bfloat16 storage; the dense
products' operands rounded to float8 e4m3), against the reference, at a
tiny size on the CPU. The real cells' limits must refuse each, and must
pass the reference against itself. (The controls at a lower matmul
precision need the chip: on the CPU every precision computes in float32.)"""
import os

import pytest

import tinycell
from bench import harness

LIMITS = ["arxiv-gcn-pallas.k8-local", "arxiv-gcn.k4-sync"]


@pytest.fixture(scope="module")
def job_and_reference(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("control"))
    cell = harness.load_cell(tinycell.write_root(tmp, mode="local"),
                             root=tmp)
    job = harness.prepare(cell, 2**31 + 3, cache=os.path.join(tmp, "c"))
    layout = harness.reference_layout(job)
    return job, layout, harness.reference_run(job, layout, 3)


def _as_program(r):
    return harness.Program(r.losses, r.params, r.embeddings, r.embeddings0)


def _refused(job_and_reference, limits_of, **control):
    job, layout, ref = job_and_reference
    limits = harness.load_cell(limits_of).limits
    same = harness.compare(_as_program(ref), ref)
    assert harness.judge(same, limits)[0]
    low = harness.reference_run(job, layout, 3, **control)
    correct, checks = harness.judge(harness.compare(_as_program(low), ref),
                                    limits)
    assert not correct, checks


@pytest.mark.parametrize("limits_of", LIMITS)
def test_float8_control_is_refused(job_and_reference, limits_of):
    _refused(job_and_reference, limits_of, product_dtype="float8_e4m3fn")


@pytest.mark.parametrize("limits_of", LIMITS)
def test_bfloat16_control_is_refused(job_and_reference, limits_of):
    _refused(job_and_reference, limits_of, dtype="bfloat16")
