"""The comparison that decides ``correct``, driven through a whole run of
each cell at a CPU size (float32 weights, so program and reference agree to
rounding): sound runs pass, and the float8 control and each fault the cells
can have fail, all judged by ``correct.judge`` on the number the cells
compare (the mean gap, bench/checks/<cell>.json) against a limit for this
size. The harness's look for a chip is skipped; everything after it is the
run the benchmark makes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import time

import pytest
import tiny

from benchlib import correct, faults, runner

LIMIT = 1e-3   # float32 program vs float32 reference: rounding only
COMPARED = "logit_gap_mean"


@pytest.fixture(autouse=True)
def tiny_limit(monkeypatch):
    monkeypatch.setattr(correct, "limits",
                        lambda name: {COMPARED: {"limit": LIMIT}})
    from repro.kernels import ops
    # restored after each test, whatever a fault put there
    monkeypatch.setattr(ops, "relevancy_topk", ops.relevancy_topk)


def run(name, fault=None, control=False, seconds=2.0, seed=5):
    cell = tiny.tiny_cell(name)
    cell.config["torch_dtype"] = "float32"
    return runner.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(), fault=fault,
                           control=control)


@pytest.mark.parametrize("name", ["qwen3-32b-l4.long-decode",
                                  "qwen2-7b-l7.long-decode",
                                  "qwen3-32b-l4.chat"])
def test_sound_run_is_correct_and_control_is_not(name):
    res = run(name, control=True)
    assert res["checks"][COMPARED]["value"] <= LIMIT
    assert res["correct"] and res["sound"]["correct"]
    assert res["control"][COMPARED] > 3 * LIMIT
    assert not res["control"]["correct"]


@pytest.mark.parametrize("fault,name", [
    ("alter_a_token", "qwen3-32b-l4.long-decode"),
    ("alter_a_token", "qwen3-32b-l4.chat"),
    ("state_unchanged", "qwen3-32b-l4.long-decode"),
    ("state_unchanged", "qwen3-32b-l4.chat"),
    ("first_pages", "qwen3-32b-l4.long-decode"),
    ("first_pages", "qwen2-7b-l7.long-decode"),
])
def test_faults_are_caught(name, fault):
    res = run(name, fault=faults.FAULTS[fault])
    assert res["checks"][COMPARED]["value"] > LIMIT
    assert not res["correct"]
