"""The DeepSeek-V3.2 cell's own pieces: a whole run at a CPU size judged by
the comparison that decides ``correct`` (sound passes; the float8 control,
a planted fault and an indexer whose choice is ignored fail), and the
step's FLOPs and the sparse MLA kernel's cost against hand counts.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import time

import jax.numpy as jnp
import pytest
import tiny

from benchlib import correct, faults, readers, runner

CELL = "deepseek-v32-exp-l5.long-decode"
LIMIT = 1e-3   # float32 program vs float32 reference: rounding only
COMPARED = "logit_gap_mean"


@pytest.fixture(autouse=True)
def tiny_limit(monkeypatch):
    monkeypatch.setattr(correct, "limits",
                        lambda name: {COMPARED: {"limit": LIMIT}})


def first_tokens(monkeypatch):
    """The indexer's scores computed and ignored: each decode step attends
    to the first top_k tokens of its context."""
    from repro.core.methods import dsa
    real = dsa.token_topk

    def first(q, w, keys, context, top_k):
        ids, n = real(q, w, keys, context, top_k)
        return jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=ids.dtype),
                                ids.shape), n

    monkeypatch.setattr(dsa, "token_topk", first)


def run(fault=None, control=False):
    cell = tiny.tiny_cell(CELL)
    cell.config["torch_dtype"] = "float32"
    return runner.run_cell(cell, 2**33 + 7, 2.0, False,
                           t_start=time.perf_counter(), fault=fault,
                           control=control)


def test_sound_run_is_correct_and_control_is_not():
    res = run(control=True)
    assert res["checks"][COMPARED]["value"] <= LIMIT
    assert res["correct"]
    assert res["control"][COMPARED] > 3 * LIMIT
    assert not res["control"]["correct"]


@pytest.mark.parametrize("fault", ["alter_a_token", "state_unchanged",
                                   "first_tokens"])
def test_faults_are_caught(fault, monkeypatch):
    if fault == "first_tokens":
        first_tokens(monkeypatch)
        res = run()
    else:
        res = run(fault=faults.FAULTS[fault])
    assert res["checks"][COMPARED]["value"] > LIMIT
    assert not res["correct"]


def config():
    return tiny.spec.load_json(tiny.spec.BENCH / "configs"
                               / "deepseek-v32-exp-l5.json")


def test_decode_step_flops():
    # per layer: MLA 340,656,128 + absorption 2 * 16,777,216, attention
    # over 2048 rows 2*128*576*2048 + 2*128*512*2048 = 570,425,344, indexer
    # 27,918,336 + 18000 * (2*64*128 + 3*64) = 326,286,336; x 5 layers;
    # + dense FFN 792,723,456; + 4 MoE layers x (router 3,670,016 + shared
    # 88,080,384 + routed 0.25 x 88,080,384); + lm_head 2*7168*129280
    c = config()
    flops = tiny.spec.arch(c).decode_flops(c, 18000)
    per_layer = 340_656_128 + 33_554_432 + 570_425_344 + 326_286_336
    moe = 3_670_016 + 88_080_384 + 22_020_096
    assert flops == 5 * per_layer + 792_723_456 + 4 * moe + 1_853_358_080
    assert flops == 9_455_774_720
    # the indexer scores every cached key: 1000 more tokens, 5 layers
    assert tiny.spec.arch(c).decode_flops(c, 19000) - flops == \
        5 * 1000 * 16576


def test_mla_kernel_cost():
    cost = readers.module("kernels", "mla_sparse_decode_attention").cost
    # per slot: 2048 rows of 576 bf16, the f32 query 128x576, the f32
    # output 128x512, the row count
    assert cost(config(), [18000, 19000]) == (
        2 * 570_425_344, 2 * (2048 * 576 * 2 + 128 * 576 * 4
                              + 128 * 512 * 4 + 4))
    # a context shorter than top_k attends to all of it
    assert cost(config(), [1000])[0] == 1000 * (2 * 128 * 576
                                                + 2 * 128 * 512)
