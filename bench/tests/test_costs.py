"""The benchmark's algorithmic FLOP and byte counts against numbers worked
out by hand at each cell's shapes (bench/kernels/)."""
import pytest
from tiny import spec  # noqa: F401  (puts bench/ on the path)

from benchlib import readers


def config(name):
    return spec.load_json(spec.BENCH / "configs" / f"{name}.json")


def test_relevancy_topk_qwen3_long():
    # pages 1125 + 1188 = 2313; per page 2*64*128 + 3*64 = 16576
    # bytes: 2313 pages * 128 * 2 + 2 * (64*128*2 + 64*4 + 128*8)
    flops, nbytes = readers.module("kernels", "relevancy_topk").cost(
        config("qwen3-32b-l4"), [18000, 19000])
    assert flops == 38_340_288
    assert nbytes == 627_456


def test_paged_decode_attention_both_configs():
    cost = readers.module("kernels", "paged_decode_attention").cost
    # qwen3: 4*64*128*2048 per slot; 2*2048*8*128*2 + 64*128*2 + 64*129*4
    # + 128*4 bytes per slot
    assert cost(config("qwen3-32b-l4"), [18000, 19000]) == (
        134_217_728, 16_877_056)
    # qwen2: G = 7 (28 heads over 4 KV heads), half the KV bytes
    assert cost(config("qwen2-7b-l7"), [18000]) == (29_360_128, 4_216_432)
    # a context shorter than top_k attends to all of it
    assert cost(config("qwen3-32b-l4"), [1000])[0] == 4 * 64 * 128 * 1000


def test_decode_step_qwen3():
    # per layer: weights 975,175,680 + indexer 154,176,448
    # + attention 67,108,864; x 4 layers; + lm_head 2*5120*151936
    qwen3 = config("qwen3-32b-l4")
    flops = spec.arch(qwen3).decode_flops(qwen3, 18000)
    assert flops == 4 * (975_175_680 + 154_176_448 + 67_108_864) \
        + 1_555_824_640


@pytest.mark.parametrize("name", ["qwen3-32b-l4", "qwen2-7b-l7"])
def test_costs_grow_linearly_with_context(name):
    cost = readers.module("kernels", "relevancy_topk").cost
    a = cost(config(name), [16000])
    b = cost(config(name), [32000])
    c = cost(config(name), [24000])
    assert c[0] == pytest.approx((a[0] + b[0]) / 2)
