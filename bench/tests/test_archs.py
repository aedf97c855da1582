"""Architecture modules (bench/archs/<arch>.py), which a configuration file
names with ``arch``: the weights they draw are the ones the harness drew
before the modules existed, their trees are the program's own, the shared
entry points reach whatever module a configuration names, and the step's
FLOPs are the hand count.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import hashlib
import types

import jax
import numpy as np
import pytest
from tiny import spec, tiny_cell

from benchlib import readers, weights

CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))
ARCHS = sorted(p.stem for p in (spec.BENCH / "archs").glob("*.py"))

# Leaf checksums of the weights at the tiny size, taken by running the
# generator the harness had before the architecture modules (spec.py's
# ARCH_KEYS, weights.py's _generate) with the same seeds: sha256 of the
# leaf's bytes, first 16 hex digits, then dtype and shape.
BIG_SEED = 2**33 + 20261018
QWEN_BF16 = {
    "[0]['embed']['w']": "f151a93918aabf58 bfloat16 (1024, 256)",
    "[0]['final_norm']['w']": "f9e72f234efbb64f float32 (256,)",
    "[0]['layers']['attn']['wk']": "d1fef4d170b16905 bfloat16 (2, 256, 128)",
    "[0]['layers']['attn']['wo']": "4d7f99c0617bbe11 bfloat16 (2, 256, 256)",
    "[0]['layers']['attn']['wq']": "1fea022cfe0fbd54 bfloat16 (2, 256, 256)",
    "[0]['layers']['attn']['wv']": "2fbd47359b9e8151 bfloat16 (2, 256, 128)",
    "[0]['layers']['attn_norm']['w']": "2191910df07b1ac8 float32 (2, 256)",
    "[0]['layers']['mlp']['w1']": "5b585eb4d303c7f4 bfloat16 (2, 256, 512)",
    "[0]['layers']['mlp']['w2']": "2387f99041cdf14f bfloat16 (2, 512, 256)",
    "[0]['layers']['mlp']['w3']": "3225a4b7fc4345dc bfloat16 (2, 256, 512)",
    "[0]['layers']['mlp_norm']['w']": "87bdb6acdae23247 float32 (2, 256)",
    "[0]['lm_head']['w']": "d51ecefa69516146 bfloat16 (256, 1024)",
    "[1]['w_wgt']": "67f3d00a3d64b21b float32 (2, 256, 4)",
    "[1]['wk_idx']": "231425a2760018b1 bfloat16 (2, 128, 32)",
    "[1]['wq_idx']": "485b1e14e02aed1f bfloat16 (2, 256, 128)",
}
PARENT_SUMS = {
    ("qwen3-32b-l4", "bfloat16", BIG_SEED): dict(QWEN_BF16, **{
        "[0]['layers']['attn']['k_norm']": "6aa392ea7da23598 float32 (2, 64)",
        "[0]['layers']['attn']['q_norm']": "11560cc114d3c35d float32 (2, 64)",
    }),
    ("qwen2-7b-l7", "bfloat16", BIG_SEED): dict(QWEN_BF16, **{
        "[0]['layers']['attn']['bk']": "aaded1ae90104093 bfloat16 (2, 128)",
        "[0]['layers']['attn']['bq']": "a6c294efe4e285dd bfloat16 (2, 256)",
        "[0]['layers']['attn']['bv']": "01cba288a4a89270 bfloat16 (2, 128)",
    }),
    ("qwen2-7b-l7", "float32", 5): {
        "[0]['embed']['w']": "98a84ee4035c8935 float32 (1024, 256)",
        "[0]['final_norm']['w']": "35c0a38dff09c88f float32 (256,)",
        "[0]['layers']['attn']['bk']": "a85956c5f2d5d890 float32 (2, 128)",
        "[0]['layers']['attn']['bq']": "632ea58e0f5f0fbc float32 (2, 256)",
        "[0]['layers']['attn']['bv']": "f76bfb65615ab2ea float32 (2, 128)",
        "[0]['layers']['attn']['wk']": "7f3522c813d0e265 float32 (2, 256, 128)",
        "[0]['layers']['attn']['wo']": "29280c24b6f464a3 float32 (2, 256, 256)",
        "[0]['layers']['attn']['wq']": "ba570f149f1e3441 float32 (2, 256, 256)",
        "[0]['layers']['attn']['wv']": "b09a842d3b556a9a float32 (2, 256, 128)",
        "[0]['layers']['attn_norm']['w']": "0606e1b628384a73 float32 (2, 256)",
        "[0]['layers']['mlp']['w1']": "1a84d81136c6125f float32 (2, 256, 512)",
        "[0]['layers']['mlp']['w2']": "44a8b054c04a46f2 float32 (2, 512, 256)",
        "[0]['layers']['mlp']['w3']": "69d6355f138d3032 float32 (2, 256, 512)",
        "[0]['layers']['mlp_norm']['w']": "60be1c40ac20a960 float32 (2, 256)",
        "[0]['lm_head']['w']": "6cacbda82edf6288 float32 (256, 1024)",
        "[1]['w_wgt']": "12969a0256aae261 float32 (2, 256, 4)",
        "[1]['wk_idx']": "95f536c049b4e747 float32 (2, 128, 32)",
        "[1]['wq_idx']": "26ec5a2a1aea8196 float32 (2, 256, 128)",
    },
}


def leaf_sums(tree):
    return {jax.tree_util.keystr(path):
            f"{hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]} "
            f"{v.dtype} {tuple(v.shape)}"
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name,dtype,seed", sorted(PARENT_SUMS))
def test_weights_are_bit_identical_to_the_generator_they_replace(
        name, dtype, seed):
    config = tiny_cell(f"{name}.long-decode").config
    config["torch_dtype"] = dtype
    assert leaf_sums(weights.generate(config, seed)) == \
        PARENT_SUMS[(name, dtype, seed)]


def _program_tree(config):
    """The program's own (params, sparse params) shapes for a configuration:
    ``models.model.init_params`` and the sparse method's init, as the engine
    calls them."""
    from repro.configs.base import ArchConfig, MemoryConfig
    from repro.core.methods import get_sparse_method
    from repro.models import model as M

    cfg = spec.arch(config).program_config(config, ArchConfig, MemoryConfig)
    tp = int(config["program"]["tp"])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: M.init_params(cfg, k, tp), key)
    method = config["program"]["method"]
    if method == "none":
        return params, None
    init, _ = get_sparse_method(method)
    mem = cfg.memory.replace(method=method)
    return params, jax.eval_shape(lambda k: init(k, cfg, mem), key)


def _layout(tree):
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)),
                                  tree)


def test_every_arch_module_has_a_configuration():
    named = {spec.load_json(spec.BENCH / "configs" / f"{c}.json")["arch"]
             for c in CONFIGS}
    assert named == set(ARCHS)


@pytest.mark.parametrize("size", ["published", "tiny"])
@pytest.mark.parametrize("name", CONFIGS)
def test_weight_trees_are_the_programs_own(name, size):
    if size == "tiny":
        config = tiny_cell(f"{name}.long-decode").config
    else:
        config = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    drawn = spec.arch(config).shapes(config)
    program = _program_tree(config)
    assert jax.tree_util.tree_structure(drawn) == \
        jax.tree_util.tree_structure(program)
    assert _layout(drawn) == _layout(program)
    if size == "tiny":
        assert _layout(weights.generate(config, 3)) == _layout(program)


@pytest.fixture
def stub(monkeypatch):
    """A test-supplied architecture module that records every call, and a
    configuration file that names it."""
    calls = []
    mod = types.SimpleNamespace(
        draw=lambda key, c: calls.append(("draw", key)) or ("p", None),
        decode_flops=lambda c, n: calls.append(("decode_flops", n)) or 7 * n,
        tiny=lambda c: calls.append("tiny") or dict(c, scaled=True),
    )
    monkeypatch.setitem(readers._MODULES, "archs/stub", mod)
    real = spec.load_json

    def load_json(path):
        out = real(path)
        return dict(out, arch="stub") if path.parent.name == "configs" \
            else out

    monkeypatch.setattr(spec, "load_json", load_json)
    return calls


def test_shared_entry_points_reach_the_module_a_config_names(stub):
    config = spec.load_json(spec.BENCH / "configs" / "qwen3-32b-l4.json")
    assert config["arch"] == "stub"
    assert spec.arch(config) is readers._MODULES["archs/stub"]
    assert weights.generate(config, 11) == ("p", None)
    # decode_mfu: 7 FLOPs a context token at contexts 100 and 300, 2 tokens
    # a second, over a peak of 700 FLOP/s
    ctx = readers.Context(
        cell=types.SimpleNamespace(name="stub.cell", config=config),
        stats={"tokens": 4, "tok_s": 2.0}, trace=None,
        peak={"bf16_flops_per_s": 700.0}, contexts=[100, 300],
        prompt_tokens_traced=0, decode_steps=2)
    assert readers.module("metrics", "decode_mfu").read(ctx) == \
        pytest.approx(100.0 * 1400 * 2 / 700)
    cell = tiny_cell("qwen3-32b-l4.long-decode")
    assert cell.config["scaled"] and cell.config["arch"] == "stub"
    assert [c if isinstance(c, str) else c[0] for c in stub] == [
        "draw", "decode_flops", "decode_flops", "tiny"]
    drawn_with = stub[0][1]
    assert np.array_equal(np.asarray(drawn_with),
                          np.asarray(weights.seed_key(11, salt=1)))
