import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (architecture x input-shape x
mesh) cell on placeholder devices, print memory/cost analysis, extract
roofline terms, and cache everything to experiments/dryrun/*.json.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
[--arch A] [--shape S] [--multi-pod] [--variant baseline]``. The XLA_FLAGS
line above executes before any jax import — nothing else in the repo sets it.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_arch
from repro.configs.base import ShapeConfig
from repro.kernels import ops
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, pick_accum
from repro.models import model as M
from repro.train.optimizer import OptConfig, init_opt_state, adamw_update

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _cell_path(arch: str, shape: str, multi_pod: bool, variant: str) -> str:
    mesh = "pod2x16x16" if multi_pod else "16x16"
    os.makedirs(OUT_DIR, exist_ok=True)
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh}__{variant}.json")


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_train_step(cfg, shape, mesh, tp, variant: str):
    """Full train step: fwd + bwd (remat) + AdamW update."""
    data_par = mesh.devices.size // tp
    accum = pick_accum(cfg, shape, data_par)
    from repro.models import moe as _moe
    from repro.models import model as _model
    _moe.set_ep_constraint(None)      # reset variant-gated flags (cells run
    _model.set_sp_residual(None)      # back-to-back in one process)
    if variant.startswith("optimized") and cfg.n_experts \
            and cfg.n_experts % tp == 0:
        _moe.set_ep_constraint("model")  # §Perf: shard-local EP dispatch
    if "sp" in variant.split("-") and shape.seq_len % tp == 0:
        from jax.sharding import PartitionSpec as P
        da = tuple(a for a in mesh.axis_names if a not in ("model",))
        _model.set_sp_residual(P(da, "model", None))  # §Perf: Megatron-SP

    def loss_fn(p, batch):
        return M.train_loss(p, cfg, batch, remat=True, tp=tp)

    def step(params, m, v, batch):
        if accum > 1:
            B = batch["tokens"].shape[0]
            mb = B // accum
            batch = {k: x.reshape((accum, mb) + x.shape[1:])
                     if k != "positions3" else
                     jnp.moveaxis(x.reshape((3, accum, mb) + x.shape[2:]), 0, 1)
                     for k, x in batch.items()}

            def micro(carry, b):
                l_acc, g_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, b)
                return (l_acc + l / accum,
                        jax.tree.map(lambda a, x: a + x / accum, g_acc, g)), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), zero), batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        from repro.train.optimizer import OptState
        state = OptState(jnp.ones((), jnp.int32), m, v, None)
        new_p, new_s, _ = adamw_update(grads, state, params, OptConfig())
        return loss, new_p, new_s.m, new_s.v

    return step, accum


def build_prefill_step(cfg, shape, tp):
    def step(params, batch):
        logits, caches = M.prefill(
            params, cfg, batch["tokens"], max_len=shape.seq_len,
            positions3=batch.get("positions3"),
            img_embeds=batch.get("img_embeds"), remat=True, tp=tp)
        return logits, caches

    return step


def build_decode_step(cfg, shape, mesh, tp, variant: str):
    """serve_step: ONE new token against a seq_len KV cache (paper pipeline
    active for attention archs — long contexts run sparse, per placement)."""
    sparse_fn = None
    stateful = False
    if cfg.family != "ssm" and shape.seq_len >= cfg.memory.min_context:
        from repro.core.methods import get_sparse_method
        _, mk = get_sparse_method(cfg.memory.method)
        big_batch = shape.global_batch >= mesh.devices.size // tp
        axis = "model" if big_batch else tuple(
            a for a in mesh.axis_names if a != "model") + ("model",)
        batch_axis = (tuple(a for a in mesh.axis_names if a != "model")
                      if big_batch else None)
        if variant == "optimized-spdecode":
            from repro.core.methods.dsa import make_sparse_fn_distributed
            sparse_fn = make_sparse_fn_distributed(
                cfg, cfg.memory, mesh, axis=axis, batch_axis=batch_axis,
                tp=tp, page=64)
        elif variant == "optimized-idxcache":
            from repro.core.methods.dsa import make_sparse_fn_cached
            sparse_fn = make_sparse_fn_cached(
                cfg, cfg.memory, mesh, axis=axis, batch_axis=batch_axis,
                tp=tp, page=64)
            stateful = True
        else:
            kw = {"page": 64} if cfg.memory.method == "dsa" else {}
            sparse_fn = mk(cfg, cfg.memory, tp=tp, **kw)

    def step(params, token, caches, sparse_params):
        return M.decode_step(params, cfg, token, caches, tp=tp,
                             sparse_fn=sparse_fn, sparse_params=sparse_params,
                             sparse_stateful=stateful)

    return step, stateful


# ---------------------------------------------------------------------------
# dry-run one cell
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "baseline", force: bool = False) -> Dict:
    path = _cell_path(arch, shape_name, multi_pod, variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh.shape["model"]
    chips = mesh.devices.size
    ops.use_pallas(False)  # dry-run lowers the XLA reference path (DESIGN §6)

    t0 = time.time()
    rec: Dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "variant": variant, "ok": False}
    try:
        # optimized decode keeps weights TP-resident (no FSDP step gathers)
        fsdp = (False if (variant.startswith("optimized")
                          and shape.kind == "decode") else None)
        specs = input_specs(cfg, shape, mesh, tp=tp, fsdp=fsdp)
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                step, accum = build_train_step(cfg, shape, mesh, tp, variant)
                rec["accum"] = accum
                opt_sds = jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                    specs["params"])
                fn = jax.jit(
                    step,
                    in_shardings=(specs["params_sharding"],
                                  specs["params_sharding"],
                                  specs["params_sharding"],
                                  specs["batch_sharding"]),
                    donate_argnums=(0, 1, 2),
                )
                lowered = fn.lower(specs["params"], opt_sds, opt_sds,
                                   specs["batch"])
            elif shape.kind == "prefill":
                step = build_prefill_step(cfg, shape, tp)
                fn = jax.jit(step, in_shardings=(specs["params_sharding"],
                                                 specs["batch_sharding"]))
                lowered = fn.lower(specs["params"], specs["batch"])
            else:
                step, stateful = build_decode_step(cfg, shape, mesh, tp,
                                                   variant)
                sp = specs.get("sparse_params")
                sp_shard = specs.get("sparse_sharding")
                if stateful and sp is not None:
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    from repro.core.methods.dsa import idx_cache_init
                    kidx = jax.eval_shape(
                        lambda: idx_cache_init(cfg, cfg.memory,
                                               shape.global_batch,
                                               shape.seq_len, page=64))
                    cspec = jax.tree.leaves(
                        {"k": specs["caches_sharding"]["k"]})[0].spec
                    # pooled index: [L, B, n_pages, di] — batch/seq like KV
                    kidx_shard = NamedSharding(
                        mesh, P(None, cspec[1], cspec[2], None))
                    sp = {"p": sp, "kidx_sum": kidx}
                    sp_shard = {"p": sp_shard, "kidx_sum": kidx_shard}
                shardings = (specs["params_sharding"],
                             specs["batch_sharding"]["token"],
                             specs["caches_sharding"], sp_shard)
                fn = jax.jit(step, in_shardings=shardings,
                             donate_argnums=(2, 3) if stateful else (2,))
                lowered = fn.lower(specs["params"], specs["batch"]["token"],
                                   specs["caches"], sp)
            rec["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
        hlo = compiled.as_text()
        rl = RL.from_compiled(compiled, hlo, chips,
                              RL.model_flops_for(cfg, shape))
        rec["roofline"] = rl.to_dict()
        rec["roofline"]["ideal_memory_s"] = (
            RL.ideal_memory_bytes(cfg, shape, chips) / RL.HBM_BW)
        rec["ok"] = True
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']} {variant}: "
              f"compute={rl.compute_s*1e3:.2f}ms memory={rl.memory_s*1e3:.2f}ms "
              f"collective={rl.collective_s*1e3:.2f}ms "
              f"bottleneck={rl.bottleneck} mfu={rl.mfu:.3f} "
              f"(lower {rec['lower_s']:.0f}s compile {rec['compile_s']:.0f}s)")
        print(f"  memory_analysis: { {k: f'{v/2**30:.2f}GiB' for k, v in rec['memory_analysis'].items()} }")
        print(f"  cost_analysis: flops/dev={rl.flops:.3e} bytes/dev={rl.hbm_bytes:.3e}")
        print(f"  collectives: { {k: f'{v/2**20:.1f}MiB' for k, v in rl.per_collective.items() if v} }")
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']} FAILED: {rec['error'][:300]}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_fail = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mp, args.variant, args.force)
                n_ok += rec.get("ok", False)
                n_fail += not rec.get("ok", False)
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
