"""Training loop substrate: jit'd train_step with remat, microbatch gradient
accumulation, optional compressed cross-pod gradient sync (error feedback),
and checkpoint/restart integration.

Fault tolerance: the Trainer saves every ``ckpt_every`` steps (atomic), tags
the data-stream position in the manifest, restores the latest checkpoint on
construction, and exposes ``emergency_save`` for the launcher's signal
handler (straggler/preemption path — distributed/elastic.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import checkpoint as ckpt
from repro.distributed.collectives import compressed_grads_with_feedback
from repro.models import model as M
from repro.train.optimizer import OptConfig, OptState, adamw_update, init_opt_state


@dataclasses.dataclass
class TrainConfig:
    opt: OptConfig = dataclasses.field(default_factory=OptConfig)
    accum: int = 1                 # microbatch gradient accumulation
    compress: str = "none"         # none | bf16 | int8 (cross-pod sync)
    remat: bool = True
    ckpt_dir: str = ""
    ckpt_every: int = 100
    tp: int = 16


def make_train_step(cfg: ArchConfig, tc: TrainConfig,
                    mesh: Optional[Mesh] = None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    With accum > 1 the batch leading dim must be [accum, mb, S]; gradients
    average over microbatches inside a scan (bounds activation memory to one
    microbatch at a time).
    """

    def loss_fn(p, b):
        return M.train_loss(p, cfg, b, remat=tc.remat, tp=tc.tp)

    def grads_of(params, batch):
        if tc.accum <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def micro(carry, mb):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_acc + l / tc.accum,
                    jax.tree.map(lambda a, b: a + b / tc.accum, g_acc, g)), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), zero), batch)
        return loss, grads

    pod_sync = (tc.compress != "none" and mesh is not None
                and "pod" in mesh.shape and mesh.shape["pod"] > 1)

    def step_fn(params, opt_state: OptState, batch):
        loss, grads = grads_of(params, batch)
        residual = opt_state.residual
        if pod_sync:
            # explicit compressed cross-pod all-reduce (bf16/int8 wire) with
            # error feedback; within-pod reduction stays implicit (GSPMD).
            grads, residual = compressed_grads_with_feedback(
                grads, residual, tc.compress)
            if tc.compress == "bf16":
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.bfloat16).astype(jnp.float32), grads)
        new_params, new_state, stats = adamw_update(
            grads, opt_state._replace(residual=residual), params, tc.opt)
        stats["loss"] = loss
        return new_params, new_state, stats

    return jax.jit(step_fn, donate_argnums=(0, 1))


class Trainer:
    def __init__(self, cfg: ArchConfig, tc: TrainConfig, params,
                 mesh: Optional[Mesh] = None):
        self.cfg, self.tc = cfg, tc
        self.params = params
        self.opt_state = init_opt_state(params, tc.compress)
        self.step_fn = make_train_step(cfg, tc, mesh)
        self.step = 0
        self.mesh = mesh
        if tc.ckpt_dir:
            last = ckpt.latest_step(tc.ckpt_dir)
            if last is not None:
                self.restore(last)

    def train_step(self, batch) -> Dict[str, float]:
        self.params, self.opt_state, stats = self.step_fn(
            self.params, self.opt_state, batch)
        self.step += 1
        if self.tc.ckpt_dir and self.step % self.tc.ckpt_every == 0:
            self.save()
        return {k: float(v) for k, v in stats.items()}

    def save(self):
        ckpt.save(self.tc.ckpt_dir, self.step,
                  {"params": self.params, "m": self.opt_state.m,
                   "v": self.opt_state.v},
                  extra={"opt_step": int(self.opt_state.step)})

    def emergency_save(self):
        """Preemption/straggler-eviction hook (atomic, safe to call anytime)."""
        if self.tc.ckpt_dir:
            self.save()

    def restore(self, step: int):
        like = {"params": self.params, "m": self.opt_state.m,
                "v": self.opt_state.v}
        tree = ckpt.restore(self.tc.ckpt_dir, step, like)
        self.params = tree["params"]
        man = ckpt.read_manifest(self.tc.ckpt_dir, step)
        self.opt_state = self.opt_state._replace(
            m=tree["m"], v=tree["v"],
            step=jnp.asarray(man["extra"].get("opt_step", step), jnp.int32))
        self.step = step
