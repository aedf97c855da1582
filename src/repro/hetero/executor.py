"""Async offload executor: overlaps memory processing with decode (§5).

Two-phase decode with ONE STEP OF LOOKAHEAD, double-buffered across two
JAX devices:

  main device     apply_t (sparse attention over preselected pages + the
                  dense transformer remainder), then ships this step's
                  per-layer queries/keys to the offload device;
  offload device  runs select_{t+1} (prepare/relevancy/retrieve over its
                  incrementally maintained index summary) CONCURRENTLY
                  with apply_t, and ingests step t's keys afterwards.

The selection serving step t therefore saw the queries of step t-2 and the
keys through step t-2 — the stale-lookahead semantics the paper accepts in
exchange for hiding the memory-bound stages entirely (the freshly written
page is force-included at apply time, so recency is never lost).

Scheduling modes share ONE dataflow — every jitted function runs with the
same inputs in the same buffer order — and differ only in barriers:

  "overlap"  no host barriers; JAX async dispatch queues select_{t+1} on
             the offload device while the main device runs apply_t.
  "sync"     block_until_ready between phases: select, apply, ingest run
             serially. This is the honest single-timeline baseline the
             benchmarks compare against.

Because the dataflow is identical, the two modes are bit-identical
(tests/test_hetero.py proves it per method); ``validate=True`` additionally
re-executes every consumed selection synchronously from the pinned inputs
and asserts bitwise equality + stale-index validity, turning any buffer
misuse in the async schedule into an immediate failure.

INVALIDATION IS PER SLOT: pool-membership events (a finished admission, a
drained retrieval splice) mark only the affected slots dirty instead of
discarding the whole pending lookahead. The next decode step still consumes
the overlapped buffer — clean slots keep their lookahead selection, dirty
rows are patched from a fresh selection launched at consumption time. Both
scheduling modes patch at the same host events, so determinism holds, and
retrieval-heavy pools stop paying a cold-start for every splice that lands
(``profiler.lookahead_hits`` vs ``lookahead_cold`` makes the reuse rate
observable; tests/test_hetero_sharded.py pins it).

The selection-state methods (`_launch_select` / `_to_apply` / `_ingest_step`
/ `_patch` / pinned-input plumbing) are the override surface of
``hetero.sharded.ShardedHeteroExecutor``, which runs one summary shard per
offload device and merges per-shard top-k candidates on the main device.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, MemoryConfig
from repro.hetero import policy as hpolicy
from repro.hetero.profiler import HeteroProfiler
from repro.hetero.select import make_offload_select
from repro.hetero.transfer import TransferLedger
from repro.models import model as M

PATCHED = "patched"   # tag of composite pinned-input records
FUSED = "fused"       # tag of pinned inputs produced by a fused window
READY = "ready"       # tag of a selection already merged on the apply side


def _is_ready(handle) -> bool:
    """A fused window returns its exit lookahead as a MERGED pidx resident
    on the apply target — no per-shard ship_up/merge left to do."""
    return isinstance(handle, tuple) and len(handle) == 2 \
        and handle[0] == READY


class HeteroExecutor:
    def __init__(self, cfg: ArchConfig, mem: MemoryConfig, sc,
                 sparse_params, *, mode: str = "overlap",
                 validate: bool = False, devices=None, main_mesh=None):
        assert mode in ("sync", "overlap"), mode
        self.cfg, self.mem, self.sc, self.mode = cfg, mem, sc, mode
        self.validate = validate
        self.main_dev, self.off_dev = devices or hpolicy.pick_devices()
        # main side as a MESH: the apply phase runs sequence-parallel over
        # it (distributed_paged_sparse_decode through the page_attn seam).
        # Everything the apply jit consumes must then be committed to the
        # mesh (replicated) rather than to a single main device — a
        # single-device-committed pidx next to mesh-committed pool buffers
        # is a jit device-assignment conflict — so ship_up targets
        # ``_apply_target`` instead of ``main_dev``.
        self.main_mesh = main_mesh
        self._apply_target = self.main_dev
        if main_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._apply_target = NamedSharding(main_mesh, PartitionSpec())
        self.sel = make_offload_select(sc.method, cfg, mem,
                                       dsa_page=sc.page,
                                       n_slots=sc.n_slots,
                                       max_len=sc.max_len)
        self.plan = hpolicy.plan_stage_placement(cfg, mem, sc.max_len)
        self.ledger = TransferLedger()
        self.profiler = HeteroProfiler(cfg, mem, mode)

        self.sel_buf = None            # selection for the NEXT decode step
        self._sel_inputs = None        # pinned inputs of it (validation)
        self._dirty = np.zeros((sc.n_slots,), bool)  # rows needing a patch
        self._neg_sel = jax.device_put(
            jnp.full((cfg.n_layers, sc.n_slots, self.sel.n_sel), -1,
                     jnp.int32), self._apply_target)
        self._init_offload_state(sparse_params)

        self._span_jits: Dict[Tuple, callable] = {}
        self._apply_jits: Dict[int, callable] = {}
        self._fused_jits: Dict[Tuple, callable] = {}
        self._sp_apply_buf = None      # sparse params on the apply target
        self._select_full_jit = None   # full-window select (fused replay)

    def _init_offload_state(self, sparse_params) -> None:
        """Offload-resident state: method params, index summary, stale
        query buffer — one copy on the single offload device."""
        cfg, sc = self.cfg, self.sc
        self.sp_off = jax.device_put(sparse_params, self.off_dev)
        self.summary = jax.device_put(self.sel.summary_init(), self.off_dev)
        from repro.models import layers as L
        hp = cfg.padded_heads(sc.tp)
        self.q_buf = jax.device_put(
            jnp.zeros((cfg.n_layers, sc.n_slots, hp, cfg.hd),
                      L.dtype_of(cfg)), self.off_dev)
        self._select_jit = jax.jit(self.sel.select)
        self._ingest_jit = jax.jit(self.sel.ingest)

    @property
    def devices(self) -> Tuple:
        """(main, offload) — shared with co-resident services (the
        retrieval subsystem places its corpus/banks on the same offload
        device so one two-device environment hosts both)."""
        return self.main_dev, self.off_dev

    # ------------------------------------------------------------------
    # jit builders
    # ------------------------------------------------------------------

    def _apply_fn(self, n_pages_view: int):
        if n_pages_view not in self._apply_jits:
            cfg, mem, sc, ps = self.cfg, self.mem, self.sc, self.sel.page
            page_attn = None
            if self.main_mesh is not None:
                import functools

                from repro.distributed.topk import \
                    distributed_paged_sparse_decode
                page_attn = functools.partial(
                    distributed_paged_sparse_decode, mesh=self.main_mesh,
                    axis="seq")
            # donation stays on under the mesh: the pool buffers are
            # committed replicated (engine._ensure_pool), so input and
            # output shardings match and XLA can update in place
            def decode_paged_presel(p, tok, kp, vp, table, lengths, live,
                                    pidx):
                return M.decode_step_paged_presel(
                    p, cfg, tok,
                    {"k_pages": kp, "v_pages": vp, "page_table": table,
                     "lengths": lengths},
                    live, pidx, mem, page_size=ps, tp=sc.tp,
                    page_attn=page_attn)

            self._apply_jits[n_pages_view] = jax.jit(
                decode_paged_presel, donate_argnums=(2, 3))
        return self._apply_jits[n_pages_view]

    def _span_fn(self, Bg: int, S: int):
        key = (Bg, S)
        if key not in self._span_jits:
            self._span_jits[key] = jax.jit(self.sel.ingest_span)
        return self._span_jits[key]

    # ------------------------------------------------------------------
    # selection-state primitives (overridden by ShardedHeteroExecutor)
    # ------------------------------------------------------------------

    def _launch_select(self, lengths_np: np.ndarray):
        """Queue a selection on the offload device from the CURRENT summary
        and stale-query buffers -> (handle, pinned inputs)."""
        lengths = jnp.asarray(lengths_np, jnp.int32)
        inputs = (self.summary, self.q_buf, lengths)
        return self._select_jit(self.sp_off, *inputs), inputs

    def _to_apply(self, handle, inputs=None):
        """Ship the consumable selection to the apply side as pidx
        [L, B, n_sel] (the index-only up exchange) — a single main device,
        or replicated over the main mesh when the apply is
        sequence-parallel. A READY handle (fused-window exit lookahead) is
        already merged and resident there."""
        if _is_ready(handle):
            return handle[1]
        return self.ledger.ship_up(handle, self._apply_target)

    def _patch(self, old, fresh, dirty_np: np.ndarray):
        """Row-patch a pending selection handle: dirty slots take the fresh
        selection, clean slots keep their overlapped lookahead."""
        d = jnp.asarray(dirty_np)[None, :, None]
        return jax.tree_util.tree_map(lambda a, b: jnp.where(d, b, a),
                                      old, fresh)

    def _pin_state(self):
        """Pre-step offload state refs for the overlapped lookahead (the
        concurrent select must not see this step's keys/queries)."""
        return self.summary, self.q_buf

    def _ingest_step(self, pinned, q_t, k_t, lengths, live):
        """Ship this step's queries/keys down; fold them into the index
        summary and the stale-query buffer."""
        summary_prev, q_prev = pinned
        q_off = self.ledger.ship_down(q_t, self.off_dev)
        k_off = self.ledger.ship_down(k_t, self.off_dev)
        self.summary = self._ingest_jit(summary_prev, self.sp_off, k_off,
                                        lengths, live)
        self.q_buf = self._blend_q(q_prev, q_off, None, live)
        return self.summary

    def _tick(self) -> None:
        self.ledger.tick()

    # -- pinned-input plumbing (shared with the sharded subclass) -------

    def _raw_lengths(self, inputs):
        return inputs[2]

    def _replay_pidx(self, inputs):
        """Synchronously recompute the FINAL pidx a consumed buffer was
        produced from, recursing through row patches. Recursion runs at the
        pidx level (patch-then-merge == merge-then-patch: the candidate
        merge is per-row) so PATCHED composites can nest FUSED pins — the
        exit lookahead of a fused window, replayed as one full-window
        select from the pinned pre-ingest state on the apply target."""
        if isinstance(inputs, tuple) and inputs and inputs[0] == PATCHED:
            _, old, fresh, dirty = inputs
            return self._patch(self._replay_pidx(old),
                               self._replay_pidx(fresh), dirty)
        if isinstance(inputs, tuple) and inputs and inputs[0] == FUSED:
            _, summary, qbuf, la_len = inputs
            return self._sel_full_jit()(self._sp_apply(), summary, qbuf,
                                        la_len)
        return self._handle_to_pidx(self._select_from_pinned(inputs),
                                    inputs)

    def _select_from_pinned(self, inputs):
        summary, q, lengths = inputs
        return self._select_jit(self.sp_off, summary, q, lengths)

    def _pinned_lengths(self, inputs):
        if isinstance(inputs, tuple) and inputs and inputs[0] == PATCHED:
            _, old, fresh, dirty = inputs
            return jnp.where(jnp.asarray(dirty),
                             self._pinned_lengths(fresh),
                             self._pinned_lengths(old))
        if isinstance(inputs, tuple) and inputs and inputs[0] == FUSED:
            return inputs[3]
        return self._raw_lengths(inputs)

    def _handle_to_pidx(self, handle, inputs):
        """Final selection from a (replayed) handle — identity here, the
        candidate merge for the sharded subclass."""
        return handle

    # ------------------------------------------------------------------
    # admission / prefill hooks (keep the offload index coherent)
    # ------------------------------------------------------------------

    @staticmethod
    def _blend_q(q_buf, q_off, sid, keep_q):
        """Stale-query refresh rule, shared with the sharded subclass:
        ``keep_q=None`` overwrites the seeded slots' rows (admission),
        otherwise only rows whose slot advanced this chunk (``keep_q``
        mask) take the new query."""
        if keep_q is None:
            return q_buf.at[:, sid].set(q_off.astype(q_buf.dtype))
        adv = jnp.asarray(keep_q)
        return jnp.where(adv[None, :, None, None],
                         q_off.astype(q_buf.dtype), q_buf)

    def _reset_slots(self, slot_ids: List[int]) -> None:
        sid = jax.device_put(jnp.asarray(slot_ids, jnp.int32), self.off_dev)
        self.summary = self.sel.reset(self.summary, sid)

    def _seed_span(self, slot_ids, k_masked, start_np, n_valid_np, q_last,
                   *, keep_q: np.ndarray = None) -> None:
        """Ship a prompt/chunk key span down (bulk prefill traffic) and fold
        it into the summary; refresh the stale-query buffer (all rows, or
        only ``keep_q`` rows for chunked spans where some slots idled)."""
        sid = jnp.asarray(slot_ids, jnp.int32)
        k_off = self.ledger.ship_down(k_masked, self.off_dev, bulk=True)
        q_off = self.ledger.ship_down(q_last, self.off_dev, bulk=True)
        Bg, S = k_off.shape[1], k_off.shape[2]
        self.summary = self._span_fn(Bg, S)(
            self.summary, self.sp_off, k_off, sid,
            jnp.asarray(start_np, jnp.int32),
            jnp.asarray(n_valid_np, jnp.int32))
        self.q_buf = self._blend_q(self.q_buf, q_off, sid, keep_q)

    def on_admit(self, slot_ids: List[int], k_masked, true_lens: np.ndarray,
                 q_last) -> None:
        """Bucketed admission: reset the slots' summary rows, bulk-ship the
        prompt keys (the memory moves to the accelerator at prefill, §5.1),
        seed the stale-query buffer with the last-prompt-token queries."""
        self._reset_slots(slot_ids)
        Bg = len(slot_ids)
        self._seed_span(slot_ids, k_masked, np.zeros((Bg,), np.int32),
                        true_lens, q_last)
        self.invalidate(slot_ids)

    def on_admit_slot(self, slot: int) -> None:
        """Chunked admission: clear the slot's rows; keys arrive per chunk."""
        self._reset_slots([slot])
        self._clear_q([slot])
        self.invalidate([slot])

    def _clear_q(self, slot_ids: List[int]) -> None:
        sid = jnp.asarray(slot_ids, jnp.int32)
        self.q_buf = self.q_buf.at[:, sid].set(0.0)

    def on_extend(self, k_span, q_last, start_np: np.ndarray,
                  n_valid_np: np.ndarray, finished: List[int]) -> None:
        """Chunked-prefill chunk landed: ingest the span, refresh the
        stale query of every advancing slot. Counted as bulk prefill
        traffic — it is admission-time memory shipping, not the per-step
        decode exchange. ``finished`` lists the slots whose payload
        (admission prompt or retrieval splice) completed this step — only
        THEIR lookahead rows go dirty."""
        Bg = k_span.shape[1]
        self._seed_span(list(range(Bg)), k_span, start_np, n_valid_np,
                        q_last, keep_q=n_valid_np > 0)
        if finished:
            self.invalidate(finished)

    def invalidate(self, slots: List[int] = None) -> None:
        """``slots=None`` drops the whole pending lookahead (the offload
        window itself changed — dynamic fallback); a slot list marks only
        those rows dirty: the next decode step patches them from a fresh
        selection and keeps every clean slot's overlapped lookahead. Both
        scheduling modes invalidate at the same host events, so determinism
        holds."""
        if slots is None:
            self.sel_buf = None
            self._sel_inputs = None
            self._dirty[:] = False
        else:
            self._dirty[list(slots)] = True

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _resolve_sel(self, lengths_np: np.ndarray, live_np: np.ndarray,
                     *, sync: bool):
        """Resolve the selection consumed by the NEXT apply: cold-start
        when no lookahead is pending, otherwise reuse it, patching the rows
        of slots whose membership changed. Shared by the stepped schedule
        and the fused-window entry (bit-identical resolution either way).
        Returns (pinned_inputs, pidx, select_wall_s)."""
        t_sel = 0.0
        if self.sel_buf is None:                          # cold start
            t0 = time.perf_counter()
            self.sel_buf, self._sel_inputs = \
                self._launch_select(lengths_np)
            self._dirty &= ~live_np
            self.profiler.lookahead_cold += 1
            if sync:
                jax.block_until_ready(self.sel_buf)
                t_sel += time.perf_counter() - t0
        else:
            self.profiler.lookahead_hits += 1
            patch_rows = self._dirty & live_np
            if patch_rows.any():
                # membership changed for these slots only: patch their
                # rows from a fresh selection, keep the overlapped
                # lookahead of every clean slot
                t0 = time.perf_counter()
                fresh, fresh_inputs = self._launch_select(lengths_np)
                if _is_ready(self.sel_buf):
                    # fused exit lookahead is already a merged pidx: patch
                    # at the pidx level (merge is per-row, so this equals
                    # patching the handles first)
                    self.sel_buf = (READY, self._patch(
                        self.sel_buf[1],
                        self._to_apply(fresh, fresh_inputs), patch_rows))
                else:
                    self.sel_buf = self._patch(self.sel_buf, fresh,
                                               patch_rows)
                self._sel_inputs = (PATCHED, self._sel_inputs,
                                    fresh_inputs, patch_rows.copy())
                self._dirty &= ~patch_rows
                self.profiler.lookahead_patched += 1
                if sync:
                    jax.block_until_ready(self.sel_buf)
                    t_sel += time.perf_counter() - t0
        return self._sel_inputs, self._to_apply(self.sel_buf), t_sel

    def decode(self, params, tok, pool_device: Dict, table,
               lengths_np: np.ndarray, live_np: np.ndarray):
        """One pooled decode step. Returns (logits, {k_pages, v_pages})."""
        sync = self.mode == "sync"
        t_step = time.perf_counter()
        lengths = jnp.asarray(lengths_np, jnp.int32)
        live = jnp.asarray(live_np)
        context = int(lengths_np.max()) + 1 if live_np.any() else 1
        offloaded = hpolicy.dynamic_mode(context, self.mem) == "offload"

        t_sel = 0.0
        if offloaded:
            pidx_inputs, pidx, t_sel = self._resolve_sel(lengths_np,
                                                         live_np, sync=sync)
        else:
            # dynamic fallback: single-device execution, no offload work
            pidx_inputs, pidx = None, self._neg_sel
            self.invalidate()

        # pin the pre-step offload state for the lookahead (the overlapped
        # select must not see this step's keys/queries)
        pinned = self._pin_state()
        next_sel = next_inputs = None
        if offloaded and not sync:
            # queue select_{t+1} BEFORE apply_t: JAX async dispatch runs it
            # on the offload device while the main device decodes
            next_sel, next_inputs = self._launch_select(
                lengths_np + live_np)

        if sync:
            jax.block_until_ready(pidx)
        t0 = time.perf_counter()
        logits, pool, q_t, k_t = self._apply_fn(table.shape[1])(
            params, tok, pool_device["k_pages"], pool_device["v_pages"],
            table, lengths, live, pidx)
        if sync:
            jax.block_until_ready(logits)
            t_apply = time.perf_counter() - t0
        else:
            t_apply = None

        if offloaded and sync:
            t0 = time.perf_counter()
            next_sel, next_inputs = self._launch_select(
                lengths_np + live_np)
            jax.block_until_ready(next_sel)
            t_sel += time.perf_counter() - t0

        # ship this step's queries/keys down; ingest into the index summary
        # (also during local fallback — the index must stay coherent for
        # when the context re-enters the offload window)
        self._tick()
        t0 = time.perf_counter()
        summary_ref = self._ingest_step(pinned, q_t, k_t, lengths, live)
        if sync:
            jax.block_until_ready(summary_ref)
            if offloaded:   # local-fallback ingest is pool upkeep — not a
                t_sel += time.perf_counter() - t0   # select-phase cost
        self.sel_buf, self._sel_inputs = next_sel, next_inputs

        if self.validate and offloaded and pidx_inputs is not None:
            self._validate(pidx, pidx_inputs)
        self.profiler.record_step(
            int(live_np.sum()), context, time.perf_counter() - t_step,
            select_s=t_sel if sync else None, apply_s=t_apply,
            offloaded=offloaded)
        return logits, pool

    # ------------------------------------------------------------------
    # fused multi-step windows (serving.fused)
    # ------------------------------------------------------------------

    def _sp_apply(self):
        """Method params on the apply target (the in-scan select/ingest
        run there for the duration of a fused window)."""
        if self._sp_apply_buf is None:
            src = self.sp_off if hasattr(self, "sp_off") else self.sp_offs[0]
            self._sp_apply_buf = jax.device_put(src, self._apply_target)
        return self._sp_apply_buf

    def _sel_full_jit(self):
        """Full-window select (device-agnostic jit) — the in-scan selection
        and the FUSED-pin validation replay both use it."""
        if self._select_full_jit is None:
            self._select_full_jit = jax.jit(self.sel.select)
        return self._select_full_jit

    def _fused_state_up(self):
        """Ship the offload-resident index state to the apply target for a
        fused window (accounted as bulk traffic — a state migration, not
        the per-step exchange). Returns (summary, q_buf)."""
        summary = self.ledger.ship_down(self.summary, self._apply_target,
                                        bulk=True)
        qbuf = self.ledger.ship_down(self.q_buf, self._apply_target,
                                     bulk=True)
        return summary, qbuf

    def _fused_state_down(self, summary, qbuf):
        """Restore the post-window index state to the offload device(s) so
        the stepped schedule can resume seamlessly."""
        self.summary = self.ledger.ship_down(summary, self.off_dev,
                                             bulk=True)
        self.q_buf = self.ledger.ship_down(qbuf, self.off_dev, bulk=True)

    def _fused_fn(self, n_pages_view: int, K: int, trigger):
        key = (n_pages_view, K, trigger)
        if key not in self._fused_jits:
            page_attn = None
            if self.main_mesh is not None:
                import functools

                from repro.distributed.topk import \
                    distributed_paged_sparse_decode
                page_attn = functools.partial(
                    distributed_paged_sparse_decode, mesh=self.main_mesh,
                    axis="seq")
            from repro.serving.fused import make_fused_presel
            fn = make_fused_presel(self.cfg, self.mem, self.sc, self.sel,
                                   K=K, trigger=trigger,
                                   page_attn=page_attn)
            self._fused_jits[key] = jax.jit(fn, donate_argnums=(3, 4))
        return self._fused_jits[key]

    def decode_fused(self, params, tok_np, pool_device: Dict, table,
                     lengths_np: np.ndarray, live_np: np.ndarray, K: int,
                     *, gen_np, maxnew_np, armed_np, arm_after_np, trigger):
        """Up to K pooled decode steps in ONE jitted scan: the two-phase
        apply + the lookahead double-buffer run entirely on the apply
        target, with early exit (masked iterations) when a slot finishes
        or a retrieval trigger fires. The window enters from the SAME
        resolved selection the stepped schedule would consume and exits
        with the pending lookahead reinstalled (READY pidx + FUSED pins),
        so stepped and fused schedules interleave bit-identically."""
        sync = self.mode == "sync"
        t_step = time.perf_counter()
        context = int(lengths_np.max()) + 1 if live_np.any() else 1
        offloaded = hpolicy.dynamic_mode(context, self.mem) == "offload"
        if offloaded:
            pidx_inputs, pidx, _ = self._resolve_sel(lengths_np, live_np,
                                                     sync=sync)
        else:
            pidx_inputs, pidx = None, self._neg_sel
            self.invalidate()
        summary0, qbuf0 = self._fused_state_up()
        outs = self._fused_fn(table.shape[1], K, trigger)(
            params, self._sp_apply(), jnp.asarray(tok_np),
            pool_device["k_pages"], pool_device["v_pages"], table,
            jnp.asarray(lengths_np, jnp.int32), jnp.asarray(live_np),
            jnp.asarray(gen_np, jnp.int32), jnp.asarray(maxnew_np,
                                                        jnp.int32),
            pidx, jnp.asarray(bool(offloaded)), summary0, qbuf0,
            jnp.asarray(armed_np), jnp.asarray(arm_after_np, jnp.int32))
        if sync:
            jax.block_until_ready(outs)
        if self.validate and offloaded and pidx_inputs is not None:
            # entry selection replayed exactly as in the stepped schedule;
            # the exit lookahead is validated at its consumption (FUSED
            # pins), mid-window selections by the fused-vs-stepped oracle
            self._validate(pidx, pidx_inputs)
        with TraceAnnotation("engine.decode.sync"):
            nsteps = int(jax.block_until_ready(outs["nsteps"]))
            emits_np = np.asarray(outs["emits"])
            offl_np = np.asarray(outs["offl"])[:nsteps]
        for _ in range(nsteps):
            self._tick()
        self._fused_state_down(outs["summary"], outs["qbuf"])
        if offl_np.size and not offl_np.all():
            # the stepped schedule calls invalidate() on every fallback
            # step, which clears the dirty rows — replicate that so a
            # pre-window dirty bit cannot outlive a mid-window fallback
            self._dirty[:] = False
        if bool(np.asarray(outs["sel_ok"])):
            self.sel_buf = (READY, outs["sel"])
            self._sel_inputs = (FUSED, outs["prev_summary"],
                                outs["prev_q"], outs["prev_len"])
        else:
            self.invalidate()
        self.profiler.record_fused(
            nsteps, int((emits_np[:nsteps] >= 0).sum()), context,
            time.perf_counter() - t_step,
            offload_steps=int(offl_np.sum()),
            local_steps=nsteps - int(offl_np.sum()))
        return {"k_pages": outs["k_pages"], "v_pages": outs["v_pages"],
                "pending": np.asarray(outs["pending"]), "nsteps": nsteps,
                "emits": emits_np, "fired": np.asarray(outs["fired"])}

    # ------------------------------------------------------------------
    # validation mode
    # ------------------------------------------------------------------

    def _validate(self, pidx, inputs) -> None:
        """Re-run the consumed selection synchronously from its pinned
        inputs: async result must be bit-identical, and every index must be
        a valid stale pick (inside the live region it was computed from)."""
        ref = jax.block_until_ready(self._replay_pidx(inputs))
        got = np.asarray(jax.block_until_ready(pidx))
        if not np.array_equal(got, np.asarray(ref)):
            raise AssertionError(
                "overlapped selection diverged from its synchronous replay")
        lens = np.asarray(self._pinned_lengths(inputs))
        sel_ok = (got == -1) | ((got >= 0)
                                & (got * self.sel.page < lens[None, :, None]))
        if not sel_ok.all():
            raise AssertionError("stale lookahead produced out-of-window "
                                 "page indices")

    # ------------------------------------------------------------------

    def report(self) -> Dict:
        d = self.profiler.summary(self.ledger, cfg=self.cfg,
                                  n_sel=self.sel.n_sel, page=self.sel.page,
                                  batch=self.sc.n_slots)
        d["devices"] = {"main": str(self.main_dev),
                        "offload": str(self.off_dev),
                        "distinct": self.main_dev != self.off_dev}
        if self.main_mesh is not None:
            d["devices"]["main_mesh"] = [
                str(x) for x in self.main_mesh.devices.flat]
        d["plan"] = {"stages": dict(self.plan.stages),
                     "offloaded": list(self.plan.offloaded())}
        return d
