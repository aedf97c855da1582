"""Batched decode engine with the memory pipeline as a first-class feature.

* builds jitted prefill / decode steps (optionally on separate role meshes —
  the paper's prefill/decode disaggregation, Fig. 6b),
* wires the sparse-attention memory pipeline into decode via the placement
  policy: a traced lax.cond implements the paper's DYNAMIC FALLBACK — dense
  attention below ``min_context`` and above ``fallback_context``, the fused
  sparse pipeline in between (for pooled decode the cond is decided on the
  max length over live slots; masks inside the branch stay per-slot),
* continuous batching runs on a PAGED KV pool with PER-SLOT lengths: slots
  allocate/free fixed-size pages at admit/release (HBM scales with live
  tokens, not ``n_slots * max_len``), every slot decodes at its own RoPE
  position / cache offset / attention mask, admission prefill is batched
  over length buckets with a small set of pre-jitted shapes, and long
  prompts prefill in fixed-size chunks interleaved with decode steps,
* the legacy dense ``n_slots x max_len`` pool with the shared
  ``lengths.max()`` watermark is kept behind ``ServeConfig(paged=False)`` as
  the benchmark baseline (bench_batch_scaling old-vs-new comparison),
* ``ServeConfig(offload_cfg=OffloadConfig(...))`` routes the
  memory-processing stages through
  the heterogeneous offload executor (src/repro/hetero): lookahead
  selection on a second device, overlapped with decode, exchanging only
  page indices — the paper's §5 system emulated on JAX devices.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, MemoryConfig
from repro.core import placement
from repro.core.methods import get_sparse_method
from repro.models import model as M
from repro.serving.api import Request, ResponseHandle
from repro.serving.events import StepEvents
from repro.serving.kv_cache import PagedKVPool, SlotManager

POOL_FAMILIES = ("dense", "moe", "audio", "vlm")


# Host spans of one serving turn (``jax.profiler.TraceAnnotation``, on the
# device trace's clock; well under a microsecond each with the profiler off):
#   engine.poll                the whole turn, holding
#     engine.admit             queue admission (and its bucketed prefill)
#     engine.prefill           one chunk of chunked prefill
#     engine.decode.launch     page-table view, argument upload, the jit call
#     engine.decode.sync       the step's tokens pulled to the host
#     engine.decode.emit       emissions, slot and page bookkeeping, retrieval
#     engine.dispatch          tokens handed to their ResponseHandles


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclasses.dataclass
class OffloadConfig:
    """Heterogeneous-offload topology as one nested config
    (``ServeConfig(offload_cfg=OffloadConfig(...))``).

    mode       "off" = inline sparse pipeline; "sync" = two-phase
               select->apply on the offload device but serialized;
               "overlap" = double-buffered lookahead selection overlapped
               with decode (the paper's heterogeneous execution).
    validate   replay each consumed selection and bit-check it.
    shards     >1 = one offload device per contiguous KV-sequence shard
               (hetero.sharded), index-only candidate merge.
    main_mesh  >1 = N-device main mesh running the apply phase
               sequence-parallel. Composes with ``shards``.
    """
    mode: str = "off"
    validate: bool = False
    shards: int = 1
    main_mesh: int = 1

    def __post_init__(self):
        if self.mode not in ("off", "sync", "overlap"):
            raise ValueError(
                f"offload mode must be 'off', 'sync' or 'overlap', "
                f"got {self.mode!r}")
        if self.shards < 1:
            raise ValueError(f"offload shards must be >= 1, "
                             f"got {self.shards}")
        if self.main_mesh < 1:
            raise ValueError(f"main_mesh must be >= 1, got {self.main_mesh}")
        if self.mode == "off" and (self.shards > 1 or self.main_mesh > 1):
            raise ValueError("shards/main_mesh need "
                             "OffloadConfig(mode='sync'|'overlap')")


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 4096
    n_slots: int = 8
    method: str = "none"       # none | dsa | seer | lserve
    tp: int = 16
    page: int = 16             # dsa micro-page size
    greedy: bool = True
    # --- paged continuous batching ---
    paged: bool = True         # False = legacy dense pool + shared watermark
    kv_page_size: int = 16     # physical KV page (pool granule)
    pool_pages: int = 0        # 0 = full backing; else arena size (oversubscribe)
    prefill_chunk: int = 128   # chunk span for chunked prefill
    chunk_threshold: int = 512 # prompts longer than this prefill in chunks
    view_buckets: bool = True  # size the decode view by max live length
                               # (pow2-bucketed) instead of max_len
    # --- heterogeneous offload (src/repro/hetero) ---
    # "off" = inline sparse pipeline; "sync" = two-phase select->apply on
    # the offload device but serialized (validation/benchmark baseline);
    # "overlap" = double-buffered lookahead selection overlapped with
    # decode (the paper's heterogeneous execution). Requires paged=True and
    # a sparse method (dsa | seer | lserve).
    offload: str = "off"
    offload_validate: bool = False  # replay each consumed selection + check
    # >1 shards the offload side over one device per KV-sequence shard
    # (hetero.sharded.ShardedHeteroExecutor): each shard keeps the page
    # summaries of its contiguous token window and ships only top-k
    # (vals, idx) candidates; the merged selection is bit-identical to
    # offload_shards=1 in both scheduling modes.
    offload_shards: int = 1
    # >1 builds an N-device MAIN mesh and runs the APPLY phase
    # sequence-parallel over it: the paged-pool view is sharded over the
    # sequence axis inside ``decode_step_paged_presel``'s page_attn seam
    # (distributed_paged_sparse_decode — both cond branches, sparse apply
    # AND dense fallback), and only (out, lse) pairs cross the mesh.
    # Composes with offload_shards=M: M selection shards + N apply shards
    # scale independently (paper Fig. 6a end to end). Requires a hetero
    # offload mode — the apply phase exists as a separate stage only under
    # the two-phase select->apply split.
    main_mesh: int = 1
    # --- retrieval subsystem (src/repro/retrieval) ---
    # A repro.retrieval.RetrievalConfig enables the document-memory service:
    # per-slot FLARE/DRAGIN triggers over the pooled decode logits, dynamic
    # RAG doc splices / MaC memory-bank embedding splices through the
    # chunked-extend path, inline or on the offload device (sync/overlap).
    # Composes with ``offload`` — retrieval slots share the pool with
    # sparse-attention slots. Requires paged=True.
    retrieval: Optional[object] = None
    # --- redesigned stepping/config surface -----------------------------
    # ``offload_cfg`` is the first-class surface for the offload topology;
    # the flat ``offload`` / ``offload_validate`` / ``offload_shards`` /
    # ``main_mesh`` fields above are kept as DEPRECATED aliases that now
    # emit a ``DeprecationWarning`` when set explicitly. Flat non-default
    # values win (pre-existing call sites behave unchanged); otherwise the
    # nested config populates the flat fields. The two surfaces stay in
    # sync through ``dataclasses.replace`` on either (a coherent
    # flat == nested replace does not warn).
    offload_cfg: Optional[OffloadConfig] = None
    # decode steps fused into one on-device lax.scan per host dispatch
    # (serving/fused.py): K>1 trades per-token host round-trips for one
    # dispatch per window, with masked early exit back to the host when a
    # slot finishes or a retrieval trigger fires. 1 = stepped host loop.
    fused_steps: int = 1

    _FLAT_OFFLOAD_DEFAULT = ("off", False, 1, 1)

    def __post_init__(self):
        flat = (self.offload, self.offload_validate, self.offload_shards,
                self.main_mesh)
        if self.offload_cfg is not None and flat == self._FLAT_OFFLOAD_DEFAULT:
            oc = self.offload_cfg
            self.offload = oc.mode
            self.offload_validate = oc.validate
            self.offload_shards = oc.shards
            self.main_mesh = oc.main_mesh
        else:
            nested = None if self.offload_cfg is None else (
                self.offload_cfg.mode, self.offload_cfg.validate,
                self.offload_cfg.shards, self.offload_cfg.main_mesh)
            if flat != self._FLAT_OFFLOAD_DEFAULT and nested != flat:
                # an explicitly-set flat kwarg (not the mirror of a
                # coherent nested config carried through replace())
                warnings.warn(
                    "flat ServeConfig offload kwargs (offload=, "
                    "offload_validate=, offload_shards=, main_mesh=) are "
                    "deprecated; use ServeConfig(offload_cfg="
                    "OffloadConfig(mode=..., validate=..., shards=..., "
                    "main_mesh=...))", DeprecationWarning, stacklevel=3)
            # (re)derive the nested view — also validates the flat fields
            self.offload_cfg = OffloadConfig(
                mode=self.offload, validate=self.offload_validate,
                shards=self.offload_shards, main_mesh=self.main_mesh)
        if self.fused_steps < 1:
            raise ValueError(
                f"fused_steps must be >= 1, got {self.fused_steps}")
        if self.fused_steps > 1 and not self.paged:
            raise ValueError("fused_steps > 1 fuses the PAGED decode loop "
                             "(ServeConfig(paged=True))")


class Engine:
    def __init__(self, cfg: ArchConfig, params, sc: ServeConfig,
                 key=None, mem: Optional[MemoryConfig] = None,
                 devices=None):
        self.cfg = cfg
        # ``devices``: pin this engine to a device GROUP (a fleet replica's
        # slice of the machine, hetero.policy.pick_devices_replicas).
        # Committing the params to the group's first device pins every jit
        # dispatch there; the remaining devices serve the offload/retrieval
        # side. None = the process-default device (single-engine behavior,
        # unchanged).
        self.devices = tuple(devices) if devices else None
        if self.devices is not None:
            params = jax.device_put(params, self.devices[0])
        self.params = params
        self.mem = mem or cfg.memory.replace(method=sc.method)
        # the paged pipeline needs the cache length page-aligned; the paged
        # pool additionally needs it kv-page aligned
        gran = max(sc.page, self.mem.block_size,
                   self.mem.block_size * self.mem.pages_per_physical
                   if sc.method == "lserve" else 1)
        if sc.method == "none":
            gran = 1
        gran = math.lcm(gran, sc.kv_page_size if sc.paged else 1)
        # sharded offload: every shard window must cover a whole number of
        # selection pages AND kv pages, so align max_len to gran * shards
        gran *= max(sc.offload_shards, 1)
        # main-mesh apply: pow2-bucketed decode views are multiples of the
        # granule, so folding the mesh size in keeps every bucket length
        # divisible by n_shards * page_size — the sequence-parallel apply's
        # shard-granularity contract (distributed_paged_sparse_decode
        # asserts it; an unaligned bucket used to trip it)
        gran *= max(sc.main_mesh, 1)
        if sc.max_len % gran:
            sc = dataclasses.replace(
                sc, max_len=((sc.max_len + gran - 1) // gran) * gran)
        self.sc = sc
        self._gran = gran
        if cfg.is_mla:
            # latent attention serves on the inline paged path only, with
            # its own (published) indexer
            assert sc.method == "dsa", sc.method
            assert sc.paged and sc.offload == "off" and sc.fused_steps == 1 \
                and sc.retrieval is None, \
                "latent attention runs inline, stepped, without retrieval"
        self.sparse_params = None
        sparse_fn = None
        if sc.method != "none" and cfg.family != "ssm":
            init_fn, mk = get_sparse_method(sc.method)
            # jitted like init_params: no eager float32 draw of a stack
            self.sparse_params = jax.jit(
                init_fn, static_argnums=(1, 2), static_argnames="stacked")(
                key if key is not None else jax.random.PRNGKey(0),
                cfg, self.mem, stacked=cfg.family != "hybrid")
        if self.sparse_params is not None and not cfg.is_mla:
            kw = {"page": sc.page} if sc.method == "dsa" else {}
            raw = mk(cfg, self.mem, tp=sc.tp, **kw)
            mem = self.mem

            def fallback_fn(q, kc, vc, length, sp, k_new=None):
                """Paper's dynamic fallback as a traced cond.

                ``length`` is a scalar (per-request decode) or a per-slot
                vector (pooled decode); the cond predicate is batch-level
                (max over slots — a jitted cond cannot branch per row), the
                branch itself masks per slot.
                """
                from repro.models import attention as A

                def dense(_):
                    return A.attention_decode(q, kc, vc, length, cfg, tp=sc.tp)

                def sparse(_):
                    return raw(q, kc, vc, length, sp, k_new=k_new)

                use_sparse = placement.traced_use_sparse(length, mem)
                return jax.lax.cond(use_sparse, sparse, dense, None)

            sparse_fn = fallback_fn
        self._sparse_fn = sparse_fn

        # --- main mesh (sequence-parallel apply) ---------------------------
        self.main_mesh = None
        self._mesh_sharding = None       # replicated NamedSharding on it
        exec_devs = None                 # executor placement override
        if sc.main_mesh > 1:
            assert self.devices is None, \
                "Engine(devices=...) pins a replica's device group; it " \
                "does not compose with main_mesh — the mesh picks its own " \
                "devices (hetero.policy.pick_devices_mesh)"
            assert sc.paged, "main_mesh shards the paged apply"
            assert sc.offload in ("sync", "overlap"), \
                "main_mesh needs ServeConfig(offload='sync'|'overlap') — " \
                "the sequence-parallel apply runs the two-phase presel step"
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.hetero import policy as hpolicy
            from repro.launch.mesh import mesh_from_devices
            mains, offs = hpolicy.pick_devices_mesh(
                sc.main_mesh, max(sc.offload_shards, 1))
            self.main_mesh = mesh_from_devices(mains, ("seq",))
            self._mesh_sharding = NamedSharding(self.main_mesh,
                                                PartitionSpec())
            exec_devs = (mains[0],
                         offs if sc.offload_shards > 1 else offs[0])
        elif self.devices is not None:
            # replica group: main device first, offload side round-robin
            # over the rest (over the whole group when it has one device —
            # transfers degenerate to no-ops, as in pick_devices)
            off_pool = self.devices[1:] or self.devices
            if sc.offload_shards > 1:
                exec_devs = (self.devices[0],
                             tuple(off_pool[i % len(off_pool)]
                                   for i in range(sc.offload_shards)))
            else:
                exec_devs = (self.devices[0], off_pool[0])

        self.hetero = None
        if sc.offload != "off":
            assert sc.offload in ("sync", "overlap"), sc.offload
            assert sc.paged, "hetero offload runs over the paged pool"
            assert sc.method in ("dsa", "seer", "lserve"), \
                "hetero offload needs a sparse memory-processing method"
            assert cfg.family in POOL_FAMILIES
            if sc.offload_shards > 1:
                from repro.hetero import ShardedHeteroExecutor
                self.hetero = ShardedHeteroExecutor(
                    cfg, self.mem, self.sc, self.sparse_params,
                    mode=sc.offload, validate=sc.offload_validate,
                    n_shards=sc.offload_shards, devices=exec_devs,
                    main_mesh=self.main_mesh)
            else:
                from repro.hetero import HeteroExecutor
                self.hetero = HeteroExecutor(
                    cfg, self.mem, self.sc, self.sparse_params,
                    mode=sc.offload, validate=sc.offload_validate,
                    devices=exec_devs, main_mesh=self.main_mesh)
        else:
            assert sc.offload_shards <= 1, \
                "offload_shards needs ServeConfig(offload='sync'|'overlap')"

        self.retrieval = None
        if sc.retrieval is not None:
            assert sc.paged, "the retrieval subsystem serves the paged pool"
            assert cfg.family in POOL_FAMILIES
            from repro.retrieval import RetrievalExecutor
            rdevs = self.hetero.devices if self.hetero else None
            if rdevs is None and exec_devs is not None:
                rdevs = (exec_devs[0],
                         exec_devs[1][0] if isinstance(exec_devs[1], tuple)
                         else exec_devs[1])
            self.retrieval = RetrievalExecutor(
                cfg, self.sc, sc.retrieval, self.params, key=key,
                devices=rdevs)

        # named functions, not lambdas: the compiled programs are called
        # jit_<name> in profiles and compile logs
        def prefill(p, toks):
            return M.prefill(p, cfg, toks, max_len=sc.max_len, tp=sc.tp)

        def decode(p, tok, caches, sp):
            return M.decode_step(p, cfg, tok, caches, tp=sc.tp,
                                 sparse_fn=self._sparse_fn, sparse_params=sp)

        def decode_paged(p, tok, kp, vp, table, lengths, live, sp):
            return M.decode_step_paged(
                p, cfg, tok,
                {"k_pages": kp, "v_pages": vp, "page_table": table,
                 "lengths": lengths},
                live, tp=sc.tp, sparse_fn=self._sparse_fn, sparse_params=sp)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)
        # pooled-path jits (built lazily; bucket/chunk shapes cached by key).
        # k_pages/v_pages are DONATED: the engine replaces its references
        # with the outputs right after each call, so XLA may update the pool
        # in place instead of copying the whole arena every token (on CPU
        # donation is a no-op warning; on TPU it is the difference between
        # O(touched pages) and O(pool) per-step HBM traffic).
        self._decode_paged = jax.jit(decode_paged, donate_argnums=(2, 3))
        self._bucket_fns: Dict[Tuple[int, int], callable] = {}
        self._extend_fns: Dict[Tuple[int, bool], callable] = {}
        self._splice_fns: Dict[Tuple[int, int], callable] = {}
        self._fused_fns: Dict[Tuple, callable] = {}   # inline fused loops
        self._table_view_cache = None  # (npv, table_version) -> sliced view

        self.slots = SlotManager(sc.n_slots, sc.max_len)
        self.pool: Optional[PagedKVPool] = None
        self.caches = None            # legacy dense pool
        # chunked-prefill state: slot -> [request_id, prompt np, next_pos]
        self._chunks: Dict[int, list] = {}
        # host_steps counts step_pool dispatch boundaries, decode_steps the
        # device steps behind them — their ratio is the host-dispatch
        # amortization a fused window buys (bench_fused_decode)
        self.stats = {"tokens": 0, "host_steps": 0, "decode_steps": 0}

        # --- request-level admission state (api.Request is the ONE way
        # into the pool; the compatibility Scheduler and the fleet router
        # both go through submit/poll) ---------------------------------
        self.prefill_token_budget = 2048   # per-poll admission budget
        self.queue: collections.deque = collections.deque()
        self._handles: Dict[int, ResponseHandle] = {}
        self._inflight_h: Dict[int, ResponseHandle] = {}
        self.done: Dict[int, ResponseHandle] = {}
        self._auto_rid = 0                 # generate() uses negative rids
        self._polled_prefill = False

    # ------------------------------------------------------------------
    # request-level serving API (submit / poll / drain)
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> ResponseHandle:
        """Enqueue one :class:`Request`. Admission happens inside ``poll``
        (FCFS under the prefill token budget, chunked for long prompts);
        the returned handle carries the live token stream and timing."""
        if not isinstance(req, Request):
            raise TypeError(
                f"submit() takes a serving.Request, got {type(req)!r}")
        if req.rid in self._handles and not self._handles[req.rid].done:
            raise ValueError(f"request id {req.rid} already in flight")
        h = ResponseHandle(req)
        self._handles[req.rid] = h
        self.queue.append(req)
        return h

    def queue_depth(self) -> int:
        return len(self.queue)

    def busy(self) -> bool:
        return bool(self.queue or self._inflight_h)

    def _next_rid(self) -> int:
        """Fresh internal rid (negative: never collides with caller ids)."""
        self._auto_rid -= 1
        return self._auto_rid

    def _mark_admitted(self, req: Request) -> None:
        h = self._handles[req.rid]
        h.admitted = time.perf_counter()
        self._inflight_h[req.rid] = h

    def _admit_from_queue(self) -> None:
        """FCFS batch admission within the per-poll prefill token budget:
        queued short prompts admit TOGETHER (one bucketed prefill per
        distinct bucket length), long prompts switch to chunked mode
        (pages reserved now, the prompt streams in ``prefill_chunk`` spans
        interleaved with decode), rejections re-queue at the FRONT."""
        if not self.queue:
            return
        budget = self.prefill_token_budget
        batch: List[Request] = []
        while self.queue and budget > 0:
            req = self.queue[0]
            plen = len(req)
            # a latent-attention model prefills every prompt in chunks
            chunked = self.sc.paged and (self.cfg.is_mla or bool(
                req.override("chunked", plen > self.sc.chunk_threshold)))
            if chunked:
                if not self._admit_chunked(req.rid, req.tokens, req.max_new,
                                           retrieval=req.retrieval):
                    break
                self.queue.popleft()
                self._mark_admitted(req)
                continue
            if batch and plen > budget:
                break                      # defer the rest to the next poll
            batch.append(req)
            self.queue.popleft()
            budget -= plen
        if not batch:
            return
        oks = self._admit_many(
            [(r.rid, r.tokens, r.max_new) for r in batch],
            retrieval=[r.retrieval for r in batch])
        # re-queue rejections at the FRONT, preserving FCFS order
        for r, ok in zip(reversed(batch), reversed(oks)):
            if ok:
                self._mark_admitted(r)
            else:
                self.queue.appendleft(r)

    def _dispatch(self, ev: StepEvents) -> None:
        """Route emissions into their ResponseHandles; finish handles that
        reached ``max_new`` and stamp the timing marks."""
        now = time.perf_counter()
        for rid, _slot, tok in ev.emissions:
            h = self._inflight_h.get(rid)
            if h is None:
                continue
            if h.first_token_t is None:
                h.first_token_t = now
            h.tokens.append(int(tok))
            if len(h.tokens) >= h.request.max_new:
                h.finished = now
                self.done[rid] = h
                del self._inflight_h[rid]

    def poll(self) -> StepEvents:
        """One serving turn: admit from the queue (budgeted), advance any
        chunked prefill, run one pooled-decode dispatch, and route the
        emissions into their handles. The fleet router and ``drain`` both
        pump this; it is safe to call on an idle engine."""
        with TraceAnnotation("engine.poll"):
            self._ensure_pool()
            if self.queue:
                with TraceAnnotation("engine.admit"):
                    self._admit_from_queue()
            self._polled_prefill = False
            if self.has_prefill_work():
                with TraceAnnotation("engine.prefill"):
                    self._polled_prefill = self.prefill_step()
            ev = self.step_pool()
            with TraceAnnotation("engine.dispatch"):
                self._dispatch(ev)
            return ev

    def drain(self, max_steps: int = 10_000) -> Dict[int, ResponseHandle]:
        """Pump ``poll`` until queue and pool are empty (or the head
        request can never admit); returns completed handles by rid."""
        steps = 0
        while (self.queue or self._inflight_h) and steps < max_steps:
            ev = self.poll()
            # a fused window consumes several device steps in one
            # dispatch; idle dispatches still count as one turn
            steps += max(1, ev.steps)
            if not ev and not self._polled_prefill:
                if self.has_retrieval_work() or self.has_prefill_work():
                    continue   # retrieval in flight, or a splice chunk
                               # was queued DURING this step's decode
                if not self.queue:
                    break
                if not self._inflight_h:
                    break      # head request can never admit: stuck
        return dict(self.done)

    def throughput_tokens_per_s(self) -> float:
        if not self.done:
            return 0.0
        toks = sum(len(h.tokens) for h in self.done.values())
        t0 = min(h.submitted for h in self.done.values())
        t1 = max(h.finished for h in self.done.values())
        return toks / max(t1 - t0, 1e-9)

    # ------------------------------------------------------------------
    # simple batched API
    # ------------------------------------------------------------------

    def generate(self, prompts: jnp.ndarray, max_new: int) -> np.ndarray:
        """prompts [B, S] -> generated [B, max_new] (greedy).

        Thin wrapper over ``submit``+``drain``: each row becomes a
        :class:`Request` through the one admission path and the pooled
        continuous-batching loop serves them — the per-row streams are
        bit-identical to the legacy per-batch dense-cache loop (the
        pooled-vs-dense equality the paged tests pin). Engines the pool
        cannot serve (ssm caches, ``paged=False``, prompts that don't
        fit, a pool already mid-flight) fall back to that loop unchanged.
        """
        prompts_np = np.asarray(prompts)
        B, S = prompts_np.shape
        poolable = (self.sc.paged and self.cfg.family in POOL_FAMILIES
                    and S + max_new <= self.sc.max_len
                    and not self.busy()
                    and not self.slots.live_mask().any())
        if not poolable:
            return self._generate_batched(prompts, max_new)
        handles = [self.submit(Request(self._next_rid(), row, max_new,
                                       retrieval=False))
                   for row in prompts_np]
        self.drain()
        for h in handles:        # generate() is a query, not a resident
            self.done.pop(h.rid, None)       # request: leave no residue
            self._handles.pop(h.rid, None)
        assert all(h.done for h in handles), \
            [h.rid for h in handles if not h.done]
        return np.stack([np.asarray(h.tokens, np.int32) for h in handles])

    def _generate_batched(self, prompts: jnp.ndarray,
                          max_new: int) -> np.ndarray:
        """Legacy batched dense-cache loop (the pre-pool oracle)."""
        logits, caches = self._prefill(self.params, prompts)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out = []
        for _ in range(max_new):
            out.append(tok)
            logits, caches = self._decode(self.params, tok, caches,
                                          self.sparse_params)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        self.stats["tokens"] += int(prompts.shape[0]) * max_new
        return np.stack([np.asarray(t) for t in out], axis=1)

    # ------------------------------------------------------------------
    # continuous batching (dense-cache families)
    # ------------------------------------------------------------------

    def _ensure_pool(self):
        if self.sc.paged:
            if self.pool is None:
                assert self.cfg.family in POOL_FAMILIES, \
                    "continuous batching requires dense KV caches"
                self.pool = PagedKVPool(
                    self.cfg, self.sc.n_slots, self.sc.max_len,
                    page_size=self.sc.kv_page_size,
                    total_pages=self.sc.pool_pages, tp=self.sc.tp)
                if self._mesh_sharding is not None:
                    # commit the pool buffers REPLICATED over the main mesh
                    # from the start: every jit touching them (apply with
                    # the shard_map seam, prefill splice, chunked extend)
                    # then compiles for the mesh, and buffer donation stays
                    # honorable (replicated in == replicated out)
                    for k in ("k_pages", "v_pages"):
                        self.pool.device[k] = jax.device_put(
                            self.pool.device[k], self._mesh_sharding)
                self._pending = np.zeros((self.sc.n_slots,), np.int32)
        elif self.caches is None:
            assert self.cfg.family in POOL_FAMILIES, \
                "continuous batching requires dense KV caches"
            self.caches = M.make_cache(self.cfg, self.sc.n_slots,
                                       self.sc.max_len, tp=self.sc.tp)
            self._pending = np.zeros((self.sc.n_slots,), np.int32)

    # -- admission (batched, length-bucketed prefill) -------------------

    def _bucket_len(self, prompt_len: int) -> int:
        ps = self.sc.kv_page_size
        b = _next_pow2(max(prompt_len, ps))
        b = ((b + ps - 1) // ps) * ps
        return min(b, self.sc.max_len)

    def _get_bucket_fn(self, B: int, Sb: int):
        key = (B, Sb)
        if key not in self._bucket_fns:
            cfg, sc = self.cfg, self.sc
            cq = self.hetero is not None

            def prefill_bucket(p, toks, lens):
                return M.prefill_bucketed(p, cfg, toks, lens, tp=sc.tp,
                                          collect_q=cq)

            self._bucket_fns[key] = jax.jit(prefill_bucket)
        return self._bucket_fns[key]

    def _get_splice_fn(self, B: int, n_pages: int):
        key = (B, n_pages)
        if key not in self._splice_fns:
            ps = self.sc.kv_page_size

            def splice(kp, vp, k, v, dest):
                # k/v [L, B, Sb, KV, hd] -> pages [L, B*n_pages, ps, KV, hd]
                Lc, Bc = k.shape[0], k.shape[1]
                kpg = k.reshape(Lc, Bc * n_pages, ps, *k.shape[3:])
                vpg = v.reshape(Lc, Bc * n_pages, ps, *v.shape[3:])
                flat = dest.reshape(-1)
                return kp.at[:, flat].set(kpg), vp.at[:, flat].set(vpg)

            self._splice_fns[key] = jax.jit(splice, donate_argnums=(0, 1))
        return self._splice_fns[key]

    def _admit_many(self, requests: List[Tuple[int, np.ndarray, int]],
                    retrieval: Optional[List] = None) -> List[bool]:
        """Admit a batch of (request_id, prompt, max_new): one bucketed
        prefill per distinct bucket length instead of one per request.
        ``retrieval[i]`` opts request i in/out of the retrieval service
        (None = service default: on when configured). Internal — callers
        admit through ``submit``."""
        self._ensure_pool()
        if not self.sc.paged:
            return [self._admit_one(rid, p, mn) for rid, p, mn in requests]
        admitted: Dict[int, List] = {}   # bucket_len -> [(slot, prompt)]
        ok: List[bool] = []
        for i, (rid, prompt, max_new) in enumerate(requests):
            prompt = np.asarray(prompt)
            total = len(prompt) + max_new
            if total > self.sc.max_len or not self.pool.can_alloc(total):
                ok.append(False)
                break                    # FCFS: don't let later requests
            slot = self.slots.admit(rid, len(prompt), max_new)
            if slot is None:             # jump a rejected head (starvation)
                ok.append(False)
                break
            assert self.pool.alloc(slot, total)
            admitted.setdefault(self._bucket_len(len(prompt)), []).append(
                (slot, prompt))
            ok.append(True)
            if self.retrieval is not None:
                self.retrieval.on_admit(
                    slot, prompt,
                    retrieval[i] if retrieval is not None else None)
        ok.extend([False] * (len(requests) - len(ok)))
        for Sb, group in admitted.items():
            self._prefill_bucket(Sb, group)
        return ok

    def _prefill_bucket(self, Sb: int, group: List[Tuple[int, np.ndarray]]):
        """One jitted prefill over a length bucket + one page splice."""
        ps = self.sc.kv_page_size
        B = len(group)
        toks = np.zeros((B, Sb), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, (_, prompt) in enumerate(group):
            toks[i, : len(prompt)] = prompt
            lens[i] = len(prompt)
        out = self._get_bucket_fn(B, Sb)(
            self.params, jnp.asarray(toks), jnp.asarray(lens))
        if self.hetero is not None:
            logits, k, v, q_last = out
            self.hetero.on_admit([slot for slot, _ in group], k, lens,
                                 q_last)
        else:
            logits, k, v = out
        n_pages = Sb // ps
        dest = np.zeros((B, n_pages), np.int32)
        for i, (slot, _) in enumerate(group):
            dest[i] = self.pool.table[slot, :n_pages]
        kp, vp = self._get_splice_fn(B, n_pages)(
            self.pool.device["k_pages"], self.pool.device["v_pages"],
            k, v, jnp.asarray(dest))
        self.pool.device["k_pages"], self.pool.device["v_pages"] = kp, vp
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        for i, (slot, _) in enumerate(group):
            self._pending[slot] = nxt[i]

    def _admit_one(self, request_id: int, prompt: np.ndarray, max_new: int,
                   retrieval: Optional[bool] = None) -> bool:
        """Prefill one request into a free slot (insertion into the pool)."""
        if self.sc.paged:
            return self._admit_many([(request_id, np.asarray(prompt),
                                      max_new)], retrieval=[retrieval])[0]
        assert self.cfg.family in POOL_FAMILIES, \
            "continuous batching requires dense KV caches"
        self._ensure_pool()
        slot = self.slots.admit(request_id, len(prompt), max_new)
        if slot is None:
            return False
        logits, c1 = self._prefill(self.params, jnp.asarray(prompt)[None])
        S = len(prompt)
        # splice the single-sequence cache into the pool at `slot`
        self.caches["k"] = jax.lax.dynamic_update_slice(
            self.caches["k"], c1["k"], (0, slot, 0, 0, 0))
        self.caches["v"] = jax.lax.dynamic_update_slice(
            self.caches["v"], c1["v"], (0, slot, 0, 0, 0))
        self._pending[slot] = int(jnp.argmax(logits[0]))
        return True

    # -- chunked prefill (long prompts, interleaved with decode) --------

    def _admit_chunked(self, request_id: int, prompt: np.ndarray,
                       max_new: int,
                       retrieval: Optional[bool] = None) -> bool:
        """Allocate slot + pages now; the prompt itself is prefilled in
        ``prefill_chunk``-sized spans by ``prefill_step`` so long prompts
        don't stall the decode pool."""
        assert self.sc.paged, "chunked prefill needs the paged pool"
        self._ensure_pool()
        prompt = np.asarray(prompt)
        total = len(prompt) + max_new
        if total > self.sc.max_len or not self.pool.can_alloc(total):
            return False
        slot = self.slots.admit(request_id, len(prompt), max_new)
        if slot is None:
            return False
        assert self.pool.alloc(slot, total)
        self.slots.slots[slot].length = 0      # grows as chunks land
        self._chunks[slot] = [request_id, prompt, 0, False]
        if self.hetero is not None:
            self.hetero.on_admit_slot(slot)
        if self.retrieval is not None:
            self.retrieval.on_admit(slot, prompt, retrieval)
        return True

    def has_prefill_work(self) -> bool:
        return bool(self._chunks)

    def _get_extend_fn(self, C: int, embeds: bool = False):
        key = (C, embeds)
        if key not in self._extend_fns:
            cfg, sc = self.cfg, self.sc
            ckq = self.hetero is not None

            def extend_paged(p, toks, kp, vp, table, lengths, nv, *extra):
                # extra: (x_embeds, emb_rows) for the embedding-splice
                # variant, or a latent-attention model's indexer, whose
                # prefill also writes the index keys
                xe, er = extra if embeds else (None, None)
                return M.extend_paged(
                    p, cfg, toks,
                    {"k_pages": kp, "v_pages": vp, "page_table": table,
                     "lengths": lengths},
                    nv, tp=sc.tp, collect_kq=ckq, x_embeds=xe, emb_rows=er,
                    sparse_params=extra[0] if cfg.is_mla else None)

            self._extend_fns[key] = jax.jit(extend_paged,
                                            donate_argnums=(2, 3))
        return self._extend_fns[key]

    def prefill_step(self) -> bool:
        """Advance every mid-prefill slot by one chunk — admission prompts
        and retrieval splices alike (retrieved documents / MaC embeddings
        ride the same chunked-extend machinery under the same budget).
        Returns True if any chunk work was done."""
        if not self._chunks:
            return False
        self._ensure_pool()
        C = self.sc.prefill_chunk
        n = self.sc.n_slots
        toks = np.zeros((n, C), np.int32)
        n_valid = np.zeros((n,), np.int32)
        emb_rows = np.zeros((n,), bool)
        x_embeds = None
        for slot, (rid, payload, pos, is_emb) in self._chunks.items():
            take = min(C, len(payload) - pos)
            if is_emb:
                if x_embeds is None:
                    x_embeds = np.zeros((n, C, self.cfg.d_model), np.float32)
                x_embeds[slot, :take] = payload[pos: pos + take]
                emb_rows[slot] = True
            else:
                toks[slot, :take] = payload[pos: pos + take]
            n_valid[slot] = take
        lengths = np.asarray([s.length for s in self.slots.slots], np.int32)
        lengths = np.where(n_valid > 0, lengths, 0)
        table = self._table_view(lengths, extra=C)
        args = (self.params, jnp.asarray(toks), self.pool.device["k_pages"],
                self.pool.device["v_pages"], table, jnp.asarray(lengths),
                jnp.asarray(n_valid))
        if x_embeds is not None:
            out = self._get_extend_fn(C, embeds=True)(
                *args, jnp.asarray(x_embeds), jnp.asarray(emb_rows))
        elif self.cfg.is_mla:
            out = self._get_extend_fn(C)(*args, self.sparse_params)
        else:
            out = self._get_extend_fn(C)(*args)
        logits, pool = out[0], out[1]
        self.pool.device["k_pages"] = pool["k_pages"]
        self.pool.device["v_pages"] = pool["v_pages"]
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        finished: List[int] = []     # slots whose payload (admission
        for slot in list(self._chunks):  # prompt or splice) completed

            rid, payload, pos, is_emb = self._chunks[slot]
            take = int(n_valid[slot])
            self.slots.slots[slot].length += take
            if pos + take >= len(payload):
                self._pending[slot] = nxt[slot]
                del self._chunks[slot]
                finished.append(slot)
            else:
                self._chunks[slot][2] = pos + take
        if self.hetero is not None:
            # per-slot lookahead invalidation: only the finishing slots'
            # selection rows go dirty — a retrieval splice landing in one
            # slot no longer discards every other slot's valid lookahead
            k_span, q_last = out[2], out[3]
            self.hetero.on_extend(k_span, q_last, lengths, n_valid, finished)
        return True

    # -- pooled decode --------------------------------------------------

    def _view_len(self, needed: int) -> int:
        """Logical length of the gathered decode view: enough pages for the
        longest live slot, bucketed (pow2 multiples of the alignment granule)
        so the jit cache stays small. This is what kills the watermark tax —
        a pool whose longest live sequence is 300 tokens attends over a
        512-token view, not ``max_len``."""
        if not self.sc.view_buckets:
            return self.sc.max_len
        g = self._gran
        units = _next_pow2(max(1, -(-needed // g)))
        return min(g * units, self.sc.max_len)

    def _table_view(self, lengths: np.ndarray, extra: int = 1) -> jnp.ndarray:
        """Page table restricted to the bucketed view length.

        The slice is cached on (view pages, pool.table_version): steady-state
        decode re-slices (and re-uploads) nothing — the cache invalidates
        only when the bucket changes or a host-side table edit (admission,
        release, splice) bumps the pool's version counter."""
        needed = int(lengths.max()) + extra if lengths.size else 1
        vl = self._view_len(needed)
        npv = vl // self.sc.kv_page_size
        key = (npv, self.pool.table_version)
        if self._table_view_cache is None or self._table_view_cache[0] != key:
            self._table_view_cache = (
                key, self.pool.device["page_table"][:, :npv])
        return self._table_view_cache[1]

    def _decode_live(self) -> np.ndarray:
        """Slots that decode this step: live, not mid-prefill, and not
        paused awaiting an overlapped retrieval result."""
        live = self.slots.live_mask()
        for slot in self._chunks:
            live[slot] = False
        if self.retrieval is not None:
            live &= ~self.retrieval.waiting_mask()
        return live

    def _fused_window(self) -> int:
        """Width of the next fused decode window. 1 = stepped host loop.
        Fused windows only open when the host has nothing to interleave:
        no chunked prefill pending and the retrieval subsystem quiescent
        (in-flight queries and waiting slots need per-step host turns)."""
        K = self.sc.fused_steps
        if K <= 1 or not self.sc.paged or self._chunks:
            return 1
        if self.retrieval is not None and self.retrieval.busy():
            return 1
        return K

    def step_pool(self) -> StepEvents:
        """One host dispatch of the decode loop; returns a ``StepEvents``
        (iterating it yields the (request_id, slot, token) emissions the
        old list API returned). Stepped path: one decode step for every
        live slot. Fused path (``fused_steps`` K > 1): up to K steps run
        on device in one ``lax.scan`` and the host replays the emitted
        event log. Paged path: per-slot lengths (each slot attends,
        writes, and rotates at its own position); legacy path: shared
        ``lengths.max()`` watermark."""
        self._ensure_pool()
        if not self.sc.paged:
            return self._step_pool_dense()
        live = self._decode_live()
        if not live.any():
            if self.retrieval is not None:
                self._retrieval_idle()
            return StepEvents()
        K = self._fused_window()
        if K > 1:
            return self._step_pool_fused(live, K)
        with TraceAnnotation("engine.decode.launch"):
            lengths = np.where(live, self.slots.lengths(), 0).astype(
                np.int32)
            table = self._table_view(lengths)
            tok = jnp.asarray(self._pending)
            if self.hetero is not None:
                logits, pool = self.hetero.decode(
                    self.params, tok, self.pool.device, table, lengths, live)
            else:
                logits, pool = self._decode_paged(
                    self.params, tok, self.pool.device["k_pages"],
                    self.pool.device["v_pages"], table, jnp.asarray(lengths),
                    jnp.asarray(live), self.sparse_params)
            self.pool.device["k_pages"] = pool["k_pages"]
            self.pool.device["v_pages"] = pool["v_pages"]
        with TraceAnnotation("engine.decode.sync"):
            nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        with TraceAnnotation("engine.decode.emit"):
            self.stats["host_steps"] += 1
            self.stats["decode_steps"] += 1
            ev = StepEvents(steps=1)
            for i in np.flatnonzero(live):
                rid = self.slots.slots[i].request_id
                ev.emissions.append((rid, int(i), int(self._pending[i])))
                if self.retrieval is not None:
                    self.retrieval.note_token(int(i), int(self._pending[i]))
                self._pending[i] = nxt[i]
            self.stats["tokens"] += len(ev.emissions)
            self.slots.step(live)
            for i in np.flatnonzero(live):
                if self.slots.slots[i].done:
                    ev.finished.append(int(i))
                    self.pool.release(int(i))
                    if self.retrieval is not None:
                        self.retrieval.on_release(int(i))
            if self.retrieval is not None:
                ev.fired.extend(self._retrieval_step(logits, live, lengths))
            return ev

    # -- fused multi-step decode (serving/fused.py) ---------------------

    def _fused_fn_inline(self, n_pages_view: int, K: int, trigger):
        key = (n_pages_view, K, trigger)
        if key not in self._fused_fns:
            from repro.serving.fused import make_fused_paged
            fn = make_fused_paged(self.cfg, self.mem, self.sc, K=K,
                                  trigger=trigger,
                                  sparse_fn=self._sparse_fn)
            self._fused_fns[key] = jax.jit(fn, donate_argnums=(3, 4))
        return self._fused_fns[key]

    def _decode_fused_inline(self, table, lengths, live, K, gen, maxnew,
                             armed, arm_after, trigger):
        fn = self._fused_fn_inline(int(table.shape[1]), K, trigger)
        outs = fn(self.params, self.sparse_params,
                  jnp.asarray(self._pending),
                  self.pool.device["k_pages"], self.pool.device["v_pages"],
                  table, jnp.asarray(lengths), jnp.asarray(live),
                  jnp.asarray(gen), jnp.asarray(maxnew),
                  jnp.asarray(armed), jnp.asarray(arm_after))
        with TraceAnnotation("engine.decode.sync"):
            nsteps = int(jax.block_until_ready(outs["nsteps"]))
            return {"k_pages": outs["k_pages"], "v_pages": outs["v_pages"],
                    "pending": outs["pending"], "nsteps": nsteps,
                    "emits": np.asarray(outs["emits"]),
                    "fired": np.asarray(outs["fired"])}

    def _step_pool_fused(self, live: np.ndarray, K: int) -> StepEvents:
        """Run up to K decode steps in one jitted scan, then replay the
        emitted per-step event log through the exact bookkeeping the
        stepped path runs — token-for-token identical emissions, finish
        order, retrieval launches, and pool accounting. The scan stops
        early (masked no-ops, ``nsteps`` reports the real count) when any
        slot finishes or fires a trigger, handing control back to the host
        for admission/splice servicing at the same step boundary the
        stepped loop would have."""
        sl = self.slots.slots
        lengths = np.where(live, self.slots.lengths(), 0).astype(np.int32)
        gen = np.asarray([s.generated for s in sl], np.int32)
        maxnew = np.asarray([s.max_new for s in sl], np.int32)
        rx = self.retrieval
        if rx is not None:
            armed, arm_after = rx.fused_gates()
            trigger = (rx.rcfg.trigger, rx.rcfg.tau)
        else:
            armed = np.zeros((self.sc.n_slots,), bool)
            arm_after = np.zeros((self.sc.n_slots,), np.int32)
            trigger = None
        # the window's device sync (engine.decode.sync) nests inside this
        with TraceAnnotation("engine.decode.launch"):
            # extra=K: mid-window lengths grow up to K past the entry
            # maximum, and a page-table view is numerically neutral but a
            # scatter outside it would silently drop — the view must cover
            # the window
            table = self._table_view(lengths, extra=K)
            if self.hetero is not None:
                res = self.hetero.decode_fused(
                    self.params, self._pending, self.pool.device, table,
                    lengths, live, K, gen_np=gen, maxnew_np=maxnew,
                    armed_np=armed, arm_after_np=arm_after, trigger=trigger)
            else:
                res = self._decode_fused_inline(table, lengths, live, K, gen,
                                                maxnew, armed, arm_after,
                                                trigger)
        with TraceAnnotation("engine.decode.emit"):
            self.pool.device["k_pages"] = res["k_pages"]
            self.pool.device["v_pages"] = res["v_pages"]
            self._pending = np.asarray(res["pending"], np.int32).copy()
            nsteps = res["nsteps"]
            emits, fired = res["emits"], res["fired"]
            self.stats["host_steps"] += 1
            self.stats["decode_steps"] += nsteps
            ev = StepEvents(steps=nsteps)
            for j in range(nsteps):
                step_live = emits[j] >= 0
                for i in np.flatnonzero(step_live):
                    ev.emissions.append((sl[i].request_id, int(i),
                                         int(emits[j, i])))
                    if rx is not None:
                        rx.note_token(int(i), int(emits[j, i]))
                self.stats["tokens"] += int(step_live.sum())
                self.slots.step(step_live)
                for i in np.flatnonzero(step_live):
                    if sl[i].done:
                        ev.finished.append(int(i))
                        self.pool.release(int(i))
                        if rx is not None:
                            rx.on_release(int(i))
                if rx is not None:
                    rx.tick()
                    for job in rx.collect_ready(min_age=1):
                        self._queue_splice(*job)
                    for i in np.flatnonzero(fired[j]):
                        if not self._reserve_splice(int(i)):
                            rx.note_suppressed(int(i))
                            continue
                        rx.launch(int(i))
                        ev.fired.append(int(i))
            return ev

    # -- retrieval service hooks (src/repro/retrieval) ------------------

    def has_retrieval_work(self) -> bool:
        """True while a retrieval is in flight or a slot awaits its result
        (the scheduler must keep stepping an otherwise-idle pool)."""
        return self.retrieval is not None and self.retrieval.busy()

    def _retrieval_idle(self) -> None:
        """No decodable slot this step: still age + drain overlapped
        queries so paused slots get their splice queued."""
        rx = self.retrieval
        rx.tick()
        for job in rx.collect_ready(min_age=1):
            self._queue_splice(*job)

    def _retrieval_step(self, logits, live_np: np.ndarray,
                        lengths_np: np.ndarray) -> List[int]:
        """Post-decode retrieval phase: consume queries launched on earlier
        steps (the fired slot paused for exactly one step in EVERY mode —
        one dataflow, barriers differ), then evaluate this step's triggers,
        reserve pages, and launch. Returns the slots whose queries
        launched this step."""
        rx = self.retrieval
        rx.tick()
        for job in rx.collect_ready(min_age=1):
            self._queue_splice(*job)
        launched: List[int] = []
        for slot in rx.trigger_slots(logits, live_np, lengths_np,
                                     self.slots.slots):
            if not self._reserve_splice(slot):
                rx.note_suppressed(slot)
                continue
            rx.launch(slot)
            launched.append(slot)
        return launched

    def _reserve_splice(self, slot: int) -> bool:
        """Grow the slot's page reservation for the retrieval upper bound
        AT THE TRIGGER STEP, so pool accounting is schedule-independent."""
        s = self.slots.slots[slot]
        need = s.length + self.retrieval.splice_bound() + \
            (s.max_new - s.generated)
        if need > self.sc.max_len:
            return False
        return self.pool.grow(slot, need)

    def _queue_splice(self, slot: int, tokens, embeds, ids) -> None:
        """Push a retrieved payload into the chunked-extend queue; the slot
        rejoins decode once the splice drains, its pending token REGENERATED
        from the document-augmented context (FLARE semantics)."""
        payload = tokens if tokens is not None else embeds
        if payload is None or len(payload) == 0:
            return
        s = self.slots.slots[slot]
        self._chunks[slot] = [s.request_id, payload, 0,
                              embeds is not None]
        self.retrieval.note_splice(
            slot, tokens if tokens is not None else len(embeds))

    def _step_pool_dense(self) -> StepEvents:
        """Legacy baseline: dense pool, shared length watermark (max over
        slots) — every slot pays the longest sequence's attention cost and
        the sparse fallback cond sees the watermark, not true lengths."""
        live = self.slots.live_mask()
        if not live.any():
            return StepEvents()
        lengths = self.slots.lengths()
        self.caches = dict(self.caches,
                           length=jnp.asarray(lengths.max(), jnp.int32))
        tok = jnp.asarray(self._pending)
        logits, self.caches = self._decode(self.params, tok, self.caches,
                                           self.sparse_params)
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        self.stats["host_steps"] += 1
        self.stats["decode_steps"] += 1
        ev = StepEvents(steps=1)
        for i in np.flatnonzero(live):
            rid = self.slots.slots[i].request_id
            ev.emissions.append((rid, int(i), int(self._pending[i])))
            self._pending[i] = nxt[i]
        self.slots.step(live)
        for i in np.flatnonzero(live):
            if self.slots.slots[i].done:
                ev.finished.append(int(i))
        return ev
