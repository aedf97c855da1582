"""Fused multi-step decode: K device steps per host dispatch (lax.scan).

The serving loop used to pay one full Python round-trip per decoded token —
launch decode, pull logits, argmax on host, update the slot table, launch
again. This module folds K steps into ONE jitted ``lax.scan``: decode,
greedy sampling, pool write/rotate, the hetero lookahead double-buffer
(select_{t+1} queued from the pre-ingest state while apply_t runs — the
ping-pong ``hetero/executor.py`` orchestrates from Python, here expressed
as carry state), and the FLARE/DRAGIN trigger predicate — all on device.

Early exit is masked, not structural: the scan body wraps in
``lax.cond(stop, idle, step)``; once any slot finishes or fires a trigger
the remaining iterations are no-ops and ``nsteps`` reports how many steps
were actually consumed. The host replays the emitted event log (per-step
emissions + fired flags) through the exact bookkeeping the stepped path
runs, so ``fused(K)`` emits token-for-token what K separate ``step_pool()``
calls emit:

  * per-step lengths are re-masked inside the body, so dead rows behave
    exactly as in the stepped path (their writes route to the zero page);
  * the dynamic-fallback window is the same traced predicate the apply
    phase uses (``placement.traced_use_sparse``), evaluated per step on the
    in-carry lengths — a window can cross ``min_context`` mid-scan and the
    selection double-buffer cold-starts on re-entry exactly like the host
    executor does;
  * the page-table view is sized with ``extra=K`` headroom (the engine's
    job): a view is numerically neutral (masked attention, exp(-1e30)=0
    exactly) but a scatter outside it would silently drop, so the window
    must cover the maximum mid-window length.

Host-visible semantics (finished slots, retrieval launches, splices,
admissions) stay host-side: the engine only enters a fused window when the
retrieval subsystem is quiescent and no chunked prefill is pending, and the
window exits back to the host at the first step that needs servicing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import placement
from repro.models import model as M


def _blend_q(q_buf, q_t, live):
    """Stale-query refresh (``HeteroExecutor._blend_q`` with a live mask):
    rows that decoded this step take the new query."""
    return jnp.where(live[None, :, None, None], q_t.astype(q_buf.dtype),
                     q_buf)


def _advance(c, logits, lengths_m, maxnew, max_len, armed, arm_after,
             trigger):
    """Shared post-decode bookkeeping of one in-scan step: greedy sampling,
    emission, slot advance, finish detection, trigger predicate, stop flag.
    Mirrors ``slots.step`` + ``_retrieval_step`` bit for bit."""
    live = c["live"]
    adv = live.astype(jnp.int32)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    emit = jnp.where(live, c["pending"], -1)
    pending = jnp.where(live, nxt, c["pending"])
    gen = c["gen"] + adv
    emitted = c["emitted"] + adv
    lengths = c["lengths"] + adv
    fin = live & ((gen >= maxnew) | (lengths >= max_len))
    if trigger is None:
        fired = jnp.zeros_like(live)
    else:
        from repro.retrieval.executor import traced_trigger
        pred = traced_trigger(trigger[0], trigger[1], logits, lengths_m)
        # the host gates (enabled, budget, cooldown, bank occupancy) are
        # static or countdown-expressible over the window: ``armed`` folds
        # the static ones, ``arm_after`` is the emitted-token count at
        # which the countdown gates open (hist grows 1/emitted token)
        fired = pred & live & ~fin & armed & (emitted >= arm_after)
    stop = fin.any() | fired.any()
    c = dict(c, pending=pending, gen=gen, emitted=emitted, lengths=lengths,
             live=live & ~fin, stop=stop, nsteps=c["nsteps"] + 1)
    return c, (emit, fired)


def make_fused_paged(cfg, mem, sc, *, K: int, trigger, sparse_fn):
    """Fused loop for the INLINE pipeline (``offload='off'``): K iterations
    of ``decode_step_paged`` (sparse method + dynamic fallback fused inside
    ``sparse_fn``) with sampling and trigger checks on device.

    Returns an unjitted ``fn(params, sp, tok, kp, vp, table, lengths, live,
    gen, maxnew, armed, arm_after) -> outs``; the engine jits it with
    the pool buffers donated."""

    def fused_decode(params, sp, tok, kp, vp, table, lengths, live, gen,
                     maxnew, armed, arm_after):
        B = tok.shape[0]

        def idle(c):
            return c, (jnp.full((B,), -1, jnp.int32),
                       jnp.zeros((B,), bool))

        def step(c):
            lengths_m = jnp.where(c["live"], c["lengths"], 0)
            pool = {"k_pages": c["kp"], "v_pages": c["vp"],
                    "page_table": table, "lengths": lengths_m}
            logits, pool = M.decode_step_paged(
                params, cfg, c["pending"], pool, c["live"], tp=sc.tp,
                sparse_fn=sparse_fn, sparse_params=sp)
            c = dict(c, kp=pool["k_pages"], vp=pool["v_pages"])
            return _advance(c, logits, lengths_m, maxnew, sc.max_len,
                            armed, arm_after, trigger)

        def body(c, _):
            return jax.lax.cond(c["stop"], idle, step, c)

        carry = {"kp": kp, "vp": vp, "pending": tok,
                 "lengths": lengths.astype(jnp.int32), "live": live,
                 "gen": gen, "emitted": jnp.zeros_like(gen),
                 "stop": jnp.zeros((), bool),
                 "nsteps": jnp.zeros((), jnp.int32)}
        carry, (emits, fired) = jax.lax.scan(body, carry, None, length=K)
        return {"k_pages": carry["kp"], "v_pages": carry["vp"],
                "pending": carry["pending"], "nsteps": carry["nsteps"],
                "emits": emits, "fired": fired}

    return fused_decode


def make_fused_presel(cfg, mem, sc, sel, *, K: int, trigger, page_attn):
    """Fused loop for the HETERO two-phase pipeline: apply over preselected
    pages + the on-device selection double-buffer.

    Per iteration, from the carry's (summary, qbuf, sel, sel_ok):

      consume   pidx = pending lookahead if sel_ok, else a cold-start
                select from the pre-ingest carry state (matching the host
                executor's cold path after a fallback step);
      lookahead nxt_sel = select(summary_pre, qbuf_pre, lengths + live) —
                the exact inputs ``_launch_select(lengths_np + live_np)``
                pins in the stepped schedule;
      apply     ``decode_step_paged_presel`` (scan-compatible carry: pool
                lengths re-masked per step, this step's per-layer q/k out);
      ingest    fold q/k into summary/qbuf for the next iteration.

    The final (sel, sel_ok) and the PRE-ingest pins of the last executed
    step come back to the host so the executor can resume its stepped
    double-buffer (and ``validate=True`` can replay the exit lookahead)
    without a cold start. Sharded executors pass the full-window summary
    (shard summaries concatenated along the page axis — bit-identical to
    the merged per-shard selection) and scatter it back after the window.
    """

    def fused_decode(params, sp, tok, kp, vp, table, lengths, live, gen,
                     maxnew, sel0, sel_ok0, summary0, qbuf0, armed,
                     arm_after):
        B = tok.shape[0]
        neg = jnp.full((cfg.n_layers, B, sel.n_sel), -1, jnp.int32)

        def idle(c):
            return c, (jnp.full((B,), -1, jnp.int32),
                       jnp.zeros((B,), bool), jnp.zeros((), bool))

        def step(c):
            lengths_m = jnp.where(c["live"], c["lengths"], 0)
            # same predicate as the apply phase's internal cond AND the
            # host executor's dynamic_mode mirror
            offl = placement.traced_use_sparse(lengths_m + 1, mem)
            pidx = jax.lax.cond(
                offl,
                lambda _: jax.lax.cond(
                    c["sel_ok"], lambda _: c["sel"],
                    lambda _: sel.select(sp, c["summary"], c["qbuf"],
                                         lengths_m), None),
                lambda _: neg, None)
            la_len = lengths_m + c["live"].astype(jnp.int32)
            nxt_sel = jax.lax.cond(
                offl,
                lambda _: sel.select(sp, c["summary"], c["qbuf"], la_len),
                lambda _: c["sel"], None)
            pool = {"k_pages": c["kp"], "v_pages": c["vp"],
                    "page_table": table, "lengths": lengths_m}
            logits, pool, q_t, k_t = M.decode_step_paged_presel(
                params, cfg, c["pending"], pool, c["live"], pidx, mem,
                page_size=sel.page, tp=sc.tp, page_attn=page_attn)
            c = dict(c, kp=pool["k_pages"], vp=pool["v_pages"],
                     # pre-ingest pins of THIS step: the inputs the exit
                     # lookahead was computed from (validation replay)
                     prev_summary=c["summary"], prev_q=c["qbuf"],
                     prev_len=la_len,
                     summary=sel.ingest(c["summary"], sp, k_t, lengths_m,
                                        c["live"]),
                     qbuf=_blend_q(c["qbuf"], q_t, c["live"]),
                     sel=nxt_sel, sel_ok=offl)
            c, (emit, fired) = _advance(c, logits, lengths_m, maxnew,
                                        sc.max_len, armed, arm_after,
                                        trigger)
            return c, (emit, fired, offl)

        def body(c, _):
            return jax.lax.cond(c["stop"], idle, step, c)

        carry = {"kp": kp, "vp": vp, "pending": tok,
                 "lengths": lengths.astype(jnp.int32), "live": live,
                 "gen": gen, "emitted": jnp.zeros_like(gen),
                 "sel": sel0, "sel_ok": sel_ok0,
                 "summary": summary0, "qbuf": qbuf0,
                 "prev_summary": summary0, "prev_q": qbuf0,
                 "prev_len": lengths.astype(jnp.int32),
                 "stop": jnp.zeros((), bool),
                 "nsteps": jnp.zeros((), jnp.int32)}
        carry, (emits, fired, offl) = jax.lax.scan(body, carry, None,
                                                   length=K)
        return {"k_pages": carry["kp"], "v_pages": carry["vp"],
                "pending": carry["pending"], "nsteps": carry["nsteps"],
                "sel": carry["sel"], "sel_ok": carry["sel_ok"],
                "summary": carry["summary"], "qbuf": carry["qbuf"],
                "prev_summary": carry["prev_summary"],
                "prev_q": carry["prev_q"], "prev_len": carry["prev_len"],
                "emits": emits, "fired": fired, "offl": offl}

    return fused_decode
