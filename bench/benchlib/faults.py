"""Faults planted under the timed path, for showing that the comparison
which decides ``correct`` catches them (bench/tests/test_correct.py at a CPU
size, ``bench/calibrate.py --fault`` at a cell's own size on the chip). The
benchmark's own runs never plant one.

Each is ``fault(engine)``, applied after the engine is built and before any
request, so every program it changes is traced with the fault in it.
``first_pages`` replaces a function of the program's kernel module for the
rest of the process: run it in a process of its own, or restore
``repro.kernels.ops.relevancy_topk`` after the run.
"""
from __future__ import annotations

import numpy as np


def alter_a_token(eng):
    """Served tokens changed where the engine hands them to their requests
    (every 25th emission)."""
    dispatch = eng._dispatch
    seen = {"n": 0}

    def bad(ev):
        for i, (rid, slot, tok) in enumerate(ev.emissions):
            seen["n"] += 1
            if seen["n"] % 25 == 0:
                ev.emissions[i] = (rid, slot, (tok + 1) % eng.cfg.vocab_size)
        return dispatch(ev)

    eng._dispatch = bad


def state_unchanged(eng):
    """Decode steps that hand back the KV pool they were given: the tokens
    they decode are never written."""
    import jax
    from repro.models import model as M
    real = M.decode_step_paged

    def stale(params, cfg, token, pool, live, **kw):
        logits, _ = real(params, cfg, token, pool, live, **kw)
        return logits, dict(pool, lengths=pool["lengths"]
                            + live.astype(np.int32))

    eng._decode_paged = jax.jit(
        lambda p, tok, kp, vp, table, lengths, live, sp: stale(
            p, eng.cfg, tok, {"k_pages": kp, "v_pages": vp,
                              "page_table": table, "lengths": lengths},
            live, tp=eng.sc.tp, sparse_fn=eng._sparse_fn, sparse_params=sp))


def first_pages(eng):
    """The DSA selection ignored: the relevancy kernel still runs, but
    sparse decode attends to the first top_k / page pages of every context
    instead of the pages it scored highest."""
    import jax.numpy as jnp
    from repro.kernels import ops
    real = ops.relevancy_topk

    def first(q, keys, weights, k, **kw):
        vals, idx = real(q, keys, weights, k, **kw)
        return vals, jnp.broadcast_to(jnp.arange(k, dtype=idx.dtype),
                                      idx.shape)

    ops.relevancy_topk = first


FAULTS = {f.__name__: f for f in (alter_a_token, state_unchanged,
                                  first_pages)}
