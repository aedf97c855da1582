"""Paper Fig. 9 / Fig. 10(right): speedup of the fused relevancy+retrieval
engine over the unfused baseline.

  * MEASURED (CPU): unfused XLA pipeline (full scores materialized -> top-k)
    vs the fused candidate scheme (jitted, use_pallas(False) so both sides
    are XLA — an apples-to-apples algorithmic comparison).
  * DERIVED (TPU roofline): byte-traffic model — the fused kernel streams
    keys once and never writes scores to HBM; the unfused path writes+reads
    the score vector. speedup = bytes_unfused / bytes_fused at 819 GB/s.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import pick, row, timeit
from repro.kernels import ops, ref


def run():
    # run.py keeps going after a failed bench, so the use_pallas(False)
    # below must be undone even on exceptions — later benches in the same
    # process (e2e, hetero_overlap) need the kernels back.
    prev = ops.pallas_enabled()
    try:
        return _run()
    finally:
        ops.use_pallas(prev)


def _run():
    rows = []
    rng = np.random.default_rng(0)
    B, Hq, dk, k = 1, 64, 128, 2048

    for S in pick((8192, 65536), (2048,)):
        q = jnp.asarray(rng.standard_normal((B, Hq, dk)), jnp.bfloat16)
        keys = jnp.asarray(rng.standard_normal((B, S, dk)), jnp.bfloat16)
        w = jnp.abs(jnp.asarray(rng.standard_normal((B, Hq)), jnp.float32))

        unfused = jax.jit(lambda q, kk, w: ref.relevancy_topk(q, kk, w, k))
        # route the "fused" side through the jitted XLA reference too (CPU
        # interpret-mode Pallas would swamp the comparison); run() restores.
        ops.use_pallas(False)
        fused = jax.jit(lambda q, kk, w: ops.relevancy_topk(q, kk, w, k,
                                                            block=4096))
        t_u = timeit(unfused, q, keys, w)
        t_f = timeit(fused, q, keys, w)
        rows.append(row(f"fig9_measured_S{S}_unfused", t_u, ""))
        rows.append(row(f"fig9_measured_S{S}_fused", t_f,
                        f"speedup={t_u / t_f:.2f}"))
        # derived roofline speedup on TPU v5e
        bytes_keys = S * dk * 2
        bytes_scores = S * 4
        unfused_bytes = bytes_keys + 2 * bytes_scores  # write + re-read scores
        fused_bytes = bytes_keys + k * 8               # only candidates leave
        rows.append(row(f"fig9_derived_S{S}", unfused_bytes / 819e9,
                        f"speedup={unfused_bytes / fused_bytes:.2f}"))

    # BM25 (Fig. 10 right): fused vs unfused over the doc panel
    D, T, kk = pick(16384, 2048), 16, 64
    tf = jnp.asarray(rng.poisson(1.0, (1, D, T)), jnp.float32)
    dl = jnp.asarray(rng.integers(20, 200, (1, D)), jnp.float32)
    idf = jnp.asarray(rng.random((1, T)), jnp.float32)
    unf = jax.jit(lambda a, b, c: ref.bm25_topk(a, b, c, kk))
    fus = jax.jit(lambda a, b, c: ops.bm25_topk(a, b, c, kk, block=4096))
    t_u = timeit(unf, tf, dl, idf)
    t_f = timeit(fus, tf, dl, idf)
    rows.append(row(f"fig10_bm25_D{D}_unfused", t_u, ""))
    rows.append(row(f"fig10_bm25_D{D}_fused", t_f,
                    f"speedup={t_u / t_f:.2f}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
