"""Memory-processing methods (paper Table 1). ``get_method(name)`` returns
(init_fn, make_sparse_fn) for the sparse-attention family; RAG / MemAgent /
MaC / TTT expose their own application-level APIs.
"""
import jax

from repro.core import placement
from repro.core.methods import dsa, seer, lserve, rag, memagent, mac, ttt

SPARSE_METHODS = {
    "dsa": (dsa.dsa_init, dsa.make_sparse_fn),
    "seer": (seer.seer_init, seer.make_sparse_fn),
    "lserve": (lserve.lserve_init, lserve.make_sparse_fn),
}


def get_sparse_method(name: str):
    if name not in SPARSE_METHODS:
        raise KeyError(f"unknown sparse method {name!r}: {sorted(SPARSE_METHODS)}")
    return SPARSE_METHODS[name]


def make_decode_attention(cfg, mem, method: str, *, tp: int = 16,
                          page: int = 16):
    """Decode attention with ``method``'s sparse function behind the
    paper's dynamic fallback, as a traced cond: the sparse pipeline while
    the context sits inside [min_context, fallback_context]
    (``placement.traced_use_sparse``), dense ``attention_decode`` outside.

    Returns ``fn(q, kc, vc, length, sp, k_new=None)``, the ``sparse_fn``
    that ``models.decode_step`` and ``models.decode_step_paged`` take.
    ``length`` is a scalar (per-request decode) or a per-slot vector
    (pooled decode); the cond predicate is batch-level (max over slots — a
    jitted cond cannot branch per row), the branch itself masks per slot.
    """
    from repro.models import attention as A

    _, mk = get_sparse_method(method)
    raw = mk(cfg, mem, tp=tp, **({"page": page} if method == "dsa" else {}))

    def fallback_fn(q, kc, vc, length, sp, k_new=None):
        def dense(_):
            return A.attention_decode(q, kc, vc, length, cfg, tp=tp)

        def sparse(_):
            return raw(q, kc, vc, length, sp, k_new=k_new)

        use_sparse = placement.traced_use_sparse(length, mem)
        return jax.lax.cond(use_sparse, sparse, dense, None)

    return fallback_fn


_METHOD_MODULES = {
    "dsa": dsa, "seer": seer, "lserve": lserve, "rag": rag,
    "memagent": memagent, "mac": mac, "ttt": ttt,
}


def offload_stages(name: str) -> tuple:
    """Which pipeline stages of ``name`` may leave the KV-owning device
    (paper §5.2): stages that read only the compressed index / documents.
    Declared per method as ``OFFLOAD_STAGES``; methods without the
    attribute (or unknown names like 'none') offload nothing."""
    mod = _METHOD_MODULES.get(name)
    return getattr(mod, "OFFLOAD_STAGES", ()) if mod else ()
