"""Plain float32 reference of a dense GQA decoder (Qwen2 / Qwen3 layers)
whose decode positions attend through a DSA lightning indexer.

Equations, per layer, for a sequence of tokens x_0..x_{T-1}:

    h      = rmsnorm(x) * g_attn
    q,k,v  = h Wq (+bq), h Wk (+bk), h Wv (+bv)       per head, hd wide
    q,k    = rmsnorm_hd(q) * g_q, rmsnorm_hd(k) * g_k   (Qwen3: qk-norm)
    q,k    = rope(q, p), rope(k, p)                   rotate-half, theta
    attn   = softmax(q k^T / sqrt(hd)) v over the keys position p attends
    x      = x + attn Wo
    x      = x + (silu(h2 W1) * (h2 W3)) W2,  h2 = rmsnorm(x) * g_mlp
    logits = (rmsnorm(x) * g_final) W_head

Which keys position p attends: every t <= p for a prompt position (p < P,
the prompt length) and for a decode position whose context p + 1 is under
``min_context``; otherwise the DSA selection: index query
q_idx = q(p) W_qidx (64 heads of 128, from the rope'd query), index keys
k_idx(t) = k(t) W_kidx, page summaries kp_j = (sum of k_idx over the tokens
t <= p of page j) / page, weights w = softmax(q(p) W_wgt), page score
s_j = sum_h w_h relu(q_idx_h . kp_j), and the top top_k/page pages among
pages 0..p//page; attention then runs over the tokens t <= p of those pages.

Every matrix product runs in float32 at ``highest`` precision. Nothing of
the program is imported; the weights are the benchmark's own.
``quant="fp8"`` computes every weight matrix product in float8 (e4m3) for
weights and inputs alike, one scale per weight column and per input row:
the lower-precision control that the comparison has to reject.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 128        # query rows per dense-attention block
SPARSE_ROWS = 64  # decode rows per sparse-attention block
MLP_ROWS = 1024   # rows per MLP block
SEQ_BLOCK = 1024  # sequences are padded to a multiple of this
DEC_BLOCK = 256   # decode spans are padded to a multiple of this
NEG = -1e30


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _dims(config: Dict) -> Tuple:
    c, mem = config, config["memory"]
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return (c["num_attention_heads"], c["num_key_value_heads"], hd,
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            bool(c["program"]["qk_norm"]), bool(c["program"]["qkv_bias"]),
            mem["index_heads"], mem["index_dim"], mem["top_k"], mem["page"],
            mem["min_context"], c["vocab_size"])


def _fp8(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3, one scale per slice along ``axis``."""
    a = a.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mat(w, quant):
    return _fp8(w, 0) if quant == "fp8" else w.astype(jnp.float32)


def _mm(x, w, quant):
    """x [..., in] @ w [in, out] in float32, or in float8 for the control."""
    if quant == "fp8":
        x = _fp8(x, -1)
    return x @ _mat(w, quant)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [T, H, hd], pos [T] -> rotate-half rope."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _dense_attention(q, k, v, n_tok):
    """Causal attention of every row; q [Tb,H,hd], k/v [Tb,KV,hd]."""
    Tb, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    kpos = jnp.arange(Tb)

    def block(i):
        r0 = i * ROWS
        qb = jax.lax.dynamic_slice_in_dim(q, r0, ROWS, 0)
        qb = qb.reshape(ROWS, KV, G, hd) / np.sqrt(hd)
        sc = jnp.einsum("rkgd,tkd->rkgt", qb, k)
        qpos = r0 + jnp.arange(ROWS)
        ok = (kpos[None] <= qpos[:, None]) & (kpos[None] < n_tok)
        sc = jnp.where(ok[:, None, None], sc, NEG)
        p = jax.nn.softmax(sc, -1)
        return jnp.einsum("rkgt,tkd->rkgd", p, v).reshape(ROWS, H, hd)

    out = jax.lax.map(block, jnp.arange(Tb // ROWS))
    return out.reshape(Tb, H, hd)


def _sparse_attention(q, k, v, kidx, q_idx, w, start, n_dec, dims):
    """DSA attention of the rows start..start+n_dec-1 (n_dec static)."""
    (H, KV, hd, _, _, _, _, Hi, di, top_k, page, _, _) = dims
    Tb = k.shape[0]
    G = H // KV
    n_pages = Tb // page
    n_sel = min(max(top_k // page, 1), n_pages)
    sums = kidx.reshape(n_pages, page, di).sum(1)             # whole pages
    tpos = jnp.arange(Tb)

    def block(i):
        r0 = start + i * SPARSE_ROWS
        pos = r0 + jnp.arange(SPARSE_ROWS)                    # [R]
        qi = jax.lax.dynamic_slice_in_dim(q_idx, r0, SPARSE_ROWS, 0)
        wi = jax.lax.dynamic_slice_in_dim(w, r0, SPARSE_ROWS, 0)
        cur = pos // page                                     # [R]
        # the current page holds only the tokens t <= p
        in_cur = ((tpos[None] // page) == cur[:, None]) & \
            (tpos[None] <= pos[:, None])                      # [R, Tb]
        part = jnp.einsum("rt,td->rd", in_cur.astype(jnp.float32), kidx)
        full = jnp.einsum("rhd,pd->rhp", qi, sums / page)     # [R,Hi,P]
        curd = jnp.einsum("rhd,rd->rh", qi, part / page)
        sc = jnp.einsum("rh,rhp->rp", wi, jax.nn.relu(full))
        scur = jnp.einsum("rh,rh->r", wi, jax.nn.relu(curd))
        pidx = jnp.arange(n_pages)[None]
        sc = jnp.where(pidx < cur[:, None], sc, NEG)
        sc = jnp.where(pidx == cur[:, None], scur[:, None], sc)
        top, sel = jax.lax.top_k(sc, n_sel)                   # [R, n_sel]
        tok = (sel[:, :, None] * page + jnp.arange(page)).reshape(
            SPARSE_ROWS, n_sel * page)
        ok = (jnp.repeat(top > NEG / 2, page, axis=1)
              & (tok <= pos[:, None]))
        kg, vg = k[tok], v[tok]                               # [R,N,KV,hd]
        qb = jax.lax.dynamic_slice_in_dim(q, r0, SPARSE_ROWS, 0)
        qb = qb.reshape(SPARSE_ROWS, KV, G, hd) / np.sqrt(hd)
        s = jnp.einsum("rkgd,rnkd->rkgn", qb, kg)
        s = jnp.where(ok[:, None, None], s, NEG)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("rkgn,rnkd->rkgd", p, vg).reshape(SPARSE_ROWS, H, hd)

    out = jax.lax.map(block, jnp.arange(n_dec // SPARSE_ROWS))
    return out.reshape(n_dec, H, hd)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _layer(x, lw, iw, n_tok, n_prompt, n_dec, dims, quant):
    (H, KV, hd, eps, theta, qk_norm, qkv_bias, Hi, di, _, _, min_ctx,
     _) = dims
    Tb = x.shape[0]
    a = lw["attn"]
    h = _rms(x, lw["attn_norm"]["w"], eps)
    q = _mm(h, a["wq"][:, : H * hd], quant)
    k = _mm(h, a["wk"], quant)
    v = _mm(h, a["wv"], quant)
    if qkv_bias:
        q = q + a["bq"][: H * hd].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q, k, v = q.reshape(Tb, H, hd), k.reshape(Tb, KV, hd), v.reshape(Tb, KV, hd)
    if qk_norm:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    pos = jnp.arange(Tb)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    out = _dense_attention(q, k, v, n_tok)
    if n_dec:
        qf = q.reshape(Tb, H * hd)
        q_idx = _mm(qf, iw["wq_idx"], quant).reshape(Tb, Hi, di)
        w = jax.nn.softmax(qf @ iw["w_wgt"].astype(jnp.float32), -1)
        kidx = _mm(k.reshape(Tb, KV * hd), iw["wk_idx"], quant)
        sp = _sparse_attention(q, k, v, kidx, q_idx, w, n_prompt, n_dec, dims)
        rows = n_prompt + jnp.arange(n_dec)
        use = (rows + 1 >= min_ctx)[:, None, None]
        dense_rows = jax.lax.dynamic_slice_in_dim(out, n_prompt, n_dec, 0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(use, sp, dense_rows), n_prompt, 0)
    x = x + _mm(out.reshape(Tb, H * hd), a["wo"][: H * hd], quant)
    m = lw["mlp"]
    w1, w3, w2 = (_mat(m[n], quant) for n in ("w1", "w3", "w2"))
    g = lw["mlp_norm"]["w"]
    mm = (lambda a, w: _fp8(a, -1) @ w) if quant == "fp8" else jnp.matmul

    def mlp(xb):
        hb = _rms(xb, g, eps)
        return xb + mm(jax.nn.silu(mm(hb, w1)) * mm(hb, w3), w2)

    xb = x.reshape(Tb // MLP_ROWS, MLP_ROWS, -1)
    return jax.lax.map(mlp, xb).reshape(Tb, -1)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _head(x, g, w_head, rows, query, dims, quant):
    """Logit statistics at ``rows``: (max over the vocabulary, its argmax,
    the logits of ``query`` [n, m])."""
    eps, V = dims[3], dims[-1]
    w = _mat(w_head, quant)[:, :V]

    def block(args):
        r, qt = args
        h = _rms(x[r], g, eps)
        lg = (_fp8(h, -1) if quant == "fp8" else h) @ w       # [R, V]
        return (lg.max(-1), jnp.argmax(lg, -1),
                jnp.take_along_axis(lg, qt, axis=1))

    n = rows.shape[0]
    R = 128
    rb, qb = rows.reshape(n // R, R), query.reshape(n // R, R, -1)
    mx, am, at = jax.lax.map(block, (rb, qb))
    return mx.reshape(n), am.reshape(n), at.reshape(n, -1)


def score(params, indexer, config: Dict, tokens: np.ndarray, n_prompt: int,
          rows: np.ndarray, query: np.ndarray,
          quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Reference logits over ``tokens`` (the prompt, then the served tokens
    fed back), read at positions ``rows``: the best logit, which token it
    is, and the logits of the tokens ``query[i]`` at row i."""
    dims = _dims(config)
    T = int(len(tokens))
    n_dec = _round_up(max(T - n_prompt, 1), DEC_BLOCK)
    Tb = _round_up(n_prompt + n_dec, SEQ_BLOCK)
    toks = np.zeros((Tb,), np.int32)
    toks[:T] = tokens
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][jnp.asarray(toks)].astype(jnp.float32)
        L = params["layers"]["mlp"]["w1"].shape[0]
        for layer in range(L):
            lw = jax.tree.map(lambda a: a[layer], params["layers"])
            iw = jax.tree.map(lambda a: a[layer], indexer)
            x = _layer(x, lw, iw, jnp.int32(T), jnp.int32(n_prompt), n_dec,
                       dims, quant)
        n = len(rows)
        nb = _round_up(n, 128)
        r = np.zeros((nb,), np.int32)
        r[:n] = rows
        qt = np.zeros((nb, query.shape[1]), np.int32)
        qt[:n] = query
        mx, am, at = _head(x, params["final_norm"]["w"],
                           params["lm_head"]["w"], jnp.asarray(r),
                           jnp.asarray(qt), dims, quant)
    return {"max": np.asarray(mx)[:n], "argmax": np.asarray(am)[:n],
            "at": np.asarray(at)[:n]}
