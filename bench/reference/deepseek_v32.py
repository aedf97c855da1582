"""Plain float32 reference of DeepSeek-V3.2-Exp's decoder as a chip of an
expert-parallel deployment holds it: multi-head latent attention with YaRN
rope, the lightning indexer choosing the tokens each decode position
attends, dense FFN layers, then MoE layers of which the held experts' part
and the shared expert are computed.

Equations, per layer, for a sequence of tokens x_0..x_{T-1}:

    h           = rmsnorm(x) * g_attn
    c_q         = rmsnorm(h W_qa) * g_q                   [1536]
    q_n | q_r   = c_q W_qb, per head [128 | 64]           128 heads
    c_kv | k_r  = h W_kva;  c_kv = rmsnorm(c_kv) * g_kv   [512 | 64]
    q_r, k_r    = rope(q_r, p), rope(k_r, p)              YaRN frequencies
    k_n | v     = c_kv W_kvb, per head [128 | 128]        (expanded here)
    attn        = softmax((q_n . k_n + q_r . k_r) * scale) v
                  over the keys position p attends
    x           = x + attn W_o
    x           = x + ffn(rmsnorm(x) * g_mlp)
    logits      = (rmsnorm(x) * g_final) W_head

YaRN (DeepSeek-V3's formula): inverse frequencies theta^(-2i/64), divided
by ``factor`` below the correction dim of ``beta_slow`` rotations over the
original context and kept above that of ``beta_fast``, ramped linearly in
between; scale = 192^-1/2 * (1 + 0.1 mscale_all_dim ln factor)^2. Rope pairs
dim i with dim i + 32 of the 64 (the published checkpoint pairs neighbours;
with drawn weights that is a fixed permutation of the rope columns).

ffn: the dense layers silu(h W1) * (h W3) W2; an MoE layer the router
s = sigmoid(h W_gate) over all 256 experts, the 4 groups of 8 whose two
best (s + bias) sum highest, the 8 best (s + bias) inside them, weights s
of those 8 normalised to sum 1 and scaled by 2.5, and sum over the chip's
held experts (0..n_routed_experts-1 of the file) of weight * expert(h),
plus the shared expert.

Which keys position p attends: every t <= p for a prompt position (p < P,
the prompt length); for a decode position the min(2048, p + 1) tokens t <= p
of highest index score s_t = sum_h w_h relu(q_idx_h . k_idx(t)), with
k_idx(t) = rope(LayerNorm(h(t) W_k)) (rope on its first 64 dims), q_idx =
rope(c_q W_qb_idx) (64 heads of 128), w = h(p) W_w * 64^-1/2 * 128^-1/2.

Departures from the published model, shared with the program:
  * prompt positions attend densely (the published model selects for them
    too);
  * no multi-token-prediction module;
  * the indexer in bfloat16 weights without its float8 quantisation and
    Hadamard rotation (the rotation is orthogonal, so in exact arithmetic
    the scores are the same).

Every matrix product runs in float32 at ``highest`` precision, blocked so
that a 20k-token sequence fits on one chip beside the weights: attention
per group of heads and per block of rows, the FFN per expert and per 2,048
columns of the dense width (one chunk's float32 weights at a time), the
head per block of the vocabulary. Nothing of the program is imported.
``quant="fp8"`` computes every weight matrix product in float8 (e4m3),
weights and inputs alike: the control the comparison has to reject.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 32          # query rows per attention block
HEAD_GROUP = 16    # heads per attention pass
SEL_ROWS = 16      # decode rows per selection block
VOCAB_BLOCK = 16384  # most vocabulary columns per head block
SEQ_BLOCK = 1024   # sequences are padded to a multiple of this
DEC_BLOCK = 256    # decode spans are padded to a multiple of this
NEG = -1e30


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _dims(config: Dict) -> Tuple:
    c, mem, rs = config, config["memory"], config["rope_scaling"]
    return (c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale_all_dim"]),
            mem["index_heads"], mem["index_dim"], mem["top_k"],
            c["n_group"], c["topk_group"], c["num_experts_per_tok"],
            float(c["routed_scaling_factor"]), bool(c["norm_topk_prob"]),
            int(config["program"]["first_held_expert"]), c["vocab_size"])


def _scale8(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """float8 e4m3 scales, one per slice along ``axis``."""
    s = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=axis,
                keepdims=True) / 448.0
    return jnp.where(s > 0, s, 1.0)


def _fp8_scaled(a: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    a = a.astype(jnp.float32)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3, one scale per slice along ``axis``."""
    return _fp8_scaled(a, _scale8(a, axis))


def _mat(w, quant):
    """A weight [..., in, out] in float32 (or float8, one scale a column)."""
    return _fp8(w, -2) if quant == "fp8" else w.astype(jnp.float32)


def _act(x, quant):
    return _fp8(x, -1) if quant == "fp8" else x


def _mm(x, w, quant):
    return _act(x, quant) @ _mat(w, quant)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _inv_freq(dims) -> np.ndarray:
    (_, _, _, _, dr, _, _, theta, factor, orig, beta_fast, beta_slow,
     *_) = dims
    base = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    if factor <= 1:
        return base

    def corr_dim(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr_dim(beta_fast)), 0)
    hi = min(math.ceil(corr_dim(beta_slow)), dr - 1)
    if lo == hi:
        hi += 0.001
    keep = 1.0 - np.clip((np.arange(dr // 2) - lo) / (hi - lo), 0.0, 1.0)
    return base * keep + base / factor * (1.0 - keep)


def _scale(dims) -> float:
    dn, dr, factor, mscale = dims[3], dims[4], dims[8], dims[12]
    s = 1.0 / math.sqrt(dn + dr)
    if factor > 1:
        s *= (1.0 + 0.1 * mscale * math.log(factor)) ** 2
    return s


def _rope(x, pos, inv):
    """x [T, ..., 2m] rotated over its last dim (dim i with i + m) by the
    angles pos * inv [m]."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    m = x.shape[-1] // 2
    x1, x2 = x[..., :m], x[..., m:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _selection(kidx, q_idx, w, n_prompt, n_dec, top_k):
    """[n_dec, Tb] bool: the tokens each decode row attends."""
    Tb = kidx.shape[0]
    tpos = jnp.arange(Tb)
    k = min(top_k, Tb)

    def block(i):
        r0 = i * SEL_ROWS
        pos = n_prompt + r0 + jnp.arange(SEL_ROWS)
        qi = jax.lax.dynamic_slice_in_dim(q_idx, r0, SEL_ROWS, 0)
        wi = jax.lax.dynamic_slice_in_dim(w, r0, SEL_ROWS, 0)
        s = jnp.einsum("rh,rht->rt", wi,
                       jax.nn.relu(jnp.einsum("rhd,td->rht", qi, kidx)))
        causal = tpos[None] <= pos[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        _, ids = jax.lax.top_k(s, k)
        chosen = jnp.zeros((SEL_ROWS, Tb), bool).at[
            jnp.arange(SEL_ROWS)[:, None], ids].set(True)
        return chosen & causal

    return jax.lax.map(block, jnp.arange(n_dec // SEL_ROWS)).reshape(
        n_dec, Tb)


def _attention(a, c_q, c_kv, k_r, pos, sel, n_prompt, dims, quant):
    """sum over heads of attn_h W_o[h] -> [Tb, d], one group of heads at a
    time: each group's keys and values expanded from the latent."""
    (H, ql, dl, dn, dr, dv, *_) = dims
    Tb = c_q.shape[0]
    inv, scale = _inv_freq(dims), _scale(dims)
    n_dec = sel.shape[0]
    tpos = jnp.arange(Tb)
    hg = math.gcd(H, HEAD_GROUP)
    wqb = a["wq_b"].reshape(ql, H // hg, hg * (dn + dr))
    wkvb = a["wkv_b"].reshape(dl, H // hg, hg * (dn + dv))
    wo = a["wo"].reshape(H // hg, hg * dv, -1)

    def group(acc, g):
        q = _mm(c_q, wqb[:, g], quant).reshape(Tb, hg, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv)], -1)
        kv = _mm(c_kv, wkvb[:, g], quant).reshape(Tb, hg, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_r[:, None], (Tb, hg, dr))], -1)
        v = kv[..., dn:]

        def rows(i):
            r0 = i * ROWS
            qb = jax.lax.dynamic_slice_in_dim(q, r0, ROWS, 0)
            s = jnp.einsum("rhe,the->rht", qb, k) * scale
            rp = r0 + jnp.arange(ROWS)
            causal = tpos[None] <= rp[:, None]
            d = jnp.clip(rp - n_prompt, 0, n_dec - 1)
            ok = jnp.where((rp >= n_prompt)[:, None], sel[d], causal)
            s = jnp.where(ok[:, None], s, NEG)
            return jnp.einsum("rht,thv->rhv", jax.nn.softmax(s, -1), v)

        out = jax.lax.map(rows, jnp.arange(Tb // ROWS)).reshape(Tb, hg * dv)
        return acc + _mm(out, wo[g], quant), None

    acc, _ = jax.lax.scan(group, jnp.zeros((Tb, wo.shape[-1]), jnp.float32),
                          jnp.arange(H // hg))
    return acc


def _ffn(h, w1, w3, w2, gate, n, quant, s2=None):
    """sum over c < n of (silu(h W1_c) * (h W3_c) * gate[:, c]) W2_c, each
    chunk's weights taken as ``w1(c)`` [d, f], ``w3(c)``, ``w2(c)`` [f, d]
    and made float32 one chunk at a time. ``s2``: the float8 scales of the
    whole W2 a chunk is cut from (a chunk's own max otherwise)."""
    hq = _act(h, quant)

    def step(acc, c):
        a = jax.nn.silu(hq @ _mat(w1(c), quant)) * (hq @ _mat(w3(c), quant))
        a = _act(a * gate[:, c][:, None], quant)
        w = w2(c)
        w = _fp8_scaled(w, s2) if quant == "fp8" and s2 is not None \
            else _mat(w, quant)
        return acc + a @ w, None

    acc, _ = jax.lax.scan(step, jnp.zeros(h.shape, jnp.float32),
                          jnp.arange(n))
    return acc


def _mlp(p, h, quant, chunk: int = 2048):
    """The dense FFN, its width cut in chunks of ``chunk``."""
    ff = p["w1"].shape[1]
    f = math.gcd(ff, chunk)
    cols = lambda w: lambda c: jax.lax.dynamic_slice_in_dim(w, c * f, f, 1)
    rows = lambda c: jax.lax.dynamic_slice_in_dim(p["w2"], c * f, f, 0)
    ones = jnp.ones((h.shape[0], ff // f), jnp.float32)
    return _ffn(h, cols(p["w1"]), cols(p["w3"]), rows, ones, ff // f, quant,
                s2=_scale8(p["w2"], -2) if quant == "fp8" else None)


def _moe(m, h, dims, quant):
    """Held experts' part plus the shared expert, h [R, d] -> [R, d]."""
    (*_, n_group, topk_group, top_e, route_scale, norm, first, _) = dims
    R = h.shape[0]
    s = jax.nn.sigmoid(h @ m["gate"].astype(jnp.float32))         # [R, E]
    E = s.shape[-1]
    b = (s + m["bias"]).reshape(R, n_group, E // n_group)
    gsc = jax.lax.top_k(b, 2)[0].sum(-1)                           # [R, G]
    gth = jax.lax.top_k(gsc, topk_group)[0][:, -1:]
    b = jnp.where((gsc >= gth)[..., None], b, -jnp.inf).reshape(R, E)
    eth = jax.lax.top_k(b, top_e)[0][:, -1:]
    w = jnp.where(b >= eth, s, 0.0)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    w = w * route_scale
    n_held = m["w1"].shape[0]
    wh = jax.lax.dynamic_slice_in_dim(w, first, n_held, 1)         # [R, Eh]
    expert = lambda name: lambda c: m[name][c]
    y = _ffn(h, expert("w1"), expert("w3"), expert("w2"), wh, n_held, quant)
    return y + _mlp(m["shared"], h, quant)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _layer(x, stack, i, indexer, layer, n_prompt, n_dec, dims, quant):
    """Layer ``i`` of a stack (layer ``layer`` of the model) over x."""
    (H, ql, dl, dn, dr, dv, eps, *_rest) = dims
    Hi, di, top_k = dims[13], dims[14], dims[15]
    Tb = x.shape[0]
    lw = jax.tree.map(lambda a: a[i], stack)
    iw = jax.tree.map(lambda a: a[layer], indexer)
    a = lw["attn"]
    pos = jnp.arange(Tb)
    inv = _inv_freq(dims)
    h = _rms(x, lw["attn_norm"]["w"], eps)
    c_q = _rms(_mm(h, a["wq_a"], quant), a["q_norm"], eps)
    kv = _mm(h, a["wkv_a"], quant)
    c_kv = _rms(kv[:, :dl], a["kv_norm"], eps)
    k_r = _rope(kv[:, dl:], pos, inv)
    # the indexer, at the decode rows
    kidx = _layer_norm(_mm(h, iw["wk"], quant), iw["k_norm"]["w"],
                       iw["k_norm"]["b"], eps)
    kidx = jnp.concatenate([_rope(kidx[:, :dr], pos, inv), kidx[:, dr:]], -1)
    rows = jax.lax.dynamic_slice_in_dim(pos, n_prompt, n_dec, 0)
    cq_d = jax.lax.dynamic_slice_in_dim(c_q, n_prompt, n_dec, 0)
    h_d = jax.lax.dynamic_slice_in_dim(h, n_prompt, n_dec, 0)
    q_idx = _mm(cq_d, iw["wq_b"], quant).reshape(n_dec, Hi, di)
    q_idx = jnp.concatenate([_rope(q_idx[..., :dr], rows, inv),
                             q_idx[..., dr:]], -1)
    w = _mm(h_d, iw["w_proj"], quant) * (Hi ** -0.5) * (di ** -0.5)
    sel = _selection(kidx, q_idx, w, n_prompt, n_dec, top_k)
    x = x + _attention(a, c_q, c_kv, k_r, pos, sel, n_prompt, dims, quant)
    h = _rms(x, lw["mlp_norm"]["w"], eps)
    if "moe" in lw:
        return x + _moe(lw["moe"], h, dims, quant)
    return x + _mlp(lw["mlp"], h, quant)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _head(x, g, w_head, rows, query, dims, quant):
    """Logit statistics at ``rows``: (max over the vocabulary, its argmax,
    the logits of ``query`` [n, m]), one block of the vocabulary at a
    time."""
    eps, V = dims[6], dims[-1]
    h = _act(_rms(x[rows], g, eps), quant)                        # [n, d]
    Vp = w_head.shape[1]
    vb = max([b for b in range(128, VOCAB_BLOCK + 1, 128) if Vp % b == 0],
             default=Vp)
    nb = Vp // vb

    def block(carry, j):
        mx, am, at = carry
        w = jax.lax.dynamic_slice_in_dim(w_head, j * vb, vb, 1)
        lg = h @ _mat(w, quant)                                   # [n, vb]
        ids = j * vb + jnp.arange(vb)
        lg = jnp.where(ids[None] < V, lg, -jnp.inf)
        bm = lg.max(-1)
        am = jnp.where(bm > mx, j * vb + jnp.argmax(lg, -1), am)
        hit = (query >= j * vb) & (query < (j + 1) * vb)
        q = jnp.take_along_axis(lg, jnp.clip(query - j * vb, 0, vb - 1),
                                axis=1)
        return (jnp.maximum(mx, bm), am, jnp.where(hit, q, at)), None

    n = rows.shape[0]
    init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,), jnp.int32),
            jnp.zeros(query.shape, jnp.float32))
    (mx, am, at), _ = jax.lax.scan(block, init, jnp.arange(nb))
    return mx, am, at


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
        layer = 0
        for stack in ("dense_layers", "moe_layers"):
            n = params[stack]["attn_norm"]["w"].shape[0]
            for i in range(n):
                x = _layer(x, params[stack], jnp.int32(i), indexer,
                           jnp.int32(layer), jnp.int32(n_prompt), n_dec,
                           dims, quant)
                layer += 1
        m = len(rows)
        nb = _round_up(m, 128)
        r = np.zeros((nb,), np.int32)
        r[:m] = rows
        qt = np.zeros((nb, query.shape[1]), np.int32)
        qt[:m] = query
        mx, am, at = _head(x, params["final_norm"]["w"],
                           params["lm_head"]["w"], jnp.asarray(r),
                           jnp.asarray(qt), dims, quant)
    return {"max": np.asarray(mx)[:m], "argmax": np.asarray(am)[:m],
            "at": np.asarray(at)[:m]}
