"""Plain reference of an EXAONE-MoE decoder (K-EXAONE), in float32, layer by
layer and in query blocks.

The block, as the EXAONE 4.0 report (arXiv:2507.11407) gives it and the
``exaone_moe`` family keeps it (QK-Reorder-LN), for a layer input h:

    q, k, v = h Wq, h Wk, h Wv                  (64 query, 8 KV heads of 128)
    q, k    = RMSNorm_hd(q), RMSNorm_hd(k)      (each head, over its 128)
    q, k    = RoPE(q), RoPE(k)                  (sliding layers only; full
                                                 layers of the hybrid: NoPE)
    a       = softmax(q k^T / sqrt(128) + M) v  (M: causal, and on a sliding
                                                 layer keys i - j < window)
    h       = h + RMSNorm(a Wo)
    h       = h + RMSNorm(FFN(h))

and a final RMSNorm before the untied head.  No norm precedes a sublayer.
FFN is a SwiGLU MLP on the leading dense layers, and on the others the
expert layer, with routing as DeepSeek-V3 defines it (sigmoid scores, no
groups):

    s   = sigmoid(h W_r)                        (float32, all n_experts)
    T   = top_k(s + b)                          (b, the correction bias: 0)
    w_i = s_i / sum_{j in T} s_j * routed_scale (i in T)
    FFN = sum_{i in T, i held} w_i E_i(h) + S(h)

with S the shared expert, added unweighted, and only the experts held on
this chip (``first_held`` .. ``first_held + n_held - 1``) computed: the
share the configuration states, as the program computes it.  Norm scales
are ones, as drawn.  The multi-token-prediction layer drafts tokens for
self-speculative decoding only and is not part of the served forward pass.

It imports nothing of the program under test and takes nothing it made.
The weights are drawn again from the seed by the serving weight recipe of
the program (``jax.random.normal`` in bfloat16, scaled by ``1/sqrt(fan_in)``,
the embedding by 0.02, the router in float32, each expert from a key of its
own, each key split as the program's init splits it, the layers grouped in
the program's scan period), one layer at a time, in float32.  Matrix
products run at ``highest`` precision.

``quant="fp8"`` computes every weight product with both operands rounded to
float8 e4m3 (per-tensor scale for the weight, per-row for the activation):
the control, one precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 1024                     # sequences are right-padded to a multiple
Q_BLOCK = 512                  # query rows of one attention block
OUT_PAD = 128                  # logit rows are computed in multiples
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def _spec(m: dict, i: int) -> Tuple[bool, Optional[int]]:
    """Layer ``i``: (expert layer?, window or None)."""
    pattern = m.get("block_pattern", ["attn"])
    k = m.get("first_k_dense", 0)
    moe = i >= k and pattern[(i - k) % len(pattern)] == "attn_moe"
    types = m.get("layer_types")
    full = types is not None and types[i] == "full_attention"
    return moe, None if full else m.get("window")


def _period(m: dict) -> int:
    """Layers a key of the program's scan covers: the shortest run of
    layers that repeats to the whole stack."""
    n, step = m["n_layers"], len(m.get("block_pattern", ["attn"]))
    if m.get("first_k_dense", 0):
        step = 1
    spec = [_spec(m, i) for i in range(n)]
    for p in range(step, n + 1, step):
        if n % p == 0 and all(spec[i] == spec[i % p] for i in range(n)):
            return p
    raise ValueError(f"no period for {m['n_layers']} layers")


def _keys(m: dict, seed: int):
    """(layer keys, embedding key, head key) as the program's init splits
    them: root -> 4; layers from the first, one key per period, split again
    over the layers of the period."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = _period(m)
    periods = jax.random.split(ks[0], m["n_layers"] // p)
    layer_keys = [jax.random.split(periods[i // p], p)[i % p]
                  for i in range(m["n_layers"])]
    return layer_keys, ks[1], jax.random.fold_in(ks[1], 1)


def _draw(key, shape, scale):
    """As the program draws a weight: a bfloat16 normal times the scale, in
    bfloat16 (kept so: the products are widened where they are used)."""
    return jax.random.normal(key, shape, jnp.bfloat16) * scale


def _attn_weights(key, m):
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], _hd(m)
    ka = jax.random.split(key, 4)
    return {
        "wq": _draw(ka[0], (d, hq * hd), 1 / math.sqrt(d)),
        "wk": _draw(ka[1], (d, hkv * hd), 1 / math.sqrt(d)),
        "wv": _draw(ka[2], (d, hkv * hd), 1 / math.sqrt(d)),
        "wo": _draw(ka[3], (hq * hd, d), 1 / math.sqrt(hq * hd)),
    }


def _mlp_weights(key, d, ff):
    km = jax.random.split(key, 3)
    return {"w_gate": _draw(km[0], (d, ff), 1 / math.sqrt(d)),
            "w_up": _draw(km[1], (d, ff), 1 / math.sqrt(d)),
            "w_down": _draw(km[2], (ff, d), 1 / math.sqrt(ff))}


@partial(jax.jit, static_argnames=("mt", "moe"))
def _layer_weights(key, mt: tuple, moe: bool) -> dict:
    m = dict(mt)
    e = dict(m["moe"]) if moe else None
    d = m["d_model"]
    blk = jax.random.split(key, 4)
    w = {"attn": _attn_weights(blk[0], m)}
    if not moe:
        w["mlp"] = _mlp_weights(blk[1], d, m["d_ff"])
        return w
    ff, n = e["d_ff_expert"], e["n_experts"]
    first = e.get("first_held", 0)
    held = e.get("n_held") or n
    k5 = jax.random.split(blk[1], 5)

    def experts(k, shape):
        keys = jax.random.split(k, n)[first:first + held]
        return jax.vmap(
            lambda kk: jax.random.normal(kk, shape, jnp.bfloat16))(keys)

    w["router"] = jax.random.normal(k5[0], (d, n), jnp.float32) * (
        1 / math.sqrt(d))
    w["w_gate"] = experts(k5[1], (d, ff)) * (1 / math.sqrt(d))
    w["w_up"] = experts(k5[2], (d, ff)) * (1 / math.sqrt(d))
    w["w_down"] = experts(k5[3], (ff, d)) / math.sqrt(ff)
    w["shared"] = _mlp_weights(k5[4], d, ff * e.get("n_shared_experts", 0))
    return w


@partial(jax.jit, static_argnames="mt")
def _embed_rows(key, tokens, mt: tuple):
    m = dict(mt)
    table = jax.random.normal(key, (m["vocab"], m["d_model"]),
                              jnp.bfloat16) * 0.02
    return table[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames="mt")
def _head_weights(key, mt: tuple):
    m = dict(mt)
    return _draw(key, (m["d_model"], m["vocab"]), 1 / math.sqrt(m["d_model"]))


def _f32(w):
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32), s


def _mm(x, w, quant):
    if quant == "fp8":
        xq, sx = _quant(x, -1)
        wq, sw = _quant(w, None)
        return jnp.matmul(xq, wq, precision=HIGHEST) * sx * sw
    return jnp.matmul(x, w, precision=HIGHEST)


def _rope(x, pos, m):
    hd = x.shape[-1]
    half = hd // 2
    freqs = m.get("rope_theta", 10000.0) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs   # (S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _swiglu(x, w, quant):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"], quant))
               * _mm(x, w["w_up"], quant), w["w_down"], quant)


def _experts(x, w, e: dict, quant):
    """The expert layer on ``x`` (S, d): the held experts' weighted sum and
    the shared expert."""
    n, k = e["n_experts"], e["top_k"]
    first = e.get("first_held", 0)
    held = w["w_gate"].shape[0]
    logits = jnp.matmul(x, w["router"], precision=HIGHEST)     # (S, n)
    s = jax.nn.sigmoid(logits)
    top_v, top_i = jax.lax.top_k(s, k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True) * e["routed_scale"]
    # each held expert's weight for each token (0 where not picked)
    coef = jnp.sum(jnp.where(top_i[:, :, None]
                             == first + jnp.arange(held)[None, None, :],
                             top_v[:, :, None], 0.0), axis=1)  # (S, held)
    out = _swiglu(x, w["shared"], quant)
    for i in range(held):
        wi = {name: w[name][i] for name in ("w_gate", "w_up", "w_down")}
        out = out + coef[:, i:i + 1] * _swiglu(x, wi, quant)
    return out


@partial(jax.jit, static_argnames=("mt", "moe", "window", "quant"))
def _layer(w, h, mt: tuple, moe: bool, window: Optional[int],
           quant: Optional[str]):
    m = dict(mt)
    w = _f32(w)
    s_len = h.shape[0]
    hq, hkv, hd, eps = m["n_heads"], m["n_kv_heads"], _hd(m), m["norm_eps"]
    rep = hq // hkv
    pos = jnp.arange(s_len)
    a = w["attn"]
    q = _rms(_mm(h, a["wq"], quant).reshape(s_len, hq, hd), eps)
    k = _rms(_mm(h, a["wk"], quant).reshape(s_len, hkv, hd), eps)
    v = _mm(h, a["wv"], quant).reshape(s_len, hkv, hd)
    if window is not None or m.get("full_rope", True):
        q, k = _rope(q, pos, m), _rope(k, pos, m)
    q = q.reshape(s_len, hkv, rep, hd)
    blocks = []
    for lo in range(0, s_len, Q_BLOCK):            # causal, in query blocks
        qb, qp = q[lo:lo + Q_BLOCK], pos[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k,
                        precision=HIGHEST) / math.sqrt(hd)
        seen = qp[:, None] >= pos[None, :]
        if window is not None:
            seen &= qp[:, None] - pos[None, :] < window
        sc = jnp.where(seen, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        blocks.append(jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST))
    o = jnp.concatenate(blocks, axis=0).reshape(s_len, hq * hd)
    h = h + _rms(_mm(o, a["wo"], quant), eps)
    f = _experts(h, w, dict(m["moe"]), quant) if moe else \
        _swiglu(h, w["mlp"], quant)
    return h + _rms(f, eps)


@partial(jax.jit, static_argnames=("mt", "quant", "rows"))
def _logits(h, lo, head, mt: tuple, quant: Optional[str], rows: int):
    h = jax.lax.dynamic_slice_in_dim(h, lo, rows, axis=0)
    return _mm(_rms(h, dict(mt)["norm_eps"]), _f32(head), quant)


def _frozen(m: dict) -> tuple:
    """The configuration as a hashable static argument."""
    def f(v):
        if isinstance(v, dict):
            return tuple(sorted((k, f(x)) for k, x in v.items()))
        if isinstance(v, list):
            return tuple(f(x) for x in v)
        return v
    return f(m)


def logits(m: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
           quant: Optional[str] = None) -> List[jax.Array]:
    """For each ``(prompt, served)`` pair: the logits at the positions that
    produced the served tokens, the prompt followed by the served tokens
    being the input; ``(rows, vocab)`` with ``rows`` the served count
    rounded up to a multiple of ``OUT_PAD`` (the rows past it are padding)."""
    mt = _frozen(m)
    layer_keys, k_embed, k_head = _keys(m, seed)
    hs, spans = [], []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n, rows = len(toks), -(-len(served) // OUT_PAD) * OUT_PAD
        padded = np.zeros(-(-(len(prompt) - 1 + rows) // PAD) * PAD, np.int32)
        padded[:n] = toks
        hs.append(_embed_rows(k_embed, jnp.asarray(padded), mt))
        spans.append((len(prompt) - 1, rows))
    with jax.default_matmul_precision("highest"):
        for i in range(m["n_layers"]):
            moe, window = _spec(m, i)
            w = _layer_weights(layer_keys[i], mt, moe)
            hs = [_layer(w, h, mt, moe, window, quant) for h in hs]
            del w
        head = _head_weights(k_head, mt)
        out = [_logits(h, lo, head, mt, quant, rows)
               for h, (lo, rows) in zip(hs, spans)]
    del head, hs
    return out


@jax.jit
def gap_of(ref_logits, tokens):
    """Per position: how far the reference's logit of ``tokens`` lies below
    its best."""
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - picked
