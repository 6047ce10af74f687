"""Plain reference of a dense GQA decoder, in float32, layer by layer.

It follows the architecture as the configuration file states it: pre-norm
blocks with RMSNorm, grouped-query attention with rotary embedding on the
first ``rope_fraction`` of each head (rotate-half pairing), optional
RMS-normalised queries and keys, a SwiGLU MLP and an untied output head.
Norm scales are ones, as drawn.

It imports nothing of the program under test and takes nothing it made.
The weights are drawn again from the seed by the serving weight recipe of
the program (``jax.random.normal`` in bfloat16, scaled by ``1/sqrt(fan_in)``,
the embedding by 0.02, each key split as the program's init splits it), one
layer at a time in float32, so that the whole model never has to be held
twice.  Matrix products run at ``highest`` precision.

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


def _keys(m: dict, seed: int):
    """(layer keys, embedding key, head key) as the program's init splits
    them: root -> 4; layers from the first, one key per period."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return jax.random.split(ks[0], m["n_layers"]), ks[1], \
        jax.random.fold_in(ks[1], 1)


def _draw(key, shape, scale):
    """As the program draws a weight: a bfloat16 normal times the scale, in
    bfloat16 (kept so: the products are widened where they are used)."""
    return jax.random.normal(key, shape, jnp.bfloat16) * scale


@partial(jax.jit, static_argnames="mt")
def _layer_weights(key, mt: tuple) -> dict:
    m = dict(mt)
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          _hd(m), m["d_ff"])
    blk = jax.random.split(jax.random.split(key, 1)[0], 4)
    ka = jax.random.split(blk[0], 4)
    km = jax.random.split(blk[1], 3)
    return {
        "wq": _draw(ka[0], (d, hq * hd), 1 / math.sqrt(d)),
        "wk": _draw(ka[1], (d, hkv * hd), 1 / math.sqrt(d)),
        "wv": _draw(ka[2], (d, hkv * hd), 1 / math.sqrt(d)),
        "wo": _draw(ka[3], (hq * hd, d), 1 / math.sqrt(hq * hd)),
        "w_gate": _draw(km[0], (d, ff), 1 / math.sqrt(d)),
        "w_up": _draw(km[1], (d, ff), 1 / math.sqrt(d)),
        "w_down": _draw(km[2], (ff, d), 1 / math.sqrt(ff)),
    }


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
    rot = int(hd * m.get("rope_fraction", 1.0))
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    freqs = m.get("rope_theta", 10000.0) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs   # (S, 1, half)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, rest], axis=-1)


@partial(jax.jit, static_argnames=("mt", "quant"))
def _layer(w, h, mt: tuple, quant: Optional[str]):
    m = dict(mt)
    w = _f32(w)
    s_len = h.shape[0]
    hq, hkv, hd, eps = m["n_heads"], m["n_kv_heads"], _hd(m), m["norm_eps"]
    rep = hq // hkv
    pos = jnp.arange(s_len)
    x = _rms(h, eps)
    q = _mm(x, w["wq"], quant).reshape(s_len, hkv, rep, hd)
    k = _mm(x, w["wk"], quant).reshape(s_len, hkv, hd)
    v = _mm(x, w["wv"], quant).reshape(s_len, hkv, hd)
    if m.get("qk_norm"):
        q, k = _rms(q, eps), _rms(k, eps)
    q = _rope(q.reshape(s_len, hq, hd), pos, m).reshape(s_len, hkv, rep, hd)
    k = _rope(k, pos, m)
    blocks = []
    for lo in range(0, s_len, Q_BLOCK):            # causal, in query blocks
        qb, qp = q[lo:lo + Q_BLOCK], pos[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k,
                        precision=HIGHEST) / math.sqrt(hd)
        sc = jnp.where(qp[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        blocks.append(jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST))
    o = jnp.concatenate(blocks, axis=0)
    h = h + _mm(o.reshape(s_len, hq * hd), w["wo"], quant)
    x = _rms(h, eps)
    g = _mm(x, w["w_gate"], quant)
    u = _mm(x, w["w_up"], quant)
    return h + _mm(jax.nn.silu(g) * u, w["w_down"], quant)


@partial(jax.jit, static_argnames=("mt", "quant", "rows"))
def _logits(h, lo, head, mt: tuple, quant: Optional[str], rows: int):
    h = jax.lax.dynamic_slice_in_dim(h, lo, rows, axis=0)
    return _mm(_rms(h, dict(mt)["norm_eps"]), _f32(head), quant)


def logits(m: dict, seed: int, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
           quant: Optional[str] = None) -> List[jax.Array]:
    """For each ``(prompt, served)`` pair: the logits at the positions that
    produced the served tokens, the prompt followed by the served tokens
    being the input; ``(rows, vocab)`` with ``rows`` the served count
    rounded up to a multiple of ``OUT_PAD`` (the rows past it are padding)."""
    mt = tuple(sorted((k, v) for k, v in m.items()
                      if isinstance(v, (int, float, str, bool))))
    layer_keys, k_embed, k_head = _keys(m, seed)
    hs, spans = [], []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n, rows = len(toks), -(-len(served) // OUT_PAD) * OUT_PAD
        padded = np.zeros(-(-(len(prompt) - 1 + rows) // PAD) * PAD, np.int32)
        padded[:n] = toks
        hs.append(_embed_rows(k_embed, jnp.asarray(padded), mt))
        spans.append((len(prompt) - 1, rows))
    for i in range(m["n_layers"]):
        w = _layer_weights(layer_keys[i], mt)
        hs = [_layer(w, h, mt, quant) for h in hs]
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
