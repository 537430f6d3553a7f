"""Plain float32 reference of a llama-style decoder, dense or top-k MoE.

Written from the layer equations, with no kernel, cache or batching of the
program under test and nothing imported from it. Weights are made here
from the seed with the same draws as the model's initialisation (one
normal per matrix, scaled by 1/sqrt(fan-in), rounded to the stated
parameter dtype), layer by layer, so the reference never holds more than
one layer's weights next to the embedding rows it needs.

Layer equations (pre-norm):
    h  = x + Wo · attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))
    x' = h + ffn(n2(h))
    ffn(y) = Wd · (silu(Wg·y) * (Wu·y))                       dense
    ffn(y) = sum_{e in top-k} softmax(top-k router logits)_e · ffn_e(y)   MoE
    logits = Whead · nf(x_L)
with RMSNorm n(x) = x / sqrt(mean(x^2) + eps) · scale (scales are ones at
initialisation), causal softmax attention at scale 1/sqrt(head_dim) and
half-split rotary embeddings.

``mode="fp8"`` is the control: every weight matrix and every activation
that enters a weight matmul goes through float8 e4m3 with a per-channel
scale (per output column for weights, per token for activations).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    n_layers: int
    d: int
    h: int
    hkv: int
    hd: int
    ff: int
    vocab: int
    experts: int
    top_k: int
    eps: float
    theta: float
    param_dtype: str

    @classmethod
    def of(cls, m: dict) -> "Dims":
        moe = m.get("moe") or {}
        return cls(m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
                   m.get("head_dim") or m["d_model"] // m["n_heads"],
                   moe.get("d_ff_expert", m["d_ff"]), m["vocab"],
                   moe.get("num_experts", 0), moe.get("top_k", 0),
                   float(m.get("norm_eps", 1e-5)), float(m.get("rope_theta", 1e4)),
                   m.get("param_dtype", "float32"))


# ---------------------------------------------------------------- weights


def _normal(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _embed_table(key, dm: Dims):
    ke, _ = jax.random.split(key, 2)
    return (jax.random.normal(ke, (dm.vocab, dm.d)) * 0.02).astype(dm.param_dtype)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _head(key, dm: Dims):
    _, kh = jax.random.split(key, 2)
    return _normal(kh, (dm.d, dm.vocab), dm.d, dm.param_dtype)


@functools.partial(jax.jit, static_argnums=1)
def _layer_weights(key, dm: Dims) -> dict:
    ka, kf = jax.random.split(key, 2)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    pd = dm.param_dtype
    w = {
        "wq": _normal(kq, (dm.d, dm.h * dm.hd), dm.d, pd),
        "wk": _normal(kk, (dm.d, dm.hkv * dm.hd), dm.d, pd),
        "wv": _normal(kv, (dm.d, dm.hkv * dm.hd), dm.d, pd),
        "wo": _normal(ko, (dm.h * dm.hd, dm.d), dm.h * dm.hd, pd),
    }
    if dm.experts:
        kr, kg, ku, kd = jax.random.split(kf, 4)
        e = dm.experts
        w["router"] = _normal(kr, (dm.d, e), dm.d, jnp.float32)
        w["wg"] = _normal(kg, (e, dm.d, dm.ff), dm.d, pd)
        w["wu"] = _normal(ku, (e, dm.d, dm.ff), dm.d, pd)
        w["wd"] = _normal(kd, (e, dm.ff, dm.d), dm.ff, pd)
    else:
        kg, ku, kd = jax.random.split(kf, 3)
        w["wg"] = _normal(kg, (dm.d, dm.ff), dm.d, pd)
        w["wu"] = _normal(ku, (dm.d, dm.ff), dm.d, pd)
        w["wd"] = _normal(kd, (dm.ff, dm.d), dm.ff, pd)
    return w


def model_keys(seed: int, n_layers: int):
    """(head key, per-layer keys) as the model's initialisation splits them."""
    kh, ks, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return kh, jax.random.split(ks, n_layers)


# ---------------------------------------------------------------- forward


def _fp8(x, axis):
    """Round through float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    """x (..., k) @ w (k, n) in float32, through fp8 in the control."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, dm: Dims):
    """Causal softmax attention of one sequence: q (S, H, hd), k/v (S, Hkv, hd)."""
    s = q.shape[0]
    g = dm.h // dm.hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(dm.hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def _ffn(y, w, dm: Dims, mode):
    if not dm.experts:
        return _mm(jax.nn.silu(_mm(y, w["wg"], mode)) * _mm(y, w["wu"], mode), w["wd"], mode)
    logits = jnp.matmul(y, w["router"], precision=HIGHEST)   # (S, E)
    top, sel = jax.lax.top_k(logits, dm.top_k)
    gate = jax.nn.softmax(top, axis=-1)                       # renormalised top-k
    weight = jnp.zeros_like(logits).at[jnp.arange(y.shape[0])[:, None], sel].set(gate)

    def expert(acc, e):
        we = jax.tree.map(lambda a: a[e], {k: w[k] for k in ("wg", "wu", "wd")})
        out = _mm(jax.nn.silu(_mm(y, we["wg"], mode)) * _mm(y, we["wu"], mode), we["wd"], mode)
        return acc + weight[:, e, None] * out, None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(y), jnp.arange(dm.experts))
    return acc


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, dm: Dims, mode):
    """One layer over one sequence x (S, d) that starts at position 0."""
    pos = jnp.arange(x.shape[0])
    n = _norm(x, dm.eps)
    q = _rope(_mm(n, w["wq"], mode).reshape(-1, dm.h, dm.hd), pos, dm.theta)
    k = _rope(_mm(n, w["wk"], mode).reshape(-1, dm.hkv, dm.hd), pos, dm.theta)
    v = _mm(n, w["wv"], mode).reshape(-1, dm.hkv, dm.hd)
    h = x + _mm(_attention(q, k, v, dm).reshape(-1, dm.h * dm.hd), w["wo"], mode)
    return h + _ffn(_norm(h, dm.eps), w, dm, mode)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(x, head, dm: Dims, mode):
    return _mm(_norm(x, dm.eps), head, mode)


@jax.jit
def _gap_stats(ref, targets):
    """Gaps by which the logit of ``targets`` (-1: not compared) lies below
    the best logit of ``ref`` (S, V): (widest, sum, count compared)."""
    got = jnp.take_along_axis(ref, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
    on = targets >= 0
    gap = jnp.where(on, ref.max(axis=-1) - got, 0.0)
    return gap.max(), gap.sum(), on.sum()


@jax.jit
def _first(logits, targets):
    return jnp.where(targets >= 0, jnp.argmax(logits, axis=-1), -1)


def forward(m: dict, seed: int, tokens: np.ndarray, modes=("f32",)):
    """(dims, output head, {mode: [final hidden states (S, d) per row]})
    for tokens (B, S) int32, each row a sequence from position 0."""
    dm = Dims.of(m)
    kh, layer_keys = model_keys(seed, dm.n_layers)
    table = _embed_table(kh, dm)
    xs = {mode: [_embed(table, jnp.asarray(t)) for t in tokens] for mode in modes}
    del table
    for i in range(dm.n_layers):
        w = _layer_weights(layer_keys[i], dm)
        for mode in modes:
            xs[mode] = [_layer(x, w, dm, mode) for x in xs[mode]]
        del w
    return dm, _head(kh, dm), xs


def logit_gaps(m: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
               modes=("f32",)) -> dict:
    """Gaps below the float32 reference's best logit.

    tokens: (B, S) int32, each row a sequence from position 0 (right
    padding is harmless under the causal mask). targets: (B, S), the token
    the program served after each position, -1 where nothing is compared.
    Returns the widest gap of the served tokens (``logit_gap``), their mean
    gap (``mean_logit_gap``) and how many were compared (``tokens``); and,
    for each further mode (the control), the same two gaps of the token
    that mode puts first at each compared position (``<mode>.logit_gap``,
    ``<mode>.mean_logit_gap``).
    """
    dm, head, xs = forward(m, seed, tokens, modes)
    acc = {mode: [0.0, 0.0] for mode in modes}  # widest, sum
    n = 0
    for b in range(len(tokens)):
        ref = _logits(xs["f32"][b], head, dm, "f32")
        tg = jnp.asarray(targets[b])
        for mode in modes:
            pick = tg if mode == "f32" else _first(_logits(xs[mode][b], head, dm, mode), tg)
            widest, total, count = _gap_stats(ref, pick)
            acc[mode][0] = max(acc[mode][0], float(widest))
            acc[mode][1] += float(total)
        n += int(count)
    out = {"tokens": n}
    for mode, (widest, total) in acc.items():
        prefix = "" if mode == "f32" else f"{mode}."
        out[prefix + "logit_gap"] = widest
        out[prefix + "mean_logit_gap"] = total / max(n, 1)
    return out
