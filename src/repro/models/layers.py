"""Shared neural-net building blocks (pure-JAX, pytree params).

Parameters are plain nested dicts of arrays. Initializers take an explicit
key; shapes follow conventions that ``repro.dist.sharding`` pattern-matches
on (leaf path names like 'wq'/'w_up'/'experts' decide the PartitionSpec).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "dense_init",
    "dense",
    "rmsnorm_init",
    "rmsnorm",
    "swiglu",
    "layernorm_init",
    "layernorm",
    "embed_init",
    "rope",
    "cross_entropy",
    "split_keys",
]


def split_keys(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


def dense_init(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    bias: bool = False,
    dtype=jnp.float32,
    scale: Optional[float] = None,
) -> dict:
    scale = (1.0 / math.sqrt(in_dim)) if scale is None else scale
    p = {"w": (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(p: dict, x: jax.Array, *, dtype=None) -> jax.Array:
    w = p["w"]
    if dtype is not None:
        w = w.astype(dtype)
        x = x.astype(dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def swiglu(p: dict, x: jax.Array, *, dtype=None) -> jax.Array:
    """SwiGLU FFN on dense params ``w_gate``, ``w_up``, ``w_down``."""
    g = dense(p["w_gate"], x, dtype=dtype)
    u = dense(p["w_up"], x, dtype=dtype)
    return dense(p["w_down"], jax.nn.silu(g) * u, dtype=dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        x.dtype
    )


def embed_init(key: jax.Array, vocab: int, dim: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


def rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 10000.0,
    inv_freq: Optional[jax.Array] = None,
) -> jax.Array:
    """Rotary position embedding. x: (..., S, H, D), positions: (..., S).
    ``inv_freq`` (D // 2,) replaces the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = inv_freq
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half : 2 * half]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf2 * cos + xf1 * sin
    out = jnp.concatenate([r1, r2], axis=-1)
    if 2 * half != d:  # odd head_dim tail passes through
        out = jnp.concatenate([out, x[..., 2 * half :].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    z_loss: float = 0.0,
) -> tuple[jax.Array, dict]:
    """Token-mean softmax CE with optional z-regularization.

    logits (..., V) any float dtype (reduced in f32); labels int (...,).
    Never materializes probabilities; safe for vocab-sharded logits under
    GSPMD (logsumexp reduces the sharded axis).
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * lse**2
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    metrics = {
        "loss": loss,
        "tokens": denom,
        "ppl_proxy": jnp.exp(jnp.clip(loss, max=20.0)),
    }
    return loss, metrics
