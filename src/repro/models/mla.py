"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per token x (d), with H heads:
    q            = x · W_q                      -> H x (nope + rope)
    [c; k_pe]    = x · W_kva                    -> rank + rope
    c_kv         = RMSNorm(c)
    [k_nope; v]  = c_kv · W_kvb                 -> H x (nope + v)
q_pe and k_pe carry YaRN-scaled RoPE; k_pe is one head shared by all H.
A head's score is q · [k_nope; k_pe] at scale ``softmax_scale``, and its
output softmax · v, mapped by W_o.

What a token leaves in the cache is its latent [c_kv; k_pe]
(``latent_dim`` values a layer). Full-sequence attention (training,
prefill) decompresses K and V per head from it. Serving attends in the
absorbed form instead, at every step width: W_kvb's key half is folded
into the query (q_lat = q_nope · W_kvb,k^T, ``rank`` wide per head), so
scores are q_lat · c_kv + q_pe · k_pe against the cached latents
themselves, and W_kvb's value half maps each head's latent output before
W_o. The module holds the arithmetic; ``models.transformer`` places it in
the cache layouts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig
from repro.models import layers as L

__all__ = [
    "mla_init",
    "yarn_inv_freq",
    "softmax_scale",
    "project",
    "decompress",
    "absorb_query",
    "latent_output",
]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: MLAConfig, theta: float) -> np.ndarray:
    """Inverse frequencies of the rotary dims (``qk_rope_head_dim // 2``):
    YaRN blends the interpolated ``base / factor`` frequencies and the
    original ones with a linear ramp between the dims that turn ``beta_fast``
    and ``beta_slow`` times over the original context."""
    dim = m.qk_rope_head_dim
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if m.rope_factor <= 1:
        return base.astype(np.float32)

    def turns_dim(turns):
        return dim * math.log(m.rope_original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(m.beta_fast)), 0)
    high = min(math.ceil(turns_dim(m.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp  # share of the original frequency
    return (base / m.rope_factor * (1.0 - keep) + base * keep).astype(np.float32)


def _rope_mscale(m: MLAConfig) -> float:
    """YaRN's scale of the rotary cos and sin (1 where mscale == mscale_all_dim)."""
    return _yarn_mscale(m.rope_factor, m.mscale) / _yarn_mscale(m.rope_factor, m.mscale_all_dim)


def softmax_scale(m: MLAConfig) -> float:
    """Score scale: qk_head_dim^-1/2 times YaRN's attention scale squared."""
    return m.qk_head_dim ** -0.5 * _yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2


def mla_init(key, cfg: ModelConfig, *, d_in=None) -> dict:
    m = cfg.mla
    d, h = d_in or cfg.d_model, cfg.n_heads
    kq, ka, kb, ko = L.split_keys(key, 4)
    pd = cfg.parameter_dtype()
    return {
        "wq": L.dense_init(kq, d, h * m.qk_head_dim, dtype=pd),
        "wkv_a": L.dense_init(ka, d, m.latent_dim, dtype=pd),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, pd),
        "wkv_b": L.dense_init(kb, m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype=pd),
        "wo": L.dense_init(ko, h * m.v_head_dim, d, dtype=pd),
    }


def _rope(cfg: ModelConfig, x, positions):
    m = cfg.mla
    out = L.rope(x, positions, inv_freq=jnp.asarray(yarn_inv_freq(m, cfg.rope_theta)))
    scale = _rope_mscale(m)
    return out if scale == 1.0 else (out * scale).astype(x.dtype)


def project(p: dict, cfg: ModelConfig, x, positions):
    """x (B, S, d) at ``positions`` (B, S) -> (q_nope (B, S, H, nope), q_pe
    (B, S, H, rope) rotated, latent (B, S, rank + rope): [c_kv; k_pe] with
    k_pe rotated, what the cache keeps)."""
    m = cfg.mla
    dt = cfg.activation_dtype()
    b, s, _ = x.shape
    q = L.dense(p["wq"], x, dtype=dt).reshape(b, s, cfg.n_heads, m.qk_head_dim)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    kv = L.dense(p["wkv_a"], x, dtype=dt)
    c = L.rmsnorm(p["kv_norm"], kv[..., : m.kv_lora_rank], cfg.norm_eps)
    k_pe = _rope(cfg, kv[..., None, m.kv_lora_rank :], positions)[:, :, 0]
    return q_nope, _rope(cfg, q_pe, positions), jnp.concatenate([c, k_pe], axis=-1)


def _kv_b(p: dict, cfg: ModelConfig):
    """W_kvb as (rank, H, nope + v), in the activation dtype."""
    m = cfg.mla
    w = p["wkv_b"]["w"].astype(cfg.activation_dtype())
    return w.reshape(m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)


def decompress(p: dict, cfg: ModelConfig, q_nope, q_pe, latent):
    """Per-head q, k (B, S, H, nope + rope) and v (B, S, H, v) from the
    projections: K and V decompressed from the latents."""
    m = cfg.mla
    c, k_pe = latent[..., : m.kv_lora_rank], latent[..., m.kv_lora_rank :]
    kv = jnp.einsum("bsr,rhn->bshn", c, _kv_b(p, cfg))
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim :]
    k_pe = jnp.broadcast_to(k_pe[:, :, None], (*k_nope.shape[:3], m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return q, jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], axis=-1), v


def absorb_query(p: dict, cfg: ModelConfig, q_nope, q_pe):
    """The absorbed query (B, C, H, rank + rope): [q_nope · W_kvb,k^T; q_pe],
    scored directly against cached latents [c_kv; k_pe]."""
    m = cfg.mla
    w_uk = _kv_b(p, cfg)[..., : m.qk_nope_head_dim]
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def latent_output(p: dict, cfg: ModelConfig, o_lat):
    """Heads' latent outputs (B, C, H, rank) -> the layer's output (B, C, d):
    each head's through W_kvb's value half, then W_o."""
    m = cfg.mla
    b, c = o_lat.shape[:2]
    w_uv = _kv_b(p, cfg)[..., m.qk_nope_head_dim :]
    o = jnp.einsum("bchr,rhv->bchv", o_lat.astype(w_uv.dtype), w_uv)
    return L.dense(p["wo"], o.reshape(b, c, -1), dtype=cfg.activation_dtype())
