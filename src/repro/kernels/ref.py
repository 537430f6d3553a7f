"""Pure-jnp oracles for every Pallas kernel in this package.

These are *independent* implementations (full materialization, no blocking)
used by the shape/dtype-sweep tests; the blockwise ``repro.core.attention``
path is itself validated against them.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attention import decode_attention as _decode_full
from repro.core.attention import mha_reference

__all__ = ["flash_attention_ref", "decode_attention_ref", "ssd_ref", "paged_write_ref"]


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Oracle for kernels.flash_attention. Layout (B, S, H, D)."""
    return mha_reference(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Oracle for kernels.flash_decode. q (B,1,Hq,D), caches (B,S,Hkv,D)."""
    return _decode_full(q, k_cache, v_cache, cache_len, window=window, scale=scale)


def ssd_ref(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 64,  # unused; oracle is sequential
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the Mamba-2 SSD kernel: sequential selective-state recurrence.

    Shapes (SSD / Mamba-2, arXiv:2405.21060):
      x:  (B, S, H, P)   inputs (P = head dim)
      dt: (B, S, H)      per-head step sizes (post-softplus, >= 0)
      a:  (H,)           negative state decay rates (A = -exp(a_log) <= 0)
      b:  (B, S, N)      input projections  (shared across heads, G=1)
      c:  (B, S, N)      output projections
    Returns (y, final_state) with y (B,S,H,P), state (B,H,P,N).

    Recurrence per head h:  S_t = exp(dt_t * a_h) * S_{t-1} + dt_t * x_t b_t^T
                            y_t = S_t c_t
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cf = c.astype(jnp.float32)

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        decay = jnp.exp(dt_t[..., None, None] * af[None, :, None, None])
        upd = (dt_t[..., None] * x_t)[..., :, None] * b_t[:, None, None, :]
        state = decay * state + upd  # (B,H,P,N)
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t)
        return state, y_t

    init = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(bf, 1, 0),
        jnp.moveaxis(cf, 1, 0),
    )
    state, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1).transpose(0, 1, 2, 3)  # (B,S,H,P)
    return y.astype(x.dtype), state


def paged_write_ref(
    pools: tuple,
    vals: tuple,
    block_table: jax.Array,
    starts: jax.Array,
    q_lens: jax.Array,
    layer,
) -> tuple:
    """Oracle for kernels.paged_write: one scatter per page array on its
    [L * n_pages, ...] view. Positions past ``q_lens`` or the block table's
    capacity are dropped."""
    c = vals[0].shape[1]
    lead, page = pools[0].shape[:2], pools[0].shape[3]
    n_blocks = block_table.shape[1]
    t = jnp.arange(c, dtype=jnp.int32)[None, :]
    pos = starts.astype(jnp.int32)[:, None] + t
    valid = (t < q_lens[:, None]) & (pos < n_blocks * page)
    phys = jnp.take_along_axis(block_table, jnp.minimum(pos // page, n_blocks - 1), axis=1)
    ids = jnp.where(valid, layer * lead[1] + phys, lead[0] * lead[1])  # out of range: dropped
    out = []
    for pool, v in zip(pools, vals):
        flat = pool.reshape((-1,) + pool.shape[2:])
        flat = flat.at[ids, :, pos % page].set(v.astype(pool.dtype), mode="drop")
        out.append(flat.reshape(pool.shape))
    return tuple(out)
