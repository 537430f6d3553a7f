"""Transformer backbone: GQA attention (RoPE, SWA, QKV-bias), SwiGLU FFN,
scanned+remat'd layer stacks, KV caches for serving.

Used directly by the dense archs and reused by the MoE / hybrid / enc-dec /
VLM families (they swap the FFN or interleave blocks). All attention goes
through ``repro.kernels.ops.attention`` and therefore through the paper's
schedulable KV traversal.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import compression
from repro.dist.context import constrain
from repro.kernels import ops
from repro.models import layers as L
from repro.models.moe import EXPERT_STACKS

__all__ = [
    "attn_init",
    "attn_apply",
    "attn_decode",
    "ffn_init",
    "ffn_apply",
    "layer_init",
    "stack_init",
    "stack_apply",
    "stack_prefill",
    "stack_decode",
    "init_cache",
    "fill_cache",
    "remat_wrap",
]


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig, *, d_in: Optional[int] = None) -> dict:
    d = d_in or cfg.d_model
    hd = cfg.hd
    kq, kk, kv, ko = L.split_keys(key, 4)
    pd = cfg.parameter_dtype()
    return {
        "wq": L.dense_init(kq, d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=pd),
        "wk": L.dense_init(kk, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=pd),
        "wv": L.dense_init(kv, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=pd),
        "wo": L.dense_init(ko, cfg.n_heads * hd, d, dtype=pd),
    }


def _qkv(p, cfg: ModelConfig, x, kv_src, positions, kv_positions, *, use_rope=True):
    dt = cfg.activation_dtype()
    b, s, _ = x.shape
    skv = kv_src.shape[1]
    hd = cfg.hd
    q = L.dense(p["wq"], x, dtype=dt).reshape(b, s, cfg.n_heads, hd)
    k = L.dense(p["wk"], kv_src, dtype=dt).reshape(b, skv, cfg.n_kv_heads, hd)
    v = L.dense(p["wv"], kv_src, dtype=dt).reshape(b, skv, cfg.n_kv_heads, hd)
    if use_rope:
        q = L.rope(q, positions, theta=cfg.rope_theta)
        k = L.rope(k, kv_positions, theta=cfg.rope_theta)
    return q, k, v


def attn_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    kv_src: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    causal: bool = True,
    use_rope: bool = True,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    cross = kv_src is not None
    kv_src = x if kv_src is None else kv_src
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _qkv(p, cfg, x, kv_src, positions, kv_positions, use_rope=use_rope)
    o = ops.attention(
        q,
        k,
        v,
        order=cfg.attn_order,
        snake_group=cfg.snake_group,
        causal=causal and not cross,
        window=cfg.window if (causal and not cross) else None,
        q_block=cfg.q_block,
        kv_block=cfg.kv_block,
        impl=cfg.attn_impl,
        score_dtype=cfg.score_dtype,
        bwd_q_block=cfg.bwd_q_block,
        bwd_kv_block=cfg.bwd_kv_block,
    )
    b, s, _, _ = o.shape
    out = L.dense(p["wo"], o.reshape(b, s, -1), dtype=cfg.activation_dtype())
    if return_kv:
        return out, (k, v)
    return out


def attn_decode(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    cache: dict,
    *,
    cross: bool = False,
):
    """Decode step. cache: {"k","v": (B,S_max,Hkv,hd), "len": scalar}.

    The paged layout is *ragged*: ``x`` may carry C > 1 chunk positions per
    row, with per-row valid counts in ``cache["q_len"]`` (default: all C) —
    one call serves decode rows (q_len 1) and chunked-prefill rows (q_len
    up to C) together. The contiguous layouts stay single-token.
    """
    dt = cfg.activation_dtype()
    b, one, _ = x.shape
    hd = cfg.hd
    q = L.dense(p["wq"], x, dtype=dt).reshape(b, -1, cfg.n_heads, hd)
    if not cross and "k_pages" in cache:
        k = L.dense(p["wk"], x, dtype=dt).reshape(b, -1, cfg.n_kv_heads, hd)
        v = L.dense(p["wv"], x, dtype=dt).reshape(b, -1, cfg.n_kv_heads, hd)
        o, cache = _attn_decode_paged(cfg, cache, q, k, v)
        out = L.dense(p["wo"], o.reshape(b, o.shape[1], -1), dtype=dt)
        return out, cache
    assert one == 1, "contiguous decode takes a single query position"
    if not cross:
        pos = cache["len"]
        k = L.dense(p["wk"], x, dtype=dt).reshape(b, 1, cfg.n_kv_heads, hd)
        v = L.dense(p["wv"], x, dtype=dt).reshape(b, 1, cfg.n_kv_heads, hd)
        q = L.rope(q, jnp.full((b, 1), pos), theta=cfg.rope_theta)
        k = L.rope(k, jnp.full((b, 1), pos), theta=cfg.rope_theta)
        s_max = cache["k"].shape[1]
        write = pos % s_max if cfg.window is not None else pos  # SWA ring buffer
        cache = _cache_write(cfg, cache, "k", k, write)
        cache = _cache_write(cfg, cache, "v", v, write)
        cache["len"] = pos + 1
        valid = jnp.minimum(pos + 1, s_max)
        o = ops.attention_decode(
            q,
            _cache_read(cfg, cache, "k"),
            _cache_read(cfg, cache, "v"),
            valid,
            order=cfg.attn_order,
            snake_group=cfg.snake_group,
            impl=cfg.attn_impl,
        )
    else:
        # cross-attention: static encoder K/V, no rope (matches prefill path)
        o = ops.attention_decode(
            q, cache["k"], cache["v"], cache["kv_len"], impl=cfg.attn_impl
        )
    out = L.dense(p["wo"], o.reshape(b, 1, -1), dtype=dt)
    return out, cache


def _paged_write(cfg: ModelConfig, cache: dict, k, v, starts, q_lens) -> dict:
    """Chunked write-at-offset into a paged cache — THE paged write path.

    Pages are laid out (n_pages, Hkv, page, hd) (int8 scales (n_pages, Hkv,
    page)), so a token lands at ``[phys, :, offset]``.
    k/v: (B, C, Hkv, hd) chunk values; row b's positions ``starts[b] + t``
    for ``t < q_lens[b]`` are written through the block table (logical page
    ``pos // page``, offset ``pos % page``). Invalid chunk rows (``t >=
    q_len`` — padding of a ragged step, or inactive serve slots) are routed
    to the reserved dummy page 0, so the fixed-shape scatter stays total.
    Both prefill (``fill_cache``: starts 0, q_lens = S) and ragged serve
    steps (decode rows at C=1, prefill chunks at C>1) funnel through here.
    """
    b, c = k.shape[:2]
    bt = cache["block_table"]
    page = cache["k_pages"].shape[2]
    capacity = bt.shape[1] * page
    tq = jnp.arange(c, dtype=jnp.int32)[None, :]
    pos = starts[:, None] + tq                             # (B, C)
    valid = tq < q_lens[:, None]
    wpos = jnp.minimum(pos, capacity - 1)  # clamp like the contiguous path
    page_log = wpos // page
    offset = wpos % page
    phys = jnp.take_along_axis(bt, page_log, axis=1)       # (B, C)
    phys = jnp.where(valid, phys, 0)                       # dummy page 0

    out = dict(cache)
    for name, val in (("k_pages", k), ("v_pages", v)):
        if cfg.kv_cache_dtype == "int8":
            qv, sc = _quantize_kv(val)                     # (B,C,H,hd),(B,C,H)
            out[name] = out[name].at[phys, :, offset].set(qv)
            out[name + "_scale"] = out[name + "_scale"].at[phys, :, offset].set(sc)
        else:
            out[name] = out[name].at[phys, :, offset].set(
                val.astype(out[name].dtype)
            )
    return out


def _attn_decode_paged(cfg: ModelConfig, cache: dict, q, k, v):
    """Ragged chunk step against a paged cache: per-row lengths + valid
    chunk counts, block-table write-at-offset, schedule-ordered ragged
    paged attention (causal inside the chunk). Rows whose ``q_len`` is 0
    (free continuous-batching slots) write only into the reserved dummy
    page and read back exact zeros."""
    b, c = q.shape[:2]
    lens = cache["len"]  # (B,) tokens already cached (chunk positions follow)
    bt = cache["block_table"]
    page = cache["k_pages"].shape[2]
    capacity = bt.shape[1] * page
    q_lens = cache.get("q_len")
    if q_lens is None:
        q_lens = jnp.full((b,), c, jnp.int32)

    positions = lens[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (B, C)
    q = L.rope(q, positions, theta=cfg.rope_theta)
    k = L.rope(k, positions, theta=cfg.rope_theta)

    cache = dict(cache)
    cache = _paged_write(cfg, cache, k, v, lens, q_lens)
    cache["len"] = lens + q_lens

    valid = jnp.minimum(lens + q_lens, capacity)
    # ``order_group`` rides the cache dict like ``q_len``: a traced
    # effective reversal-group scalar that overrides cfg.attn_order for
    # this step (the serve engine's runtime order switch; absent outside
    # the continuous path, where the static config order applies).
    o = ops.attention_decode(
        q,
        _cache_read(cfg, cache, "k_pages"),
        _cache_read(cfg, cache, "v_pages"),
        valid,
        order=cfg.attn_order,
        snake_group=cfg.snake_group,
        impl=cfg.attn_impl,
        block_table=bt,
        q_lens=q_lens,
        order_group=cache.get("order_group"),
    )
    return o, cache


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head)-vector symmetric int8. x (B,S,H,D) -> (q, scale)."""
    return compression.quantize_int8_vec(x)


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return compression.dequantize_int8_vec(q, scale, dtype)


def _cache_read(cfg: ModelConfig, cache: dict, name: str) -> jax.Array:
    if cfg.kv_cache_dtype == "int8":
        return _dequantize_kv(cache[name], cache[name + "_scale"], cfg.activation_dtype())
    return cache[name]


def page_geometry(cfg: ModelConfig, max_len: int) -> tuple[int, int]:
    """(page rows, blocks-per-sequence) for a paged cache of ``max_len``.

    Page size defaults to ``kv_block`` so physical pages coincide with the
    KV tiles the schedule walks — a block-table entry is then exactly one
    schedule step (DESIGN.md §8).
    """
    page = cfg.page_size or cfg.kv_block
    page = max(1, min(page, max_len))
    return page, -(-max_len // page)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=None) -> dict:
    """Self-attention KV cache; SWA archs get a window-sized ring buffer.
    kv_cache_dtype='int8' stores quantized values + per-vector scales.

    ``cfg.kv_layout == 'paged'`` switches to a page-pool layout: k/v pages
    (n_pages, Hkv, page, hd) plus a per-row ``block_table`` (B, n_blocks)
    initialized to the identity mapping (row i owns pages [i*n, (i+1)*n)),
    and per-row ``len`` (B,). A serving pool (repro.serve.kv_pool) re-maps
    block tables as sequences join and leave the running batch.
    """
    if cfg.kv_layout == "paged":
        if cfg.window is not None:
            raise ValueError(
                "paged KV layout requires full attention; sliding-window "
                "archs keep the ring-buffer layout (kv_layout='contiguous')"
            )
        page, bpr = page_geometry(cfg, max_len)
        shape = (batch * bpr, cfg.n_kv_heads, page, cfg.hd)
        cache = {
            "len": jnp.zeros((batch,), jnp.int32),
            "block_table": jnp.arange(batch * bpr, dtype=jnp.int32).reshape(
                batch, bpr
            ),
        }
        if cfg.kv_cache_dtype == "int8":
            for name in ("k_pages", "v_pages"):
                cache[name] = jnp.zeros(shape, jnp.int8)
                cache[name + "_scale"] = jnp.ones(shape[:3], jnp.float32)
        else:
            dt = dtype or cfg.activation_dtype()
            cache["k_pages"] = jnp.zeros(shape, dt)
            cache["v_pages"] = jnp.zeros(shape, dt)
        return cache
    size = min(max_len, cfg.window) if cfg.window is not None else max_len
    shape = (batch, size, cfg.n_kv_heads, cfg.hd)
    cache = {"len": jnp.zeros((), jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        for name in ("k", "v"):
            cache[name] = jnp.zeros(shape, jnp.int8)
            cache[name + "_scale"] = jnp.ones(shape[:3], jnp.float32)
    else:
        dt = dtype or cfg.activation_dtype()
        cache["k"] = jnp.zeros(shape, dt)
        cache["v"] = jnp.zeros(shape, dt)
    return cache


def _cache_write(cfg: ModelConfig, cache: dict, name: str, val: jax.Array, pos) -> dict:
    """Write ``val`` (B,s,H,D) at sequence offset ``pos`` (traced ok)."""
    out = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        q, scale = _quantize_kv(val)
        out[name] = jax.lax.dynamic_update_slice_in_dim(cache[name], q, pos, axis=1)
        out[name + "_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache[name + "_scale"], scale, pos, axis=1
        )
    else:
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], val.astype(cache[name].dtype), pos, axis=1
        )
    return out


def fill_cache(cfg: ModelConfig, cache: dict, k: jax.Array, v: jax.Array) -> dict:
    """Write prefill K/V into a fresh cache (handles SWA truncation).

    Paged caches must come straight from :func:`init_cache` (identity block
    table): row i's logical pages are then physically contiguous, so the
    prefill scatter is a reshape.
    """
    if "k_pages" in cache:
        return _fill_cache_paged(cfg, cache, k, v)
    s = k.shape[1]
    size = cache["k"].shape[1]
    if s >= size:
        k, v = k[:, -size:], v[:, -size:]
        if cfg.window is not None:
            # Ring-buffer layout: decode writes position p at index p % size,
            # so the kept tail (positions s-size..s-1) must land on those
            # indices — otherwise the first decode writes evict the wrong
            # (non-oldest) entries. Rolling by s % size puts position p at
            # index p % size.
            shift = s % size
            if shift:
                k = jnp.roll(k, shift, axis=1)
                v = jnp.roll(v, shift, axis=1)
    cache = _cache_write(cfg, cache, "k", k, 0)
    cache = _cache_write(cfg, cache, "v", v, 0)
    cache["len"] = jnp.asarray(s, jnp.int32)
    return cache


def _fill_cache_paged(cfg: ModelConfig, cache: dict, k: jax.Array, v: jax.Array) -> dict:
    b, s = k.shape[:2]
    page = cache["k_pages"].shape[2]
    capacity = cache["block_table"].shape[1] * page
    if s > capacity:
        k, v = k[:, -capacity:], v[:, -capacity:]
        s = capacity
    out = _paged_write(
        cfg,
        cache,
        k,
        v,
        jnp.zeros((b,), jnp.int32),
        jnp.full((b,), s, jnp.int32),
    )
    out["len"] = jnp.full((b,), s, jnp.int32)
    return out


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------


def ffn_init(key, cfg: ModelConfig, *, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    kg, ku, kd = L.split_keys(key, 3)
    pd = cfg.parameter_dtype()
    return {
        "w_gate": L.dense_init(kg, d, ff, dtype=pd),
        "w_up": L.dense_init(ku, d, ff, dtype=pd),
        "w_down": L.dense_init(kd, ff, d, dtype=pd),
    }


def ffn_apply(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    dt = cfg.activation_dtype()
    g = L.dense(p["w_gate"], x, dtype=dt)
    u = L.dense(p["w_up"], x, dtype=dt)
    return L.dense(p["w_down"], jax.nn.silu(g) * u, dtype=dt)


# --------------------------------------------------------------------------
# layer + stack (scan over stacked params)
# --------------------------------------------------------------------------


def layer_init(key, cfg: ModelConfig, *, ffn_init_fn=None) -> dict:
    ka, kf = L.split_keys(key, 2)
    pd = cfg.parameter_dtype()
    f_init = ffn_init_fn or (lambda k: ffn_init(k, cfg))
    return {
        "ln_attn": L.rmsnorm_init(cfg.d_model, pd),
        "attn": attn_init(ka, cfg),
        "ln_ffn": L.rmsnorm_init(cfg.d_model, pd),
        "ffn": f_init(kf),
    }


def remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def layer_scan(cfg: ModelConfig, body, carry, xs):
    """lax.scan over stacked layer params, or a python-unrolled loop when
    cfg.scan_layers=False (dry-run roofline: XLA cost_analysis counts while
    bodies once, so trip-count-correct metrics need unrolled HLO)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


def stack_init(key, cfg: ModelConfig, n_layers: int, *, ffn_init_fn=None) -> dict:
    keys = jnp.stack(L.split_keys(key, n_layers))
    return jax.vmap(lambda k: layer_init(k, cfg, ffn_init_fn=ffn_init_fn))(keys)


def _layer_fwd(lp, cfg: ModelConfig, x, positions, *, causal, ffn_apply_fn):
    h = x + attn_apply(
        lp["attn"], cfg, L.rmsnorm(lp["ln_attn"], x, cfg.norm_eps), positions=positions, causal=causal
    )
    extras = None
    y = ffn_apply_fn(lp["ffn"], cfg, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps))
    if isinstance(y, tuple):  # MoE returns (out, aux)
        y, extras = y
    return h + y, extras


def stack_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    ffn_apply_fn=None,
):
    """Scan the layer stack; returns (hidden, aux_sum)."""
    ffn_fn = ffn_apply_fn or (lambda p, c, h: ffn_apply(p, c, h))

    def body(h, lp):
        out, extras = _layer_fwd(
            lp, cfg, h, positions, causal=causal, ffn_apply_fn=ffn_fn
        )
        out = constrain(out, "residual")
        aux = extras if extras is not None else jnp.zeros((), jnp.float32)
        return out, aux

    body = remat_wrap(body, cfg)
    h, auxes = layer_scan(cfg, body, x, params)
    return h, jnp.sum(auxes)


def _split_expert_stacks(cfg: ModelConfig, params: dict):
    """Split a serve stack's params into what the layer scan slices and the
    dropless MoE expert stacks it must not: ``(scanned params, stacks,
    layer indices)``.

    ``lax.ragged_dot`` lowers to a TPU custom call, which cannot fuse a
    dynamic-slice operand, so an expert stack scanned per layer is copied
    whole (all E experts) every layer of every step. Where the layers' FFN
    holds expert stacks ([L, E, ...]) and serving runs dropless, the stacks
    leave the scanned params, are closed over whole, and the scan carries
    the layer index instead (``moe._moe_dropless`` reads the layer's experts
    in place). Dense stacks come back unchanged, with no stacks and no
    indices.
    """
    ffn = params["ffn"]
    if cfg.moe is None or not cfg.moe_serve_dropless or ffn["w_gate"].ndim != 4:
        return params, None, None
    stacks = {k: ffn[k] for k in EXPERT_STACKS}
    rest = {k: v for k, v in ffn.items() if k not in stacks}
    layers = jnp.arange(ffn["w_gate"].shape[0], dtype=jnp.int32)
    return {**params, "ffn": rest}, stacks, layers


def _serve_ffn(ffn_fn, cfg: ModelConfig, lp: dict, stacks, layer, x):
    """The layer's FFN on a serve path: on ``lp["ffn"]`` alone, or with the
    expert stacks whole and the layer index (``_split_expert_stacks``)."""
    if stacks is None:
        y = ffn_fn(lp["ffn"], cfg, x)
    else:
        y = ffn_fn({**lp["ffn"], **stacks}, cfg, x, layer=layer)
    return y[0] if isinstance(y, tuple) else y


def stack_prefill(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    max_len: int,
    *,
    ffn_apply_fn=None,
):
    """Forward + build per-layer KV caches (stacked on a leading L axis)."""
    ffn_fn = ffn_apply_fn or (lambda p, c, h: ffn_apply(p, c, h))
    b = x.shape[0]
    scanned, stacks, layers = _split_expert_stacks(cfg, params)

    def body(h, xs):
        lp, layer = xs
        xn = L.rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
        a, (k, v) = attn_apply(
            lp["attn"], cfg, xn, positions=positions, causal=True, return_kv=True
        )
        h = h + a
        y = _serve_ffn(
            ffn_fn, cfg, lp, stacks, layer, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
        )
        cache = fill_cache(cfg, init_cache(cfg, b, max_len), k, v)
        return constrain(h + y, "residual"), cache

    body = remat_wrap(body, cfg)
    h, caches = layer_scan(cfg, body, x, (scanned, layers))
    return h, caches


def stack_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    caches: dict,
    *,
    ffn_apply_fn=None,
):
    """One-token step through all layers, updating stacked caches."""
    ffn_fn = ffn_apply_fn or (lambda p, c, h: ffn_apply(p, c, h))
    scanned, stacks, layers = _split_expert_stacks(cfg, params)

    def body(h, xs):
        lp, cache, layer = xs
        xn = L.rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
        a, cache = attn_decode(lp["attn"], cfg, xn, cache)
        h = h + a
        y = _serve_ffn(
            ffn_fn, cfg, lp, stacks, layer, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
        )
        return h + y, cache

    h, caches = layer_scan(cfg, body, x, (scanned, caches, layers))
    return h, caches
