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
from repro.models import mla as MLA
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
    "init_pages",
    "page_leaves",
    "remat_wrap",
]


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig, *, d_in: Optional[int] = None) -> dict:
    if cfg.mla is not None:
        return MLA.mla_init(key, cfg, d_in=d_in)
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
    """Full-sequence attention (train / prefill / encoder / cross).
    ``return_kv`` also returns what the cache keeps: (k, v), or (latent,)
    under latent attention."""
    if cfg.mla is not None:
        assert kv_src is None and use_rope, "latent attention is causal self-attention"
        return _mla_apply(p, cfg, x, positions, causal=causal, return_kv=return_kv)
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


def _mla_apply(p: dict, cfg: ModelConfig, x, positions, *, causal: bool, return_kv: bool):
    """Latent attention over the full sequence: K and V decompressed per
    head (``mla.decompress``); V is zero-padded to the query-key width so
    the one attention op serves, and cut back after it."""
    q_nope, q_pe, latent = MLA.project(p, cfg, x, positions)
    q, k, v = MLA.decompress(p, cfg, q_nope, q_pe, latent)
    dv = v.shape[-1]
    o = ops.attention(
        q,
        k,
        jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q.shape[-1] - dv))),
        order=cfg.attn_order,
        snake_group=cfg.snake_group,
        causal=causal,
        scale=MLA.softmax_scale(cfg.mla),
        q_block=cfg.q_block,
        kv_block=cfg.kv_block,
        impl=cfg.attn_impl,
        score_dtype=cfg.score_dtype,
        bwd_q_block=cfg.bwd_q_block,
        bwd_kv_block=cfg.bwd_kv_block,
    )[..., :dv]
    b, s = o.shape[:2]
    out = L.dense(p["wo"], o.reshape(b, s, -1), dtype=cfg.activation_dtype())
    return (out, (latent,)) if return_kv else out


def _mla_decode(p: dict, cfg: ModelConfig, x: jax.Array, cache: dict):
    """Latent attention against a latent cache, in the absorbed form: the
    new tokens' latents are written, and the absorbed queries attend the
    cached latents directly (V is their first ``kv_lora_rank`` columns).
    Paged: ragged C-position chunks, as ``_attn_decode_paged``; contiguous:
    one position."""
    b, c, _ = x.shape
    lens = cache["len"]
    paged = "latent_pages" in cache
    if paged:
        q_lens = cache.get("q_len")
        if q_lens is None:
            q_lens = jnp.full((b,), c, jnp.int32)
        positions = lens[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    else:
        assert c == 1, "contiguous decode takes a single query position"
        positions = jnp.full((b, 1), lens)
    q_nope, q_pe, latent = MLA.project(p, cfg, x, positions)
    q = MLA.absorb_query(p, cfg, q_nope, q_pe)
    cache = dict(cache)
    kw = {}
    if paged:
        cache = _paged_write(cfg, cache, {"latent_pages": latent[:, :, None]}, lens, q_lens)
        cache["len"] = lens + q_lens
        bt = cache["block_table"]
        capacity = bt.shape[1] * cache["latent_pages"].shape[-2]
        valid = jnp.minimum(lens + q_lens, capacity)
        pool, base = _paged_pool(cfg, cache, "latent_pages")
        kw = dict(block_table=bt + base, q_lens=q_lens, order_group=cache.get("order_group"))
    else:
        cache = _cache_write(cfg, cache, "latent", latent[:, :, None], lens)
        cache["len"] = lens + 1
        valid = lens + 1
        pool = cache["latent"]
    o = ops.attention_decode(
        q,
        pool,
        None,
        valid,
        order=cfg.attn_order,
        snake_group=cfg.snake_group,
        impl=cfg.attn_impl,
        scale=MLA.softmax_scale(cfg.mla),
        v_dim=cfg.mla.kv_lora_rank,
        **kw,
    )
    return MLA.latent_output(p, cfg, o), cache


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
    if cfg.mla is not None and not cross:
        return _mla_decode(p, cfg, x, cache)
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


def _paged_write(cfg: ModelConfig, cache: dict, vals: dict, starts, q_lens) -> dict:
    """Chunked write-at-offset into a paged cache — THE paged write path.

    Pages are laid out (n_pages, heads, page, width) (int8 scales (n_pages,
    Hkv, page)), so a token lands at ``[phys, :, offset]``; inside the serve
    layer scan each page array is the whole [L, n_pages, ...] stack and
    ``cache["layer"]`` the layer written (``stack_decode``).
    ``vals`` maps each page array of ``cache`` to its (B, C, heads, width)
    chunk values (``k_pages``/``v_pages``, or one ``latent_pages``, whose
    narrower latents are zero-padded to the page rows' width); row b's
    positions ``starts[b] + t`` for ``t < q_lens[b]`` are written through
    the block table (logical page ``pos // page``, offset ``pos % page``).
    Invalid chunk rows (``t >= q_len`` — padding of a ragged step, or
    inactive serve slots) and positions past the table's capacity are not
    written. Both prefill (``fill_cache``: starts 0, q_lens = S) and ragged
    serve steps (decode rows at C=1, prefill chunks at C>1) funnel through
    here; ``ops.paged_write`` writes the stack in place on TPU.
    """
    stacked = "layer" in cache  # else one layer's pools: a stack of one
    new = {}
    for name, val in vals.items():
        pad = cache[name].shape[-1] - val.shape[-1]
        if pad:
            val = jnp.pad(val, ((0, 0),) * (val.ndim - 1) + ((0, pad),))
        if cfg.kv_cache_dtype == "int8":
            new[name], new[name + "_scale"] = _quantize_kv(val)  # (B,C,H,hd),(B,C,H)
        else:
            new[name] = val
    pools = ops.paged_write(
        {n: cache[n] if stacked else cache[n][None] for n in new},
        new, cache["block_table"], starts, q_lens, cache.get("layer", 0),
        impl=cfg.attn_impl,
    )
    out = dict(cache)
    out.update({n: p if stacked else p[0] for n, p in pools.items()})
    return out


def _paged_pool(cfg: ModelConfig, cache: dict, name: str):
    """(pool, first page id) of a page array as the paged attention reads
    it: inside the serve layer scan the [L * n_pages, ...] view of the
    stack, whose layer ``cache["layer"]`` starts at page ``layer *
    n_pages`` (int8: that layer's pages, dequantized, at 0); else the
    layer's own pool at 0."""
    layer = cache.get("layer")
    if layer is None:
        return _cache_read(cfg, cache, name), 0
    pool = cache[name]
    if cfg.kv_cache_dtype == "int8":
        scale = cache[name + "_scale"][layer]
        return _dequantize_kv(pool[layer], scale, cfg.activation_dtype()), 0
    return pool.reshape((-1,) + pool.shape[2:]), layer * pool.shape[1]


def _attn_decode_paged(cfg: ModelConfig, cache: dict, q, k, v):
    """Ragged chunk step against a paged cache: per-row lengths + valid
    chunk counts, block-table write-at-offset, schedule-ordered ragged
    paged attention (causal inside the chunk). Rows whose ``q_len`` is 0
    (free continuous-batching slots) write only into the reserved dummy
    page and read back exact zeros."""
    b, c = q.shape[:2]
    lens = cache["len"]  # (B,) tokens already cached (chunk positions follow)
    bt = cache["block_table"]
    page = cache["k_pages"].shape[-2]
    capacity = bt.shape[1] * page
    q_lens = cache.get("q_len")
    if q_lens is None:
        q_lens = jnp.full((b,), c, jnp.int32)

    positions = lens[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (B, C)
    q = L.rope(q, positions, theta=cfg.rope_theta)
    k = L.rope(k, positions, theta=cfg.rope_theta)

    cache = dict(cache)
    cache = _paged_write(cfg, cache, {"k_pages": k, "v_pages": v}, lens, q_lens)
    cache["len"] = lens + q_lens

    valid = jnp.minimum(lens + q_lens, capacity)
    k_pool, base = _paged_pool(cfg, cache, "k_pages")
    v_pool, _ = _paged_pool(cfg, cache, "v_pages")
    # ``order_group`` rides the cache dict like ``q_len``: a traced
    # effective reversal-group scalar that overrides cfg.attn_order for
    # this step (the serve engine's runtime order switch; absent outside
    # the continuous path, where the static config order applies).
    o = ops.attention_decode(
        q,
        k_pool,
        v_pool,
        valid,
        order=cfg.attn_order,
        snake_group=cfg.snake_group,
        impl=cfg.attn_impl,
        block_table=bt + base,
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


PAGE_LANES = 128  # a latent page row's width is a multiple of the TPU's lanes


def page_leaves(cfg: ModelConfig, n_pages: int, page: int, *, lead=(), dtype=None) -> dict:
    """A paged cache's page arrays, each (*lead, n_pages, heads, page,
    width): ``k_pages`` and ``v_pages`` (int8 adds ``*_scale`` (*lead,
    n_pages, Hkv, page), at ones), or under latent attention one
    ``latent_pages`` of a single head, whose page tile the paged kernel
    reads as K and, in its first ``kv_lora_rank`` columns, as V.

    A latent row is ``latent_dim`` values (576 at DeepSeek-V2-Lite's
    widths) zero-padded to a multiple of 128 lanes (640): at 576 the TPU
    lays the pool out with the page rows minor (no lane padding), and the
    kernel, which reads rows, then needs a transposed copy of every layer's
    pages in every step."""
    dt = dtype or cfg.activation_dtype()
    if cfg.mla is not None:
        if cfg.kv_cache_dtype == "int8":
            raise ValueError("the latent cache is kept in the activation dtype, not int8")
        width = -(-cfg.mla.latent_dim // PAGE_LANES) * PAGE_LANES
        return {"latent_pages": jnp.zeros((*lead, n_pages, 1, page, width), dt)}
    shape = (*lead, n_pages, cfg.n_kv_heads, page, cfg.hd)
    pages = {}
    if cfg.kv_cache_dtype == "int8":
        for name in ("k_pages", "v_pages"):
            pages[name] = jnp.zeros(shape, jnp.int8)
            pages[name + "_scale"] = jnp.ones(shape[:-1], jnp.float32)
    else:
        for name in ("k_pages", "v_pages"):
            pages[name] = jnp.zeros(shape, dt)
    return pages


def is_page_leaf(name: str) -> bool:
    """Whether a cache leaf is a page array of ``page_leaves`` (pages or
    their int8 scales), under any stack prefix."""
    return "_pages" in name


# Cache leaves of the leading dense stack carry this prefix in the model's
# cache pytree (``models.model``); the per-row metadata is shared by all
# layers.
DENSE_PREFIX = "dense_"
CACHE_META = ("len", "block_table", "q_len", "order_group")


def layer_stacks(cfg: ModelConfig, n_layers: Optional[int] = None) -> tuple:
    """(cache prefix, layers) of each layer stack in order: the leading
    dense stack (``first_dense_layers``), if any, then the main stack."""
    n = cfg.n_layers if n_layers is None else n_layers
    k = cfg.first_dense_layers
    return ((DENSE_PREFIX, k), ("", n - k)) if k else (("", n),)


def init_pages(cfg: ModelConfig, n_layers: int, n_pages: int, page: int, *, dtype=None) -> dict:
    """A serving pool's page arrays for ``n_layers`` layers: each stack's
    ``page_leaves`` stacked on a leading layer axis, named with its prefix."""
    return {
        prefix + name: leaf
        for prefix, n in layer_stacks(cfg, n_layers)
        for name, leaf in page_leaves(cfg, n_pages, page, lead=(n,), dtype=dtype).items()
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=None) -> dict:
    """Self-attention KV cache; SWA archs get a window-sized ring buffer.
    kv_cache_dtype='int8' stores quantized values + per-vector scales.
    Latent attention caches one ``latent`` (B, S, 1, latent_dim) instead of
    K and V.

    ``cfg.kv_layout == 'paged'`` switches to a page-pool layout: page arrays
    (``page_leaves``: k/v pages (n_pages, Hkv, page, hd)) plus a per-row
    ``block_table`` (B, n_blocks) initialized to the identity mapping (row i
    owns pages [i*n, (i+1)*n)), and per-row ``len`` (B,). A serving pool
    (repro.serve.kv_pool) re-maps block tables as sequences join and leave
    the running batch.
    """
    if cfg.kv_layout == "paged":
        if cfg.window is not None:
            raise ValueError(
                "paged KV layout requires full attention; sliding-window "
                "archs keep the ring-buffer layout (kv_layout='contiguous')"
            )
        page, bpr = page_geometry(cfg, max_len)
        cache = {
            "len": jnp.zeros((batch,), jnp.int32),
            "block_table": jnp.arange(batch * bpr, dtype=jnp.int32).reshape(
                batch, bpr
            ),
        }
        cache.update(page_leaves(cfg, batch * bpr, page, dtype=dtype))
        return cache
    size = min(max_len, cfg.window) if cfg.window is not None else max_len
    cache = {"len": jnp.zeros((), jnp.int32)}
    if cfg.mla is not None:
        dt = dtype or cfg.activation_dtype()
        cache["latent"] = jnp.zeros((batch, size, 1, cfg.mla.latent_dim), dt)
        return cache
    shape = (batch, size, cfg.n_kv_heads, cfg.hd)
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


def fill_cache(cfg: ModelConfig, cache: dict, k: jax.Array, v: Optional[jax.Array] = None) -> dict:
    """Write prefill K/V (or, under latent attention, the latents ``k`` (B,
    S, latent_dim) alone) into a fresh cache (handles SWA truncation).

    Paged caches must come straight from :func:`init_cache` (identity block
    table): row i's logical pages are then physically contiguous, so the
    prefill scatter is a reshape.
    """
    vals = {"k": k, "v": v} if v is not None else {"latent": k[:, :, None]}
    if "block_table" in cache:
        return _fill_cache_paged(cfg, cache, vals)
    s = k.shape[1]
    first = next(iter(vals))
    size = cache[first].shape[1]
    if s >= size:
        vals = {n: val[:, -size:] for n, val in vals.items()}
        if cfg.window is not None:
            # Ring-buffer layout: decode writes position p at index p % size,
            # so the kept tail (positions s-size..s-1) must land on those
            # indices — otherwise the first decode writes evict the wrong
            # (non-oldest) entries. Rolling by s % size puts position p at
            # index p % size.
            shift = s % size
            if shift:
                vals = {n: jnp.roll(val, shift, axis=1) for n, val in vals.items()}
    for name, val in vals.items():
        cache = _cache_write(cfg, cache, name, val, 0)
    cache["len"] = jnp.asarray(s, jnp.int32)
    return cache


def _fill_cache_paged(cfg: ModelConfig, cache: dict, vals: dict) -> dict:
    first = next(iter(vals))
    b, s = vals[first].shape[:2]
    page = cache[first + "_pages"].shape[-2]
    capacity = cache["block_table"].shape[1] * page
    if s > capacity:
        vals = {n: val[:, -capacity:] for n, val in vals.items()}
        s = capacity
    out = _paged_write(
        cfg,
        cache,
        {n + "_pages": val for n, val in vals.items()},
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
    return L.swiglu(p, x, dtype=cfg.activation_dtype())


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
    if cfg.moe is None or not cfg.moe_serve_dropless or getattr(ffn["w_gate"], "ndim", 0) != 4:
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
        a, kv = attn_apply(
            lp["attn"], cfg, xn, positions=positions, causal=True, return_kv=True
        )
        h = h + a
        y = _serve_ffn(
            ffn_fn, cfg, lp, stacks, layer, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
        )
        cache = fill_cache(cfg, init_cache(cfg, b, max_len), *kv)
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
    """One-token step through all layers, updating stacked caches.

    A paged cache's page arrays ([L, n_pages, ...] stacks, ``is_page_leaf``)
    do not ride the scan as slices: sliced per layer they are copied out,
    relaid out for the write and stacked back every layer of every step.
    They are carried whole, the scan carries the layer index, and each layer
    writes its rows into the stacks and reads its pages from them in place
    (``_paged_write``, ``_paged_pool``). The per-row metadata and contiguous
    caches are scanned as before."""
    ffn_fn = ffn_apply_fn or (lambda p, c, h: ffn_apply(p, c, h))
    scanned, stacks, layers = _split_expert_stacks(cfg, params)
    pages = {n: v for n, v in caches.items() if is_page_leaf(n)}
    rest = {n: v for n, v in caches.items() if n not in pages}
    if pages and layers is None:
        layers = jnp.arange(next(iter(pages.values())).shape[0], dtype=jnp.int32)

    def body(carry, xs):
        h, pages = carry
        lp, cache, layer = xs
        if pages:
            cache = {**cache, **pages, "layer": layer}
        xn = L.rmsnorm(lp["ln_attn"], h, cfg.norm_eps)
        a, cache = attn_decode(lp["attn"], cfg, xn, cache)
        pages = {n: cache[n] for n in pages}
        cache = {n: v for n, v in cache.items() if n not in pages and n != "layer"}
        h = h + a
        y = _serve_ffn(
            ffn_fn, cfg, lp, stacks, layer, L.rmsnorm(lp["ln_ffn"], h, cfg.norm_eps)
        )
        return (h + y, pages), cache

    (h, pages), caches = layer_scan(cfg, body, (x, pages), (scanned, rest, layers))
    return h, {**caches, **pages}
