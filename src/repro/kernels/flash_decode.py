"""Pallas TPU decode-attention kernel (one new token vs a long KV cache).

Used by ``serve_step`` for the decode_32k / long_500k shapes. The KV cache is
streamed chunk-by-chunk with online softmax; per-batch valid lengths and
sliding windows are carried by a precomputed (B, 1, S_max) mask operand so the
kernel needs no scalar plumbing.

In the contiguous layout, sawtooth alternates the chunk-scan direction
across consecutive (batch·kv-head) grid rows. Unlike prefill there is no
*intrinsic* KV reuse between rows (different heads/batches read different
cache lines), so that toggle is exposed for symmetry and measurement, not
claimed as a win — see DESIGN.md §2 and kernels/traffic.py.

The *paged* layout (``paged_flash_decode_fwd``: shared page pools + per-row
block tables, scalar-prefetched visit order) restores a real reuse axis:
consecutive decode steps of one sequence re-walk the same pages, and
sawtooth parity keyed on the cache length re-touches the tail pages first
(DESIGN.md §8; reuse-distance deltas in core/cache_sim's page-trace mode).
It is also *ragged*: q may carry C > 1 chunk positions per row with
per-row valid counts, causally masked inside the chunk — the serve
engine's unified mixed step (decode rows + chunked prefill rows) is one
launch of this kernel per layer.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (
    Order,
    Traversal,
    kv_index,
    page_visit_order_dynamic,
)
from repro.kernels.flash_attention import MASK_VALUE, LANES, _pad_axis

__all__ = ["flash_decode_fwd", "paged_flash_decode_fwd"]


def _decode_step(
    q, k, v, ok, o_ref, m_scr, l_scr, acc_scr, *, c, n_chunks, scale, live=None
):
    """One online-softmax chunk: q (Gp, Dk), k (ck, Dk), v (ck, Dv), ok
    (1|Gp, ck) bool (broadcast against the (Gp, ck) score tile — per-query-row
    masks carry the ragged chunk's in-chunk causal structure). ``k`` may
    instead be a function giving q and equal-length lists (ks, vs, oks): the
    tiles of one chunk (several pool pages), loaded and masked inside the
    live branch and folded in one update (``q``, ``v`` and ``ok`` are then
    None).
    ``live``, a traced scalar, skips the chunk's loads and arithmetic where
    no query row of the tile may see any of its keys."""

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _chunk():
        q_, ks, vs, oks = k() if callable(k) else (q, (k,), (v,), (ok,))
        ss = []
        for k_, ok_ in zip(ks, oks):
            s = (
                jax.lax.dot_general(
                    q_, k_, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                * scale
            )  # (Gp, ck)
            ss.append(jnp.where(ok_, s, MASK_VALUE))

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = m_prev
        for s in ss:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        ps = [jnp.where(ok_, jnp.exp(s - m_new), 0.0) for s, ok_ in zip(ss, oks)]
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha
        for p in ps:
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha
        for p, v_ in zip(ps, vs):
            acc = acc + jax.lax.dot_general(
                p.astype(v_.dtype), v_, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_scr[...] = acc
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if live is None:
        _chunk()
    else:
        pl.when(live)(_chunk)

    @pl.when(c == n_chunks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_kernel(
    q_ref,  # (1, Gp, D)
    k_ref,  # (1, ck, D)
    v_ref,
    mask_ref,  # (1, 1, ck) f32 0/1
    o_ref,  # (1, Gp, D)
    m_scr,
    l_scr,
    acc_scr,
    *,
    n_chunks: int,
    scale: float,
):
    _decode_step(
        q_ref[0],
        k_ref[0],
        v_ref[0],
        mask_ref[0] > 0.0,
        o_ref,
        m_scr,
        l_scr,
        acc_scr,
        c=pl.program_id(1),
        n_chunks=n_chunks,
        scale=scale,
    )


def _tile_live(t0, t1, col0, page, length, q_len, window):
    """Whether query rows at chunk positions [t0, t1) of a row may see any key
    of the page starting at ``col0`` (scalars): some row is valid, the page
    starts inside the row's length and at or before the block's last query,
    and, under a window, ends inside the first query's window."""
    first_q = length - q_len + t0
    last_q = length - q_len + jnp.minimum(t1, q_len) - 1
    live = (t0 < q_len) & (col0 < length) & (col0 <= last_q)
    if window is not None:
        live &= col0 + page - 1 > first_q - window
    return live


def _paged_decode_kernel(
    phys_ref,     # scalar prefetch: (B, n_blocks) physical page ids (index maps)
    logical_ref,  # scalar prefetch: (B, n_blocks) visit-ordered logical page ids
    meta_ref,     # scalar prefetch: (B, 2) per-row [cache_len, q_len]
    q_ref,  # (1, CGp, Dk) — C chunk rows × G GQA rows, query-major
    *refs,  # ``per`` K page refs (1, 1, page, Dk), one pool page, one kv head;
            # as many V page refs (1, 1, page, Dv) unless V is K's first
            # v_dim columns; o_ref (1, CGp, Dv); m, l, acc scratch
    n_chunks: int,
    scale: float,
    page: int,
    g: int,
    hkv: int,
    window: Optional[int],
    v_dim: Optional[int] = None,
    blocked: bool = False,
    per: int = 1,
):
    """Ragged paged chunk: the whole mask is derived in-kernel from the
    scalar-prefetched (cache_len, q_len) row metadata and the visit-ordered
    logical page id — no O(B·n_blocks·C·page) mask operand ever exists.
    Query row r of the folded tile is chunk position ``r // g`` at absolute
    position ``cache_len - q_len + r // g``; rows past ``q_len`` (padding /
    inactive slots) are fully masked and finalize to exact zeros.

    ``blocked``: the grid is (rows, query blocks, page groups), the tile
    holds one block of folded query rows against ``per`` pages (each its own
    ref and DMA), and a group no query of the block may see is skipped.
    ``v_dim``: one pool serves as K (all its columns) and V (its first
    ``v_dim``)."""
    n_kv = per if v_dim is not None else 2 * per
    k_refs, v_refs = refs[:per], refs[per:n_kv]
    o_ref, m_scr, l_scr, acc_scr = refs[n_kv:]
    if not blocked:
        (k_ref,), (v_ref,) = k_refs, v_refs
        c = pl.program_id(1)
        b = pl.program_id(0) // hkv
        logical = logical_ref[b, c]
        length = meta_ref[b, 0]
        q_len = meta_ref[b, 1]
        rows = q_ref.shape[1]
        row_t = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // g
        col = logical * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        q_pos = (length - q_len) + row_t
        ok = (col <= q_pos) & (col < length) & (row_t < q_len)
        if window is not None:
            ok &= col > q_pos - window
        _decode_step(
            q_ref[0], k_ref[0, 0], v_ref[0, 0], ok, o_ref, m_scr, l_scr, acc_scr,
            c=c, n_chunks=n_chunks, scale=scale,
        )
        return
    b = pl.program_id(0) // hkv
    length = meta_ref[b, 0]
    q_len = meta_ref[b, 1]
    rows = q_ref.shape[1]
    c = pl.program_id(2)
    t0 = pl.program_id(1) * (rows // g)  # the block's first chunk position
    # Per query row (rows, 1) and per key column (1, page): the mask is
    # their broadcast comparison, not a full tile of positions.
    row_t = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g
    q_pos = (length - q_len) + row_t
    row_ok = row_t < q_len
    logical = [logical_ref[b, c * per + i] for i in range(per)]
    live = False
    for lg in logical:
        live |= _tile_live(t0, t0 + rows // g, lg * page, page, length, q_len, window)

    def tiles():
        ks, vs, oks = [], [], []
        for i, lg in enumerate(logical):
            col = lg * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
            ok = (col <= q_pos) & (col < length) & row_ok
            if window is not None:
                ok &= col > q_pos - window
            k = k_refs[i][0, 0]
            ks.append(k)
            vs.append(k[:, :v_dim] if v_dim is not None else v_refs[i][0, 0])
            oks.append(ok)
        return q_ref[0], ks, vs, oks

    _decode_step(
        None, tiles, None, None, o_ref, m_scr, l_scr, acc_scr,
        c=c, n_chunks=n_chunks, scale=scale, live=live,
    )


def flash_decode_fwd(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int,
    *,
    order: Order | str = Order.CYCLIC,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    chunk: int = 512,
    snake_group: Optional[int] = None,
    interpret: bool = False,
    block_table: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,
    order_group: Optional[jax.Array] = None,
    v_dim: Optional[int] = None,
) -> jax.Array:
    """q (B,1,Hq,D); caches (B,S_max,Hkv,D); cache_len scalar or (B,).

    With ``block_table`` (B, n_blocks), caches are shared page pools
    (n_pages, Hkv, page, D) and the kernel visits each row's pages through
    the block table in schedule order; q may then carry C > 1 ragged chunk
    positions per row with per-row ``q_lens`` (see
    :func:`paged_flash_decode_fwd`). ``order_group`` (paged only) replaces
    the static order with a traced effective reversal-group operand so the
    visit order can change per step without retracing. ``v_cache`` None
    (paged only) reads V as the first ``v_dim`` columns of K.
    """
    if block_table is not None:
        return paged_flash_decode_fwd(
            q,
            k_cache,
            v_cache,
            cache_len,
            block_table,
            q_lens=q_lens,
            order=order,
            window=window,
            scale=scale,
            snake_group=snake_group,
            interpret=interpret,
            order_group=order_group,
            v_dim=v_dim,
        )
    if v_cache is None:
        raise NotImplementedError(
            "a latent cache (V inside K) decodes from the paged pool (block_table)"
        )
    assert q_lens is None, "q_lens requires the paged layout (block_table)"
    assert order_group is None, "order_group requires the paged layout"
    return _flash_decode_contiguous(
        q,
        k_cache,
        v_cache,
        cache_len,
        order=Order.parse(order),
        window=window,
        scale=scale,
        chunk=chunk,
        snake_group=snake_group,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("order", "window", "scale", "chunk", "snake_group", "interpret"),
)
def _flash_decode_contiguous(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int,
    *,
    order: Order,
    window: Optional[int],
    scale: Optional[float],
    chunk: int,
    snake_group: Optional[int],
    interpret: bool,
) -> jax.Array:
    b, one, hq, d = q.shape
    assert one == 1, "decode kernel takes a single query position"
    _, s_max, hkv, _ = k_cache.shape
    g = hq // hkv
    scale_ = float(d**-0.5 if scale is None else scale)
    chunk = min(chunk, max(128, 1 << (s_max - 1).bit_length()))

    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    ok = pos < lens[:, None]
    if window is not None:
        ok &= pos > (lens[:, None] - 1 - window)
    # (B, 1, S_max): a unit row axis keeps the mask block's last two dims
    # (1, chunk) legal on TPU for any batch size.
    mask = ok.astype(jnp.float32)[:, None, :]
    mask = _pad_axis(mask, 2, chunk)

    g_pad = max(8, g)
    qf = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    qf = _pad_axis(_pad_axis(qf, 1, g_pad), 2, LANES)
    kf = _pad_axis(
        _pad_axis(k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_max, d), 1, chunk),
        2,
        LANES,
    )
    vf = _pad_axis(
        _pad_axis(v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_max, d), 1, chunk),
        2,
        LANES,
    )
    dp = kf.shape[2]
    n_chunks = kf.shape[1] // chunk

    # The chunk walk derives from the same IR as every other consumer:
    # kv_index over n_chunks with the (batch*kv-head) grid row as the parity
    # driver (contiguous decode has no intrinsic cross-row reuse — DESIGN.md
    # §2 — so the toggle is for symmetry and measurement).
    def q_map(bh, c):
        return (bh, 0, 0)

    def kv_map(bh, c):
        return (bh, kv_index(order, bh, c, n_chunks, snake_group=snake_group), 0)

    def mask_map(bh, c):
        return (bh // hkv, 0, kv_index(order, bh, c, n_chunks, snake_group=snake_group))

    kernel = functools.partial(_decode_kernel, n_chunks=n_chunks, scale=scale_)

    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, n_chunks),
        in_specs=[
            pl.BlockSpec((1, g_pad, dp), q_map),
            pl.BlockSpec((1, chunk, dp), kv_map),
            pl.BlockSpec((1, chunk, dp), kv_map),
            pl.BlockSpec((1, 1, chunk), mask_map),
        ],
        out_specs=pl.BlockSpec((1, g_pad, dp), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g_pad, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g_pad, LANES), jnp.float32),
            pltpu.VMEM((g_pad, LANES), jnp.float32),
            pltpu.VMEM((g_pad, dp), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="flash_decode_fwd",
    )(qf, kf, vf, mask)

    out = out.reshape(b, hkv, g_pad, dp)[:, :, :g, :d]
    return out.reshape(b, 1, hq, d)


# Folded query rows (chunk positions x GQA group) a tile holds at most:
# beyond it the rows are split into blocks on a grid axis of their own. A
# block of 1024 rows against a 640-lane latent page keeps the tile's
# buffers (query and output double-buffered, f32 accumulator, scores) near
# 9 MB, inside the 16 MB of VMEM a v5e kernel is given by default, where
# 16 heads x a 512-position chunk (8192 rows) would need about 25 MB for
# the f32 accumulator and softmax statistics alone.
MAX_Q_ROWS = 1024

# Keys a step of the blocked grid reads: this many rows of pool pages, each
# page its own ref and DMA. A grid step then does four 128-row pages' work
# for one step's fixed cost, and a group of pages no query sees is skipped
# as one step. Against a 1024-row query block the score and probability
# tiles add about 10 MB of f32 to the buffers above (about 19 MB in all at
# the latent width), so the blocked grid asks for a larger share of the
# v5e's 128 MB of VMEM than the 16 MB a kernel is given by default. On a
# v5e, DeepSeek-V2-Lite's 27 latent layers for a 512-row chunk at 8k keys
# beside three decode rows take 126 ms at one page a step, 54 ms at four.
KV_ROWS = 512
BLOCKED_VMEM_BYTES = 48 * 2**20


@functools.partial(
    jax.jit,
    static_argnames=("order", "window", "scale", "snake_group", "interpret", "v_dim"),
)
def paged_flash_decode_fwd(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: Optional[jax.Array],
    cache_len: jax.Array | int,
    block_table: jax.Array,
    *,
    q_lens: Optional[jax.Array] = None,
    order: Order | str = Order.CYCLIC,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    snake_group: Optional[int] = None,
    interpret: bool = False,
    order_group: Optional[jax.Array] = None,
    v_dim: Optional[int] = None,
) -> jax.Array:
    """Ragged paged attention: q (B,C,Hq,D); pools (n_pages, Hkv, page, D).

    ``v_pool`` None is a latent pool (multi-head latent attention): one
    page tile, read by one DMA, is K in all its columns (past q's width,
    zeros) and V in its first ``v_dim``; the result is then (B, C, Hq,
    v_dim). More folded query rows
    (C x the GQA group) than ``MAX_Q_ROWS`` are split into blocks of at most
    that many, on a grid axis of their own.

    C = 1 is plain decode; C > 1 is a chunked-prefill / mixed serve step,
    with per-row ``q_lens`` valid chunk rows and causal masking *inside*
    the chunk (query t of row b sits at position ``cache_len - q_len + t``).

    The schedule is folded into the operands before the kernel launches:
    the compiled ``Traversal``'s ``visit_order`` lowering (sawtooth parity
    = cache_len per row, so consecutive steps reverse direction) gives each
    row's logical visit order, the block table maps it to physical pool
    pages, and that (B, n_blocks) physical id array is the scalar-prefetch
    operand the KV ``index_map`` reads — the classic TPU paged-attention
    pattern. Validity/causality is computed *in-kernel* from two more
    scalar-prefetch operands (the visit-ordered logical ids and per-row
    (cache_len, q_len)), so no O(B·n_blocks·C·page) mask operand exists.

    The pool puts the kv-head axis before the page rows, so each grid step
    reads one ``(page, D)`` tile — the block's last two dims equal the
    array's, which is what the TPU lowering requires for any page size.
    """
    order = Order.parse(order)
    b, c, hq, d = q.shape
    n_pages, hkv, page, _ = k_pool.shape
    n_blocks = block_table.shape[1]
    g = hq // hkv
    scale_ = float(d**-0.5 if scale is None else scale)
    latent = v_pool is None
    dv = v_dim if latent else v_pool.shape[-1]

    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    qls = (
        jnp.full((b,), c, jnp.int32)
        if q_lens is None
        else jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (b,))
    )
    if order_group is not None:
        # Runtime-switchable order: the schedule is already folded into the
        # scalar-prefetch operands outside the kernel, so rebinding the
        # visit order is pure data — the effective reversal group arrives
        # as a traced scalar (schedule.resolve_order_group) and the static
        # ``order``/``snake_group`` arguments are ignored. The kernel body
        # is untouched; no recompile happens across order switches.
        visit = page_visit_order_dynamic(lens, n_blocks, order_group)
    else:
        tr = Traversal(
            order=order, n_q=1, n_kv=n_blocks, q_block=1, kv_block=page,
            snake_group=snake_group,
        )
        visit = tr.visit_order(lens)  # (B, n_blocks) logical
    phys = jnp.take_along_axis(block_table.astype(jnp.int32), visit, axis=1)
    meta = jnp.stack([lens, qls], axis=1)  # (B, 2)

    # Fold (chunk, GQA group) into one query-major row axis: row = t*g + gg.
    cg = c * g
    q_rows = max(g, MAX_Q_ROWS // g * g)
    q_blocks = -(-cg // q_rows)
    # One query block of a K/V pool keeps the two-axis grid; more blocks, or
    # a latent pool, take the blocked grid (rows, query blocks, pages).
    blocked = latent or q_blocks > 1
    per = 1
    if blocked:
        q_rows = min(q_rows, max(8, -(-cg // 8) * 8))
        cg_pad = q_blocks * q_rows
        # Group ``per`` pages a grid step; the groups' padding visits a
        # logical page past every row's length, which no query sees.
        per = max(1, min(KV_ROWS // page, n_blocks))
        pad = -n_blocks % per
        visit = jnp.pad(visit, ((0, 0), (0, pad)), constant_values=n_blocks)
        phys = jnp.pad(phys, ((0, 0), (0, pad)))
    else:
        cg_pad = max(8, -(-cg // 8) * 8)
    n_chunks = -(-n_blocks // per)
    qf = (
        q.reshape(b, c, hkv, g, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * hkv, cg, d)
    )
    if latent:
        # The latent page is read at the pool's width (its rows zero-padded
        # past the latent's own, as the model lays them out); a tile whose
        # last two dims equal the array's is legal at any width.
        kf = k_pool
        dp, dvp = kf.shape[3], dv
        qf = jnp.pad(_pad_axis(qf, 1, cg_pad), ((0, 0), (0, 0), (0, dp - d)))
        kv_specs = 1
    else:
        qf = _pad_axis(_pad_axis(qf, 1, cg_pad), 2, LANES)
        kf = _pad_axis(k_pool, 3, LANES)
        vf = _pad_axis(v_pool, 3, LANES)
        dp = kf.shape[3]
        dvp = vf.shape[3]
        kv_specs = 2

    kernel = functools.partial(
        _paged_decode_kernel,
        n_chunks=n_chunks,
        scale=scale_,
        page=page,
        g=g,
        hkv=hkv,
        window=window,
        v_dim=dv if latent else None,
        blocked=blocked,
        per=per,
    )
    if not blocked:

        def q_map(bh, j, phys_ref, logical_ref, meta_ref):
            return (bh, 0, 0)

        def kv_map(bh, j, phys_ref, logical_ref, meta_ref):
            return (phys_ref[bh // hkv, j], bh % hkv, 0, 0)

        kv_maps = [kv_map]
        grid = (b * hkv, n_blocks)
        semantics = ("parallel", "arbitrary")
        vmem = {}
        q_block = (1, cg_pad, dp)
        o_block = (1, cg_pad, dvp)
    else:

        def q_map(bh, qb, j, phys_ref, logical_ref, meta_ref):
            return (bh, qb, 0)

        def kv_map(i, bh, qb, j, phys_ref, logical_ref, meta_ref):
            # Page i of group j. A page no query of the block sees reads the
            # dummy page 0, so a run of such pages fetches nothing new.
            row = bh // hkv
            t0 = qb * (q_rows // g)
            jj = j * per + i
            live = _tile_live(
                t0, t0 + q_rows // g, logical_ref[row, jj] * page, page,
                meta_ref[row, 0], meta_ref[row, 1], window,
            )
            return (jnp.where(live, phys_ref[row, jj], 0), bh % hkv, 0, 0)

        kv_maps = [functools.partial(kv_map, i) for i in range(per)]
        grid = (b * hkv, q_blocks, n_chunks)
        semantics = ("parallel", "parallel", "arbitrary")
        vmem = {"vmem_limit_bytes": BLOCKED_VMEM_BYTES}
        q_block = (1, q_rows, dp)
        o_block = (1, q_rows, dvp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[pl.BlockSpec(q_block, q_map)]
        + [pl.BlockSpec((1, 1, page, dp), m) for m in kv_maps] * kv_specs,
        out_specs=pl.BlockSpec(o_block, q_map),
        scratch_shapes=[
            pltpu.VMEM((q_block[1], LANES), jnp.float32),
            pltpu.VMEM((q_block[1], LANES), jnp.float32),
            pltpu.VMEM((q_block[1], dvp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, cg_pad, dvp), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics, **vmem),
        name="paged_flash_decode_fwd",
    )(phys, visit, meta, qf, *[kf] * per, *(() if latent else [vf] * per))

    out = out[:, :cg, :dv].reshape(b, hkv, c, g, dv)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, dv)
