"""Blockwise (flash) attention in pure JAX, parameterized by KV schedule.

This is the framework's reference execution path: it is the oracle for the
Pallas kernels, the implementation used on CPU (and in the multi-pod
dry-run, where Pallas-TPU cannot lower), and the place where the paper's
sawtooth schedule is demonstrably *math-preserving* — online softmax is
traversal-order invariant, so cyclic and sawtooth produce identical outputs
up to floating-point reassociation (property-tested).

Layout convention: q:(B, Sq, Hq, D), k/v:(B, Skv, Hkv, D) with Hq % Hkv == 0
(GQA). Output (B, Sq, Hq, D), accumulation in f32, output in q.dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import (
    KVSchedule,
    Order,
    Traversal,
    page_visit_order_dynamic,
)

__all__ = [
    "mha_reference",
    "flash_attention",
    "flash_attention_bwd",
    "decode_attention",
    "paged_decode_attention",
]

NEG_INF = float(np.finfo(np.float32).min)


def _valid_mask(
    rows: jax.Array,
    cols: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    kv_len: int,
) -> jax.Array:
    """Boolean (len(rows), len(cols)) visibility mask for global indices."""
    m = cols < kv_len  # mask out kv padding
    if causal:
        m &= cols[None, :] <= rows[:, None]
    if window is not None:
        m &= cols[None, :] > rows[:, None] - window
    if not causal and window is None:
        m = jnp.broadcast_to(m[None, :], (rows.shape[0], cols.shape[0]))
    return m


def _mask_bias(
    rows: jax.Array,
    cols: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    kv_len: int,
) -> jax.Array:
    """Additive mask bias (0 or -inf) for global row/col index grids."""
    m = _valid_mask(rows, cols, causal=causal, window=window, kv_len=kv_len)
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Full-materialization attention. Small shapes / testing only."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = d ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    rows = jnp.arange(sq)
    cols = jnp.arange(skv)
    s = s + _mask_bias(rows, cols, causal=causal, window=window, kv_len=skv)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


@functools.partial(
    jax.jit,
    static_argnames=(
        "order",
        "causal",
        "window",
        "q_block",
        "kv_block",
        "scale",
        "score_dtype",
        "snake_group",
        "return_lse",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    order: Order | str = Order.CYCLIC,
    causal: bool = False,
    window: Optional[int] = None,
    q_block: int = 128,
    kv_block: int = 128,
    scale: Optional[float] = None,
    score_dtype: str = "float32",
    snake_group: Optional[int] = None,
    return_lse: bool = False,
) -> jax.Array:
    """Blockwise online-softmax attention, KV traversed in schedule order.

    Structure mirrors paper Alg. 1 (split-Q: Q tile resident, KV streamed)
    with the KV visit order given by Alg. 4 when ``order == 'sawtooth'``.
    Q blocks are independent (vmapped — the 'parallel for' of Alg. 1); the
    KV stream is a ``lax.scan`` so the lowered HLO stays small at any S.

    ``return_lse=True`` additionally returns the per-row log-sum-exp of the
    *scaled* scores, shape (B, Sq, Hq) f32 — the residual the fused flash
    backward (:func:`flash_attention_bwd`) consumes instead of recomputing
    the forward. Fully-masked (padding) rows report ``NEG_INF``-scale lse.
    """
    order = Order.parse(order)
    sdt = jnp.dtype(score_dtype)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale_ = d ** -0.5 if scale is None else scale

    q_block = min(q_block, max(sq, 1))
    kv_block = min(kv_block, max(skv, 1))

    qp = _pad_to(q, 1, q_block)
    kp = _pad_to(k, 1, kv_block)
    vp = _pad_to(v, 1, kv_block)
    sq_p, skv_p = qp.shape[1], kp.shape[1]
    nq, nkv = sq_p // q_block, skv_p // kv_block

    # (B, Hkv, G, nq, qb, D) queries; (B, Hkv, nkv, kb, D) keys/values.
    # The compiled traversal: the XLA path masks instead of trimming, so it
    # walks the full tile range in IR order (``kv_step``).
    tr = Traversal(
        order=order, n_q=nq, n_kv=nkv, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, n_groups=g, snake_group=snake_group,
    )

    qb_ = (
        qp.reshape(b, nq, q_block, hkv, g, d)
        .transpose(0, 3, 4, 1, 2, 5)
        .astype(sdt)
        * jnp.asarray(scale_, sdt)
    )
    kb_ = kp.reshape(b, nkv, kv_block, hkv, d).transpose(0, 3, 1, 2, 4)
    vb_ = vp.reshape(b, nkv, kv_block, hkv, d).transpose(0, 3, 1, 2, 4)

    rows = jnp.arange(q_block)
    cols = jnp.arange(kv_block)

    def one_q_block(i, q_tile):
        # q_tile: (B, Hkv, G, qb, D)
        def body(carry, j):
            m, l, acc = carry
            kv_j = tr.kv_step(i, j)
            k_j = jax.lax.dynamic_index_in_dim(kb_, kv_j, axis=2, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb_, kv_j, axis=2, keepdims=False)
            # scores/probs in score_dtype (bf16 halves the dominant HBM
            # traffic term — EXPERIMENTS.md §Perf); softmax stats stay f32.
            s = jnp.einsum(
                "bhgqd,bhkd->bhgqk",
                q_tile,
                k_j.astype(sdt),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=sdt,
            )
            bias = _mask_bias(
                rows + i * q_block,
                cols + kv_j * kv_block,
                causal=causal,
                window=window,
                kv_len=skv,
            ).astype(sdt)
            s = s + bias
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new.astype(sdt)[..., None])  # stays in sdt
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1).astype(jnp.float32)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_j.astype(sdt),
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, hkv, g, q_block), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, q_block), jnp.float32),
            jnp.zeros((b, hkv, g, q_block, d), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nkv))
        lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding)
        return acc / l[..., None], lse

    out, lse = jax.vmap(one_q_block, in_axes=(0, 3), out_axes=(3, 3))(
        jnp.arange(nq), qb_
    )  # (B, Hkv, G, nq, qb, D), (B, Hkv, G, nq, qb)
    out = out.transpose(0, 3, 4, 1, 2, 5).reshape(b, sq_p, hq, d)
    out = out[:, :sq].astype(q.dtype)
    if not return_lse:
        return out
    lse = lse.transpose(0, 3, 4, 1, 2).reshape(b, sq_p, hq)[:, :sq]
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=(
        "order",
        "causal",
        "window",
        "q_block",
        "kv_block",
        "scale",
        "score_dtype",
        "snake_group",
    ),
)
def flash_attention_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    *,
    order: Order | str = Order.CYCLIC,
    causal: bool = False,
    window: Optional[int] = None,
    q_block: int = 128,
    kv_block: int = 128,
    scale: Optional[float] = None,
    score_dtype: str = "float32",
    snake_group: Optional[int] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused blockwise flash backward from saved ``(o, lse)`` residuals.

    The FlashAttention-2 two-pass structure, without re-running the forward:

      delta = rowsum(dO * O)                      (per-row, f32)
      dQ pass: Q tile resident, KV tiles streamed in schedule order
               (the forward grid), accumulating dQ += scale * dS @ K
      dK/dV pass: KV tile resident, Q/dO tiles streamed in the *transposed*
               schedule order (parity keyed on the KV-tile counter — see
               ``core.schedule.BwdKVSchedule``), accumulating
               dV += P^T @ dO and dK += scale * dS^T @ Q

    with P = exp(S - lse) recovered from the saved log-sum-exp (already
    normalized — no second softmax reduction) and dS = P * (dP - delta).
    Out-of-range tiles contribute exact zeros through the mask, so both
    passes scan the full tile range (the Pallas kernels trim instead).
    ``score_dtype`` drops the two score-shaped einsums to bf16 like the
    forward; softmax recovery and accumulation stay f32.
    """
    order = Order.parse(order)
    sdt = jnp.dtype(score_dtype)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale_ = d ** -0.5 if scale is None else scale

    q_block = min(q_block, max(sq, 1))
    kv_block = min(kv_block, max(skv, 1))

    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)  # (B,Sq,Hq)

    qp = _pad_to(q, 1, q_block)
    dop = _pad_to(do, 1, q_block)
    lsep = _pad_to(lse.astype(jnp.float32), 1, q_block)
    deltap = _pad_to(delta, 1, q_block)
    kp = _pad_to(k, 1, kv_block)
    vp = _pad_to(v, 1, kv_block)
    sq_p, skv_p = qp.shape[1], kp.shape[1]
    nq, nkv = sq_p // q_block, skv_p // kv_block

    tr = Traversal(
        order=order, n_q=nq, n_kv=nkv, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, n_groups=g, snake_group=snake_group,
    )
    # The transposed (dK/dV) pass streams Q tiles with parity on the resident
    # KV-tile counter: the same IR with the roles of the axes swapped.
    tr_t = Traversal(
        order=order, n_q=nkv, n_kv=nq, q_block=kv_block, kv_block=q_block,
        snake_group=snake_group,
    )

    def fold_q(x):  # (B, Sq, Hq[, D]) -> (B, Hkv, G, nq, qb[, D])
        tail = x.shape[3:]
        x = x.reshape((b, nq, q_block, hkv, g) + tail)
        perm = (0, 3, 4, 1, 2) + tuple(range(5, x.ndim))
        return x.transpose(perm)

    qb_ = fold_q(qp.astype(jnp.float32))
    dob_ = fold_q(dop.astype(jnp.float32))
    lseb = fold_q(lsep)
    deltab = fold_q(deltap)
    kb_ = kp.astype(jnp.float32).reshape(b, nkv, kv_block, hkv, d).transpose(0, 3, 1, 2, 4)
    vb_ = vp.astype(jnp.float32).reshape(b, nkv, kv_block, hkv, d).transpose(0, 3, 1, 2, 4)

    rows = jnp.arange(q_block)
    cols = jnp.arange(kv_block)

    def _p_ds(q_t, do_t, lse_t, delta_t, k_j, v_j, ok):
        """Shared tile math: normalized probs P and score grad dS."""
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", q_t.astype(sdt), k_j.astype(sdt),
            preferred_element_type=sdt,
        ).astype(jnp.float32) * scale_
        p = jnp.where(ok, jnp.exp(s - lse_t[..., None]), 0.0)
        dp = jnp.einsum(
            "bhgqd,bhkd->bhgqk", do_t.astype(sdt), v_j.astype(sdt),
            preferred_element_type=sdt,
        ).astype(jnp.float32)
        ds = p * (dp - delta_t[..., None])
        return p, ds

    # ---- dQ pass: forward grid (Q resident, KV streamed) ---------------------
    def dq_block(i, q_t, do_t, lse_t, delta_t):
        def body(acc, j):
            kv_j = tr.kv_step(i, j)
            k_j = jax.lax.dynamic_index_in_dim(kb_, kv_j, axis=2, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb_, kv_j, axis=2, keepdims=False)
            ok = _valid_mask(
                rows + i * q_block, cols + kv_j * kv_block,
                causal=causal, window=window, kv_len=skv,
            )
            _, ds = _p_ds(q_t, do_t, lse_t, delta_t, k_j, v_j, ok)
            acc = acc + scale_ * jnp.einsum("bhgqk,bhkd->bhgqd", ds, k_j)
            return acc, None

        init = jnp.zeros((b, hkv, g, q_block, d), jnp.float32)
        acc, _ = jax.lax.scan(body, init, jnp.arange(nkv))
        return acc

    dq = jax.vmap(dq_block, in_axes=(0, 3, 3, 3, 3), out_axes=3)(
        jnp.arange(nq), qb_, dob_, lseb, deltab
    )
    dq = dq.transpose(0, 3, 4, 1, 2, 5).reshape(b, sq_p, hq, d)[:, :sq]

    # ---- dK/dV pass: transposed grid (KV resident, Q/dO streamed) ------------
    def dkv_block(jt, k_t, v_t):
        def body(carry, jq):
            dk_acc, dv_acc = carry
            q_i = tr_t.kv_step(jt, jq)  # transposed: parity on KV tile
            q_t = jax.lax.dynamic_index_in_dim(qb_, q_i, axis=3, keepdims=False)
            do_t = jax.lax.dynamic_index_in_dim(dob_, q_i, axis=3, keepdims=False)
            lse_t = jax.lax.dynamic_index_in_dim(lseb, q_i, axis=3, keepdims=False)
            delta_t = jax.lax.dynamic_index_in_dim(deltab, q_i, axis=3, keepdims=False)
            ok = _valid_mask(
                rows + q_i * q_block, cols + jt * kv_block,
                causal=causal, window=window, kv_len=skv,
            )
            p, ds = _p_ds(q_t, do_t, lse_t, delta_t, k_t, v_t, ok)
            dv_acc = dv_acc + jnp.einsum("bhgqk,bhgqd->bhkd", p, do_t)
            dk_acc = dk_acc + scale_ * jnp.einsum("bhgqk,bhgqd->bhkd", ds, q_t)
            return (dk_acc, dv_acc), None

        init = (
            jnp.zeros((b, hkv, kv_block, d), jnp.float32),
            jnp.zeros((b, hkv, kv_block, d), jnp.float32),
        )
        (dk_acc, dv_acc), _ = jax.lax.scan(body, init, jnp.arange(nq))
        return dk_acc, dv_acc

    dk, dv = jax.vmap(dkv_block, in_axes=(0, 2, 2), out_axes=2)(
        jnp.arange(nkv), kb_, vb_
    )  # (B, Hkv, nkv, kb, D)
    dk = dk.transpose(0, 2, 3, 1, 4).reshape(b, skv_p, hkv, d)[:, :skv]
    dv = dv.transpose(0, 2, 3, 1, 4).reshape(b, skv_p, hkv, d)[:, :skv]

    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_table: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,
    order: Order | str = Order.CYCLIC,
    snake_group: Optional[int] = None,
    order_group: Optional[jax.Array] = None,
    v_dim: Optional[int] = None,
) -> jax.Array:
    """Single-position decode attention against a (possibly padded) KV cache.

    ``v_cache`` None reads V as the first ``v_dim`` columns of K (a latent
    cache); the result is then ``v_dim`` wide.

    Contiguous layout: q (B, 1, Hq, D); caches (B, S_max, Hkv, D);
    cache_len: valid prefix length (scalar or (B,)). Linear in S_max — used
    for decode_32k/long_500k serve steps. Window applies Mistral-style SWA
    over absolute positions.

    Paged layout (``block_table`` given): caches are shared page pools
    (n_pages, Hkv, page, D); ``block_table`` (B, n_blocks) maps each row's
    logical page j to a physical pool page, and pages are visited in
    ``KVSchedule`` order (``order='sawtooth'`` alternates direction per
    decode step, parity keyed on ``cache_len``). The paged path is ragged:
    q may carry C > 1 chunk positions per row with per-row ``q_lens``
    (chunked prefill / mixed serve steps) — see
    :func:`paged_decode_attention`. ``order_group`` (paged only) overrides
    the static order with a traced effective reversal-group scalar
    (``schedule.resolve_order_group``) so the order can change per step
    without retracing.
    """
    if block_table is not None:
        return paged_decode_attention(
            q,
            k_cache,
            v_cache,
            cache_len,
            block_table,
            q_lens=q_lens,
            window=window,
            scale=scale,
            order=order,
            snake_group=snake_group,
            order_group=order_group,
            v_dim=v_dim,
        )
    assert q_lens is None, "q_lens requires the paged layout (block_table)"
    assert order_group is None, "order_group requires the paged layout"
    if v_cache is None:
        v_cache = k_cache[..., :v_dim]
    b, one, hq, d = q.shape
    assert one == 1
    _, s_max, hkv, _ = k_cache.shape
    g = hq // hkv
    scale_ = d ** -0.5 if scale is None else scale
    lens = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32)) * scale_
    pos = jnp.arange(s_max)[None, :]  # (1, S)
    valid = pos < lens[:, None]
    if window is not None:
        valid &= pos > (lens[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, hq, v_cache.shape[-1]).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    cache_len: jax.Array | int,
    block_table: jax.Array,
    *,
    q_lens: Optional[jax.Array] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    order: Order | str = Order.CYCLIC,
    snake_group: Optional[int] = None,
    order_group: Optional[jax.Array] = None,
    v_dim: Optional[int] = None,
) -> jax.Array:
    """Blockwise ragged attention over a paged KV pool, schedule-ordered.

    q: (B, C, Hq, D) — a ragged chunk of C query positions per row (C=1 is
    plain decode; C>1 is a chunked-prefill / mixed serve step).
    k_pool/v_pool: (n_pages, Hkv, page, D) — one shared pool across the
    batch. block_table: (B, n_blocks) int32, logical page j of row b lives
    in pool page ``block_table[b, j]``. cache_len: (B,) or scalar valid KV
    lengths *including* this chunk's writes. q_lens: (B,) number of valid
    query rows in each row's chunk (default: all C); query t of row b sits
    at absolute position ``cache_len - q_len + t`` and attends causally to
    positions ``<=`` its own — causal masking *inside* the chunk, so one
    ragged step serves decode rows (q_len 1) and prefill chunks (q_len up
    to C) together.

    Pages are streamed through online softmax in the order given by a
    :class:`KVSchedule` over the gathered pages; sawtooth parity is driven
    per row by ``cache_len`` (the visited length) so consecutive steps of
    one sequence reverse direction (the tail pages of step t are the head
    pages of step t+1 — the decode analogue of the paper's prefill
    reordering). The result is traversal-order invariant, matching the
    contiguous oracle.

    Fully-masked rows (q_len 0 / len 0 — e.g. a free slot in a
    continuous-batching pool) return exact zeros rather than NaN.

    ``v_pool`` None is a latent pool: V is the first ``v_dim`` columns of
    each K page (whose columns past q's width are zero padding), and the
    result is ``v_dim`` wide.
    """
    b, c, hq, d = q.shape
    dv = v_dim if v_pool is None else v_pool.shape[-1]
    n_pages, hkv, page, _ = k_pool.shape
    n_blocks = block_table.shape[1]
    g = hq // hkv
    scale_ = d ** -0.5 if scale is None else scale
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    qls = (
        jnp.full((b,), c, jnp.int32)
        if q_lens is None
        else jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (b,))
    )
    # Absolute position of each query row; invalid rows (t >= q_len) get a
    # fully-masked position so they contribute exact zeros.
    tq = jnp.arange(c, dtype=jnp.int32)[None, :]
    q_pos = (lens - qls)[:, None] + tq          # (B, C)
    q_valid = tq < qls[:, None]

    if order_group is not None:
        # Runtime-switchable order: the effective reversal group arrives as
        # a traced scalar operand (schedule.resolve_order_group), so a serve
        # engine can flip cyclic/sawtooth/block_snake between steps inside
        # one compiled step — the static ``order`` argument is ignored.
        visit = page_visit_order_dynamic(lens, n_blocks, order_group)
    else:
        sched = KVSchedule(
            order, n_q=1, n_kv=n_blocks, causal=False, q_block=1,
            kv_block=page, snake_group=snake_group,
        )
        visit = sched.page_order(lens)  # (B, n_blocks) logical page ids
    phys = jnp.take_along_axis(block_table.astype(jnp.int32), visit, axis=1)

    qf = q.astype(jnp.float32).reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4)
    qf = qf * scale_                            # (B, Hkv, G, C, D)
    offs = jnp.arange(page, dtype=jnp.int32)[None, :]

    def body(carry, j):
        m, l, acc = carry
        logical = jax.lax.dynamic_index_in_dim(visit, j, axis=1, keepdims=False)
        pid = jax.lax.dynamic_index_in_dim(phys, j, axis=1, keepdims=False)
        k_j = k_pool[pid].astype(jnp.float32)  # (B, Hkv, page, D)
        v_j = k_j[..., :dv] if v_pool is None else v_pool[pid].astype(jnp.float32)
        k_j = k_j[..., :d]  # a latent pool's rows are zero-padded past q's width
        pos = logical[:, None] * page + offs   # (B, page) absolute positions
        # (B, C, page): kv visible to query row t iff within [0, len),
        # causally at-or-before the query's own position, and the query
        # row itself is valid; a window trims the low end per query row.
        valid = (pos[:, None, :] <= q_pos[:, :, None]) & q_valid[:, :, None]
        valid &= pos[:, None, :] < lens[:, None, None]
        if window is not None:
            valid &= pos[:, None, :] > (q_pos[:, :, None] - window)
        ok = valid[:, None, None, :, :]        # (B, 1, 1, C, page)
        s = jnp.einsum("bhgcd,bhkd->bhgck", qf, k_j)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhgck,bhkd->bhgcd", p, v_j)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, hkv, g, c), NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, c), jnp.float32),
        jnp.zeros((b, hkv, g, c, dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (free slots)
    o = acc / l[..., None]           # (B, Hkv, G, C, Dv)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, c, hq, dv).astype(q.dtype)
