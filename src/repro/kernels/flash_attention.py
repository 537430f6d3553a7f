"""Pallas TPU flash-attention kernels with schedulable KV traversal.

The paper's Sawtooth Wavefront Reordering (Alg. 4) is expressed *entirely in
the BlockSpec index_map*: the kernel bodies are identical for every
traversal order. The index arithmetic itself is not owned here — each
launch compiles a ``repro.core.schedule.Traversal`` and consumes its traced
lowerings (``kv_block_index`` for the forward/dQ grid,
``stream_block_index`` for the transposed dK/dV grid), so the kernels, the
blockwise XLA path, the traffic models, and the cache simulator all share
one source of truth for the order (``block_snake`` included). On TPU the
traversal controls the HBM->VMEM DMA stream of the Pallas software
pipeline; consecutive grid steps that map to the same block elide the copy,
so the sawtooth boundary block (last block of pass i == first block of pass
i+1) is fetched once instead of twice (see kernels/traffic.py for the
counting model and DESIGN.md §2/§3 for the GB10->TPU adaptation and the IR).

Forward dataflow is the paper's split-Q (Alg. 1): the Q tile is resident
(one per grid row), K/V tiles stream. Causal and sliding-window ranges are
*clamped in the index_map* so out-of-range steps re-map to a boundary block
(elided fetch) with compute skipped — the TPU analogue of causal grid
trimming.

The fused backward (FlashAttention-2 style, cf. the CUTLASS Hopper case
study) is three kernels consuming the forward's saved ``(o, lse)``:

  * ``_delta_kernel``      — delta = rowsum(dO * O), per-row preprocess;
  * ``_dq_kernel``         — the forward grid (Q resident, KV streamed);
  * ``_dkv_kernel``        — the *transposed* grid: each KV tile is
    resident (accumulating dK/dV) and the Q-side operands (Q, dO, lse,
    delta) stream — exactly the cyclic-traversal reuse pathology the
    reordering targets, now on the Q stream. The whole per-resident stream
    (all GQA groups over the trimmed Q range) is one sweep, reordered as
    one range with parity keyed on the resident KV-tile counter.
    ``core.schedule.BwdKVSchedule`` is the host-side (G=1) model.

Layout: q (B, Sq, Hq, D), k/v (B, Skv, Hkv, D), GQA folded by stacking the
``G = Hq // Hkv`` query groups along the row axis per KV head.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import Order, Traversal

__all__ = ["flash_attention_fwd", "flash_attention_bwd", "MASK_VALUE"]

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128


def _tile_mask(q_tile, jj, *, q_block, kv_block, causal, window, kv_len):
    """(q_block, kv_block) visibility mask for tile pair (q_tile, jj)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0) + q_tile * q_block
    cols = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1) + jj * kv_block
    ok = cols < kv_len
    if causal:
        ok &= cols <= rows
    if window is not None:
        ok &= cols > rows - window
    return ok


def _tr_mask_kwargs(tr: Traversal, kv_len: int) -> dict:
    return dict(
        q_block=tr.q_block,
        kv_block=tr.kv_block,
        causal=tr.causal,
        window=tr.window,
        kv_len=kv_len,
    )


# --------------------------------------------------------------------------
# layout folding (GQA groups stacked along the row axis per KV head)
# --------------------------------------------------------------------------


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    rem = (-x.shape[axis]) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _fold_q(x: jax.Array, hkv: int, g: int, q_block: int):
    """(B, Sq, Hq, D) -> ((B*Hkv, G*Sq_p, Dp), Sq_p)."""
    b, sq, _, d = x.shape
    xf = x.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,Sq,D)
    xf = _pad_axis(xf, 3, q_block)
    sq_p = xf.shape[3]
    xf = xf.reshape(b * hkv, g * sq_p, d)
    return _pad_axis(xf, 2, LANES), sq_p


def _fold_kv(x: jax.Array, kv_block: int) -> jax.Array:
    """(B, Skv, Hkv, D) -> (B*Hkv, Skv_p, Dp)."""
    b, skv, hkv, d = x.shape
    xf = _pad_axis(x.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d), 1, kv_block)
    return _pad_axis(xf, 2, LANES)


def _fold_rows(x: jax.Array, hkv: int, g: int, q_block: int) -> jax.Array:
    """Per-row vector (B, Sq, Hq) -> (B*Hkv, G*Sq_p), zero-padded."""
    b, sq, _ = x.shape
    xf = x.reshape(b, sq, hkv, g).transpose(0, 2, 3, 1)  # (B,Hkv,G,Sq)
    xf = _pad_axis(xf, 3, q_block)
    sq_p = xf.shape[3]
    return xf.reshape(b * hkv, g * sq_p)


def _clamp_blocks(q_block: int, kv_block: int, sq: int, skv: int):
    q_block = min(q_block, max(8, 1 << (sq - 1).bit_length()))
    kv_block = min(kv_block, max(128, 1 << (skv - 1).bit_length()))
    return q_block, kv_block


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest,
    tr: Traversal,
    kv_len: int,
    scale: float,
    emit_lse: bool,
):
    lse_ref = rest[0] if emit_lse else None
    m_scr, l_scr, acc_scr = rest[-3:]
    i = pl.program_id(1)
    j = pl.program_id(2)
    jj, valid = tr.kv_block_index(i, j)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(valid)
    def _compute():
        q = q_ref[0]  # (qb, D)
        k = k_ref[0]  # (kb, D)
        v = v_ref[0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (qb, kb)

        q_tile = jax.lax.rem(i, tr.n_q)
        ok = _tile_mask(q_tile, jj, **_tr_mask_kwargs(tr, kv_len))
        s = jnp.where(ok, s, MASK_VALUE)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Explicit mask on p: with a reversed-causal traversal the *diagonal*
        # block can be visited first on odd passes, where early rows have no
        # valid columns yet — exp(mask - mask) would poison l without this.
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)  # (qb, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == tr.n_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if emit_lse:
            lse = m_scr[:, :1] + jnp.log(l)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


@functools.partial(
    jax.jit,
    static_argnames=(
        "order",
        "causal",
        "window",
        "scale",
        "q_block",
        "kv_block",
        "snake_group",
        "interpret",
        "return_lse",
    ),
)
def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    order: Order | str = Order.SAWTOOTH,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_block: int = 256,
    kv_block: int = 256,
    snake_group: Optional[int] = None,
    interpret: bool = False,
    return_lse: bool = False,
) -> jax.Array:
    """Forward flash attention via pl.pallas_call. See module docstring.

    With ``return_lse=True`` returns ``(o, lse)``; lse is the per-row
    log-sum-exp of the scaled scores, shape (B, Sq, Hq) f32 — the residual
    the fused backward consumes instead of recomputing the forward.
    ``snake_group`` sizes the ``block_snake`` reversal window (KV tiles).
    """
    order = Order.parse(order)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    g = hq // hkv
    scale_ = float(d**-0.5 if scale is None else scale)
    q_block, kv_block = _clamp_blocks(q_block, kv_block, sq, skv)

    qf, sq_p = _fold_q(q, hkv, g, q_block)
    nq = sq_p // q_block
    kf = _fold_kv(k, kv_block)
    vf = _fold_kv(v, kv_block)
    skv_p = kf.shape[1]
    nkv = skv_p // kv_block
    dp = kf.shape[2]

    tr = Traversal(
        order=order,
        n_q=nq,
        n_kv=nkv,
        causal=causal,
        window=window,
        q_block=q_block,
        kv_block=kv_block,
        n_groups=g,
        snake_group=snake_group,
    )

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        jj, _ = tr.kv_block_index(i, j)
        return (bh, jj, 0)

    kernel = functools.partial(
        _fwd_kernel, tr=tr, kv_len=skv, scale=scale_, emit_lse=return_lse
    )

    grid = (b * hkv, g * nq, nkv)

    out_shape = [jax.ShapeDtypeStruct((b * hkv, g * sq_p, dp), q.dtype)]
    out_specs = [pl.BlockSpec((1, q_block, dp), q_map)]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((b * hkv, g * sq_p, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, q_block, LANES), q_map))

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, dp), q_map),
            pl.BlockSpec((1, kv_block, dp), kv_map),
            pl.BlockSpec((1, kv_block, dp), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((q_block, LANES), jnp.float32),
            pltpu.VMEM((q_block, LANES), jnp.float32),
            pltpu.VMEM((q_block, dp), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        name="flash_attention_fwd",
    )(qf, kf, vf)

    out = outs[0].reshape(b, hkv, g, sq_p, dp)[:, :, :, :sq, :d]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    if not return_lse:
        return out
    lse = outs[1][:, :, 0].reshape(b, hkv, g, sq_p)[:, :, :, :sq]
    lse = lse.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    return out, lse


# --------------------------------------------------------------------------
# backward: delta preprocess
# --------------------------------------------------------------------------


def _delta_kernel(o_ref, do_ref, delta_ref):
    """delta = rowsum(dO * O): the softmax-grad dot the dQ/dKV kernels reuse."""
    prod = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
    delta_ref[0] = jnp.broadcast_to(
        jnp.sum(prod, axis=-1, keepdims=True), delta_ref.shape[1:]
    )


# --------------------------------------------------------------------------
# backward: dQ (forward grid — Q resident, KV streamed)
# --------------------------------------------------------------------------


def _dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_scr,
    *,
    tr: Traversal,
    kv_len: int,
    scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    jj, valid = tr.kv_block_index(i, j)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(valid)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse_row = lse_ref[0][:, :1]  # (qb, 1)
        delta_row = delta_ref[0][:, :1]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        q_tile = jax.lax.rem(i, tr.n_q)
        ok = _tile_mask(q_tile, jj, **_tr_mask_kwargs(tr, kv_len))
        # exp(s - lse) is the *normalized* P (lse = m + log l) — masked
        # explicitly so padded/fully-masked rows can't poison the grads.
        p = jnp.where(ok, jnp.exp(s - lse_row), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_row) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == tr.n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# backward: dK/dV (transposed grid — KV resident, Q/dO streamed)
# --------------------------------------------------------------------------


def _dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    tr: Traversal,
    kv_len: int,
    scale: float,
):
    jkv = pl.program_id(1)
    u = pl.program_id(2)
    _, qi, valid = tr.stream_block_index(jkv, u)

    @pl.when(u == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(valid)
    def _compute():
        q = q_ref[0]  # (qb, D)
        k = k_ref[0]  # (kb, D)
        v = v_ref[0]
        do = do_ref[0]
        lse_row = lse_ref[0][:, :1]
        delta_row = delta_ref[0][:, :1]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # (qb, kb)
        ok = _tile_mask(qi, jkv, **_tr_mask_kwargs(tr, kv_len))
        p = jnp.where(ok, jnp.exp(s - lse_row), 0.0)
        # dV += P^T @ dO  (contract the q rows)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_row) * scale
        # dK += dS^T @ Q
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(u == tr.grid_rows - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "order",
        "causal",
        "window",
        "scale",
        "q_block",
        "kv_block",
        "snake_group",
        "interpret",
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
    order: Order | str = Order.SAWTOOTH,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_block: int = 256,
    kv_block: int = 256,
    snake_group: Optional[int] = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Pallas flash backward from saved ``(o, lse)`` residuals.

    Launches the delta preprocess, the dQ kernel (forward grid) and the
    dK/dV kernel (transposed grid), all traversed per the compiled
    ``Traversal``. No forward recompute: the normalized probabilities are
    recovered as ``exp(s - lse)``. Block sizes may differ from the
    forward's (they are autotuned separately — benchmarks/hillclimb.py).
    """
    order = Order.parse(order)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    g = hq // hkv
    scale_ = float(d**-0.5 if scale is None else scale)
    q_block, kv_block = _clamp_blocks(q_block, kv_block, sq, skv)

    qf, sq_p = _fold_q(q, hkv, g, q_block)
    dof, _ = _fold_q(do.astype(q.dtype), hkv, g, q_block)
    of, _ = _fold_q(o, hkv, g, q_block)
    kf = _fold_kv(k, kv_block)
    vf = _fold_kv(v, kv_block)
    nq = sq_p // q_block
    skv_p = kf.shape[1]
    nkv = skv_p // kv_block
    dp = kf.shape[2]

    tr = Traversal(
        order=order,
        n_q=nq,
        n_kv=nkv,
        causal=causal,
        window=window,
        q_block=q_block,
        kv_block=kv_block,
        n_groups=g,
        snake_group=snake_group,
    )

    # lse/delta stream lane-replicated as (q_block, LANES) f32 tiles — the
    # upstream JAX TPU flash-bwd residual layout: Mosaic has no cheap
    # lane->sublane broadcast, so replicating at materialization beats an
    # in-kernel transpose. kernels/traffic.py counts the replicated bytes.
    lse_f = _fold_rows(lse.astype(jnp.float32), hkv, g, q_block)
    lse_f = jnp.broadcast_to(lse_f[:, :, None], (b * hkv, g * sq_p, LANES))

    def row_map(bh, i):
        return (bh, i, 0)

    interp = {"interpret": interpret}
    compiler3 = {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        )
    }

    # ---- delta = rowsum(dO * O) ---------------------------------------------
    delta_f = pl.pallas_call(
        _delta_kernel,
        grid=(b * hkv, g * nq),
        in_specs=[
            pl.BlockSpec((1, q_block, dp), row_map),
            pl.BlockSpec((1, q_block, dp), row_map),
        ],
        out_specs=pl.BlockSpec((1, q_block, LANES), row_map),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g * sq_p, LANES), jnp.float32),
        **interp,
        name="flash_attention_bwd_delta",
    )(of, dof)

    # ---- dQ: forward grid ----------------------------------------------------
    def q_map3(bh, i, j):
        return (bh, i, 0)

    def kv_map3(bh, i, j):
        jj, _ = tr.kv_block_index(i, j)
        return (bh, jj, 0)

    dqf = pl.pallas_call(
        functools.partial(_dq_kernel, tr=tr, kv_len=skv, scale=scale_),
        grid=(b * hkv, g * nq, nkv),
        in_specs=[
            pl.BlockSpec((1, q_block, dp), q_map3),
            pl.BlockSpec((1, kv_block, dp), kv_map3),
            pl.BlockSpec((1, kv_block, dp), kv_map3),
            pl.BlockSpec((1, q_block, dp), q_map3),
            pl.BlockSpec((1, q_block, LANES), q_map3),
            pl.BlockSpec((1, q_block, LANES), q_map3),
        ],
        out_specs=pl.BlockSpec((1, q_block, dp), q_map3),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g * sq_p, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_block, dp), jnp.float32)],
        name="flash_attention_bwd_dq",
        **interp,
        **compiler3,
    )(qf, kf, vf, dof, lse_f, delta_f)

    # ---- dK/dV: transposed grid ---------------------------------------------
    def stream_map(bh, jkv, u):
        gg, qi, _ = tr.stream_block_index(jkv, u)
        return (bh, gg * nq + qi, 0)

    def resident_map(bh, jkv, u):
        return (bh, jkv, 0)

    dkf, dvf = pl.pallas_call(
        functools.partial(_dkv_kernel, tr=tr, kv_len=skv, scale=scale_),
        grid=(b * hkv, nkv, g * nq),
        in_specs=[
            pl.BlockSpec((1, q_block, dp), stream_map),
            pl.BlockSpec((1, kv_block, dp), resident_map),
            pl.BlockSpec((1, kv_block, dp), resident_map),
            pl.BlockSpec((1, q_block, dp), stream_map),
            pl.BlockSpec((1, q_block, LANES), stream_map),
            pl.BlockSpec((1, q_block, LANES), stream_map),
        ],
        out_specs=[
            pl.BlockSpec((1, kv_block, dp), resident_map),
            pl.BlockSpec((1, kv_block, dp), resident_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, skv_p, dp), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, skv_p, dp), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv_block, dp), jnp.float32),
            pltpu.VMEM((kv_block, dp), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        **interp,
        **compiler3,
    )(qf, kf, vf, dof, lse_f, delta_f)

    dq = dqf.reshape(b, hkv, g, sq_p, dp)[:, :, :, :sq, :d]
    dq = dq.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    dk = dkf.reshape(b, hkv, skv_p, dp)[:, :, :skv, :d].transpose(0, 2, 1, 3)
    dv = dvf.reshape(b, hkv, skv_p, dp)[:, :, :skv, :d].transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
