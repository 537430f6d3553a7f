"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Addresses the §Roofline finding that SSM train/prefill cells are
memory-bound on f32 chunk intermediates: the (c×c) decay/score matrices and
per-chunk states live in VMEM scratch and never touch HBM; only x/dt/B/C
chunks stream in and y streams out.

Grid: (B·H, n_chunks), chunk axis sequential — the running state is carried
in VMEM scratch across chunk steps (reset at chunk 0, emitted at the last).
B/C projections are shared across heads (Mamba-2 G=1), so their BlockSpec
index_map repeats the same (batch, chunk) block for all H heads of a batch —
consecutive grid steps then elide the fetch in the Pallas pipeline, the same
revisiting mechanism the sawtooth schedule exploits for attention
(DESIGN.md §2). Grid order (h outer would break this) is (b, h) flattened
with h fastest, giving H−1 elided B/C fetches per (batch, chunk).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_fwd"]


def _ssd_kernel(
    x_ref,      # (1, c, P)
    da_ref,     # (1, c)      dt * a  (<= 0)
    dt_ref,     # (1, c)
    b_ref,      # (1, c, N)
    c_ref,      # (1, c, N)
    init_ref,   # (1, P, N)
    y_ref,      # (1, c, P)  out
    s_out_ref,  # (1, P, N)  out (final state)
    state_scr,  # (P, N) f32
    *,
    n_chunks: int,
    chunk: int,
):
    z = pl.program_id(1)

    @pl.when(z == 0)
    def _init():
        state_scr[...] = init_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)        # (c, P)
    da = da_ref[0].astype(jnp.float32)      # (c,)
    dt = dt_ref[0].astype(jnp.float32)
    bm = b_ref[0].astype(jnp.float32)       # (c, N)
    cm = c_ref[0].astype(jnp.float32)

    cum = jnp.cumsum(da)                    # (c,)
    # intra-chunk: W[i,j] = (c_i . b_j) * exp(cum_i - cum_j) * dt_j,  j <= i
    diff = cum[:, None] - cum[None, :]
    tril = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    decay = jnp.where(tril, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (c, c)
    w = cb * decay * dt[None, :]
    y_intra = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (c, P)

    # inter-chunk: y_i += c_i . (exp(cum_i) * S_in)
    state = state_scr[...]
    c_scaled = cm * jnp.exp(cum)[:, None]   # (c, N)
    y_inter = jax.lax.dot_general(
        c_scaled, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (c, P)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S_out = exp(cum_last) S_in + sum_j dt_j e^{cum_last-cum_j} x_j b_j^T
    cum_last = cum[chunk - 1]
    coeff = (dt * jnp.exp(cum_last - cum))[:, None] * x  # (c, P)
    s_new = jnp.exp(cum_last) * state + jax.lax.dot_general(
        coeff, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state_scr[...] = s_new

    @pl.when(z == n_chunks - 1)
    def _emit():
        s_out_ref[0] = s_new.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_fwd(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)   post-softplus
    a: jax.Array,    # (H,)        negative decay rates
    b: jax.Array,    # (B, S, N)
    c: jax.Array,    # (B, S, N)
    *,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Pallas SSD forward. Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, max(8, 1 << (s - 1).bit_length()))
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nz = sp // chunk

    da = dt * a[None, None, :]                                  # (B, Sp, H)
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, sp, p)        # (BH, Sp, P)
    daf = da.transpose(0, 2, 1).reshape(bsz * h, sp)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, sp)
    init = (
        jnp.zeros((bsz, h, p, n), jnp.float32) if init_state is None else init_state
    ).reshape(bsz * h, p, n)

    kernel = functools.partial(_ssd_kernel, n_chunks=nz, chunk=chunk)

    def bh_map(bh, z):
        return (bh, z, 0)

    def seq_map(bh, z):
        return (bh, z)

    def bc_map(bh, z):
        return (bh // h, z, 0)  # B/C shared across heads: repeated -> elided

    def state_map(bh, z):
        return (bh, 0, 0)

    y, s_out = pl.pallas_call(
        kernel,
        grid=(bsz * h, nz),
        in_specs=[
            pl.BlockSpec((1, chunk, p), bh_map),
            pl.BlockSpec((1, chunk), seq_map),
            pl.BlockSpec((1, chunk), seq_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, p, n), state_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), bh_map),
            pl.BlockSpec((1, p, n), state_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, sp, p), x.dtype),
            jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="ssd_fwd",
    )(xf, daf, dtf, b, c, init)

    y = y.reshape(bsz, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    return y, s_out.reshape(bsz, h, p, n)
