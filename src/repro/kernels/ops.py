"""Public, differentiable, platform-dispatched kernel ops.

``attention`` / ``attention_decode`` are what the model layers call. Each op:

  * dispatches to the Pallas TPU kernel on TPU backends, the blockwise pure
    JAX path elsewhere (CPU dry-run / tests), or an explicit impl override
    ('pallas' | 'pallas_interpret' | 'xla' | 'jnp' | 'reference'),
  * carries the KV schedule (cyclic / sawtooth) through to whichever path,
  * is differentiable with a *fused* flash backward (DESIGN.md §7.5): the
    forward saves ``(o, lse)`` residuals and the backward dispatches to the
    Pallas backward kernels ('pallas' / 'pallas_interpret') or the fused
    blockwise JAX backward ('xla') — no forward recompute. ``impl='jnp'``
    keeps the old recompute-VJP path (differentiate through the blockwise
    forward) as the fallback; 'reference' recomputes through the
    full-materialization oracle (tiny shapes only).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import attention as core_attn
from repro.core.schedule import Order
from repro.kernels import ref as kref
from repro.kernels import flash_attention as kflash
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode_fwd
from repro.kernels.paged_write import paged_write_fwd
from repro.kernels.ssd import ssd_fwd

__all__ = ["attention", "attention_decode", "paged_write", "ssd", "default_impl"]

Impl = str  # 'auto' | 'pallas' | 'pallas_interpret' | 'xla' | 'jnp' | 'reference'

# Impls whose backward consumes (o, lse) residuals instead of recomputing.
_FUSED_BWD_IMPLS = ("pallas", "pallas_interpret", "xla")


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Impl) -> str:
    return default_impl() if impl == "auto" else impl


def _batch_axes(mesh, b: int):
    """Mesh axes a Pallas call splits a batch of ``b`` rows over, or None
    off a multi-device mesh (the call is then a plain one).

    GSPMD cannot partition a Mosaic kernel, so under a mesh each device
    runs the kernel on its own batch slice inside a ``shard_map``: mesh
    axes go to the batch while their product divides it. The operands
    are replicated along every other axis (heads are never split here).
    """
    if mesh.empty or mesh.size == 1:
        return None
    axes, n = [], 1
    for name, size in mesh.shape.items():
        if size > 1 and b % (n * size) == 0:
            axes.append(name)
            n *= size
    return tuple(axes)


def _per_shard(call, *args, out_ranks=(4,)):
    """``call(*args)`` batch shard by batch shard on the ambient mesh (see
    :func:`_batch_axes`); a plain call off-mesh. Operands and results are
    (B, S, H, D) arrays or (B, S, Hq) lse rows, told apart by rank."""
    axes = _batch_axes(jax.sharding.get_abstract_mesh(), args[0].shape[0])
    if axes is None:
        return call(*args)
    axes = axes or None  # no axis divides the batch: replicate everywhere
    spec = {4: P(axes, None, None, None), 3: P(axes, None, None)}
    out = tuple(spec[r] for r in out_ranks)
    return jax.shard_map(
        call,
        in_specs=tuple(spec[a.ndim] for a in args),
        out_specs=out if len(out) > 1 else out[0],
        check_vma=False,
    )(*args)


def _fwd_dispatch(
    q, k, v, *, impl, order, causal, window, scale, q_block, kv_block, score_dtype,
    snake_group, return_lse=False,
):
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        call = functools.partial(
            flash_attention_fwd,
            order=order,
            causal=causal,
            window=window,
            scale=scale,
            q_block=q_block,
            kv_block=kv_block,
            snake_group=snake_group,
            interpret=(impl == "pallas_interpret"),
            return_lse=return_lse,
        )
        return _per_shard(call, q, k, v, out_ranks=(4, 3) if return_lse else (4,))
    if impl in ("xla", "jnp"):
        return core_attn.flash_attention(
            q,
            k,
            v,
            order=order,
            causal=causal,
            window=window,
            scale=scale,
            q_block=q_block,
            kv_block=kv_block,
            score_dtype=score_dtype,
            snake_group=snake_group,
            return_lse=return_lse,
        )
    if impl == "reference":
        out = kref.flash_attention_ref(
            q, k, v, causal=causal, window=window, scale=scale
        )
        assert not return_lse, "reference impl has no fused backward"
        return out
    raise ValueError(f"unknown attention impl: {impl!r}")


@functools.lru_cache(maxsize=None)
def _make_attention(
    impl, order, causal, window, scale, q_block, kv_block, score_dtype,
    bwd_q_block, bwd_kv_block, snake_group,
):
    """Build a custom_vjp attention fn for one static configuration."""

    cfg = dict(
        impl=impl,
        order=order,
        causal=causal,
        window=window,
        scale=scale,
        q_block=q_block,
        kv_block=kv_block,
        score_dtype=score_dtype,
        snake_group=snake_group,
    )
    bqb = bwd_q_block or q_block
    bkb = bwd_kv_block or kv_block

    def _recompute_fn(q, k, v):
        # The recompute fallback differentiates the blockwise JAX path
        # (order kept: the schedule is math-preserving, so grads match any
        # forward impl) — one extra attention pass per backward.
        return core_attn.flash_attention(
            q,
            k,
            v,
            order=order,
            causal=causal,
            window=window,
            scale=scale,
            q_block=q_block,
            kv_block=kv_block,
            score_dtype=score_dtype,
            snake_group=snake_group,
        )

    @jax.custom_vjp
    def attn(q, k, v):
        return _fwd_dispatch(q, k, v, **cfg)

    def fwd(q, k, v):
        r = _resolve(impl)
        if r in _FUSED_BWD_IMPLS:
            o, lse = _fwd_dispatch(q, k, v, **{**cfg, "impl": r}, return_lse=True)
            return o, (q, k, v, o, lse)
        return attn(q, k, v), (q, k, v, None, None)

    def bwd(res, g):
        q, k, v, o, lse = res
        r = _resolve(impl)
        if r in ("pallas", "pallas_interpret"):
            call = functools.partial(
                kflash.flash_attention_bwd,
                order=order,
                causal=causal,
                window=window,
                scale=scale,
                q_block=bqb,
                kv_block=bkb,
                snake_group=snake_group,
                interpret=(r == "pallas_interpret"),
            )
            return _per_shard(call, q, k, v, o, lse, g, out_ranks=(4, 4, 4))
        if r == "xla":
            return core_attn.flash_attention_bwd(
                q, k, v, o, lse, g,
                order=order,
                causal=causal,
                window=window,
                scale=scale,
                q_block=bqb,
                kv_block=bkb,
                score_dtype=score_dtype,
                snake_group=snake_group,
            )
        if r == "reference":
            _, vjp = jax.vjp(
                lambda q_, k_, v_: kref.flash_attention_ref(
                    q_, k_, v_, causal=causal, window=window, scale=scale
                ),
                q, k, v,
            )
            return vjp(g)
        # 'jnp': memory-safe flash-style recompute (the pre-fused design).
        _, vjp = jax.vjp(_recompute_fn, q, k, v)
        return vjp(g)

    attn.defvjp(fwd, bwd)
    return attn


def attention(
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
    impl: Impl = "auto",
    score_dtype: str = "float32",
    bwd_q_block: Optional[int] = None,
    bwd_kv_block: Optional[int] = None,
    snake_group: Optional[int] = None,
) -> jax.Array:
    """Flash attention, layout (B, S, H, D); GQA via Hq > Hkv.

    ``bwd_q_block`` / ``bwd_kv_block`` size the fused backward kernels'
    tiles (default: the forward blocks) — the backward's working set is
    larger (Q, dO, lse, delta stream against a resident dK/dV accumulator),
    so its optimum is usually smaller; benchmarks/hillclimb.py autotunes
    them separately. ``snake_group`` sizes the ``block_snake`` order's
    reversal window (KV tiles); ignored by the other orders.
    """
    order = Order.parse(order)
    fn = _make_attention(
        impl, order, causal, window, scale, q_block, kv_block, score_dtype,
        bwd_q_block, bwd_kv_block, snake_group,
    )
    return fn(q, k, v)


def attention_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len,
    *,
    order: Order | str = Order.CYCLIC,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    chunk: int = 512,
    impl: Impl = "auto",
    block_table: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,
    snake_group: Optional[int] = None,
    order_group: Optional[jax.Array] = None,
    v_dim: Optional[int] = None,
) -> jax.Array:
    """Decode / ragged-chunk attention vs a KV cache. Not differentiated.

    ``v_cache`` None reads V as the first ``v_dim`` columns of K: a latent
    cache (multi-head latent attention), whose one page tile is both.

    ``block_table`` switches both backends to the paged layout: caches are
    shared (n_pages, Hkv, page, D) pools and pages are visited in schedule
    order through the table (sawtooth parity keyed per row on
    ``cache_len``). The paged layout is ragged: q may carry C > 1 chunk
    positions per row with per-row ``q_lens`` valid rows and causal masking
    inside the chunk — the serve engine's unified mixed step (decode rows
    at q_len 1 + chunked prefill rows) runs through exactly this call.
    ``order_group`` (paged only) overrides the static ``order`` with a
    traced effective reversal-group scalar
    (``core.schedule.resolve_order_group``) — both backends then compute
    the visit order from that operand, so the serve engine's online order
    adaptation switches traversal orders with zero recompiles.
    """
    order = Order.parse(order)
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        return flash_decode_fwd(
            q,
            k_cache,
            v_cache,
            cache_len,
            order=order,
            window=window,
            scale=scale,
            chunk=chunk,
            snake_group=snake_group,
            interpret=(impl == "pallas_interpret"),
            block_table=block_table,
            q_lens=q_lens,
            order_group=order_group,
            **({} if v_dim is None else {"v_dim": v_dim}),
        )
    if impl in ("xla", "reference"):
        return core_attn.decode_attention(
            q,
            k_cache,
            v_cache,
            cache_len,
            window=window,
            scale=scale,
            block_table=block_table,
            q_lens=q_lens,
            order=order,
            snake_group=snake_group,
            order_group=order_group,
            v_dim=v_dim,
        )
    raise ValueError(f"unknown decode impl: {impl!r}")


def paged_write(
    pools: dict,
    vals: dict,
    block_table: jax.Array,
    starts: jax.Array,
    q_lens: jax.Array,
    layer,
    *,
    impl: Impl = "auto",
) -> dict:
    """Write a ragged chunk's new rows into stacked page arrays: ``pools``
    maps each name to its [L, n_pages, heads, page(, width)] stack, ``vals``
    to its (B, C, heads(, width)) values; row b's positions ``starts[b] +
    t``, ``t < q_lens[b]``, land at ``[layer, block_table[b, pos // page],
    :, pos % page]``, and nothing else is written. On TPU the Pallas write
    updates the stacks in place (``kernels/paged_write.py``); elsewhere one
    scatter per array (``kernels/ref.py``)."""
    impl = _resolve(impl)
    names = list(vals)
    args = (
        tuple(pools[n] for n in names),
        tuple(vals[n].astype(pools[n].dtype) for n in names),
        block_table,
        starts,
        q_lens,
        jnp.asarray(layer, jnp.int32),
    )
    if impl in ("pallas", "pallas_interpret"):
        out = paged_write_fwd(*args, interpret=(impl == "pallas_interpret"))
    else:
        out = kref.paged_write_ref(*args)
    return dict(zip(names, out))


# --------------------------------------------------------------------------
# Mamba-2 SSD op (Pallas on TPU, chunked jnp elsewhere; bwd via jnp recompute)
# --------------------------------------------------------------------------


def _ssd_jnp(x, dt, a, b, c, init_state, chunk):
    from repro.models.ssm import ssd_chunked  # lazy: avoids import cycle

    return ssd_chunked(x, dt, a, b, c, chunk=chunk, init_state=init_state)


@functools.lru_cache(maxsize=None)
def _make_ssd(impl, chunk):
    def _dispatch(x, dt, a, b, c, init_state):
        r = _resolve(impl)
        if r in ("pallas", "pallas_interpret"):
            return ssd_fwd(
                x, dt, a, b, c, init_state=init_state, chunk=chunk,
                interpret=(r == "pallas_interpret"),
            )
        return _ssd_jnp(x, dt, a, b, c, init_state, chunk)

    @jax.custom_vjp
    def op(x, dt, a, b, c, init_state):
        return _dispatch(x, dt, a, b, c, init_state)

    def fwd(x, dt, a, b, c, init_state):
        return op(x, dt, a, b, c, init_state), (x, dt, a, b, c, init_state)

    def bwd(res, g):
        x, dt, a, b, c, init_state = res
        _, vjp = jax.vjp(
            lambda *args: _ssd_jnp(*args, chunk), x, dt, a, b, c, init_state
        )
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def ssd(x, dt, a, b, c, *, init_state=None, chunk: int = 128, impl: Impl = "auto"):
    """Mamba-2 SSD scan: (y, final_state). Layouts as kernels.ref.ssd_ref."""
    if init_state is None:
        bsz, _, h, p = x.shape
        init_state = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    return _make_ssd(impl, chunk)(x, dt, a, b, c, init_state)
