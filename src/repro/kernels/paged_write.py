"""Pallas TPU write of a serve step's new KV rows into a stacked page pool,
in place.

A serve step's layer scan keeps each page array whole, stacked over the
layers ([L, n_pages, heads, page, width]; int8 scale planes [L, n_pages,
heads, page]), and writes every layer's new rows into that stack. An XLA
scatter there would slice the layer out, relay it out for the scatter and
write it back, every layer of every step; this kernel aliases the stack
(``input_output_aliases``) and reads and writes only the tiles the step's
rows land in.

A bf16 row shares its 32-bit words with its neighbour row (int8 with three
more), so a row cannot be written by a DMA alone. The unit of work is a
*group*: the consecutive positions of one sequence that fill whole row
tiles of the page arrays (16 of bf16, 32 of int8), so a group never
crosses a page. The grid walks (sequence, group) pairs; each step reads
the group's rows of every page array (a (heads, group, width) tile; for a
scale plane, the page's (heads, page) tile), merges the new rows in, and
writes the tile back. A step whose group holds no new row repeats the previous step's
block indices, so the pipeline fetches and writes nothing for it, and a
tile visited by consecutive steps is fetched once and written once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_write_fwd"]


def _group_rows(dtypes, page: int) -> int:
    """Positions a group holds: the row tiling of the narrowest page dtype
    (8 rows of 32 bits, so 16 of bf16 and 32 of int8), within one page."""
    rows = max(8 * 4 // jnp.dtype(dt).itemsize for dt in dtypes)
    return math.gcd(rows, page)


def _write_kernel(
    page_ref,   # scalar prefetch: (S,) flat page id of each step's tile
    grp_ref,    # (S,) the tile's group within its page
    src_ref,    # (S,) the step whose values the step reads
    lo_ref,     # (S,) first row of the group to write
    hi_ref,     # (S,) one past the last (lo == hi: nothing to write)
    fgrp_ref,   # (S,) 1 where the step's group tile is not the previous step's
    fpage_ref,  # (S,) 1 where the step's page is not the previous step's
    *refs,      # the values, the pools in (aliased), the pools out
    n: int,
    rows: int,
    scales: tuple,
):
    vals, pools_in, pools_out = refs[:n], refs[n:2 * n], refs[2 * n:]
    s = pl.program_id(0)
    lo, hi = lo_ref[s], hi_ref[s]
    for v_ref, in_ref, out_ref, scale in zip(vals, pools_in, pools_out, scales):
        first = (fpage_ref if scale else fgrp_ref)[s] > 0

        @pl.when(first)
        def _load(in_ref=in_ref, out_ref=out_ref):
            out_ref[...] = in_ref[...]

        shape = out_ref.shape
        if scale:  # (1, heads, page): the group's columns of the page
            r = jax.lax.broadcasted_iota(jnp.int32, shape, 2) - grp_ref[s] * rows
        else:      # (1, heads, rows, width): the group's rows
            r = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        keep = (r >= lo) & (r < hi)
        out_ref[...] = jnp.where(keep, v_ref[...], out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_fwd(
    pools: tuple,
    vals: tuple,
    block_table: jax.Array,
    starts: jax.Array,
    q_lens: jax.Array,
    layer: jax.Array,
    *,
    interpret: bool = False,
) -> tuple:
    """Write row b's chunk positions ``starts[b] + t``, ``t < q_lens[b]``,
    of layer ``layer`` into stacked page arrays, in place.

    ``pools``: [L, n_pages, heads, page, width] page arrays and [L, n_pages,
    heads, page] scale planes; ``vals``: each pool's (B, C, heads, width) or
    (B, C, heads) new values, already in its dtype and width. A position
    lands at ``[layer, block_table[b, pos // page], :, pos % page]``.
    Positions past ``q_lens`` (a ragged step's padding, free slots) and past
    the block table's capacity are not written. Returns the pools, aliased
    to the inputs (donate them to the enclosing jit to update in place).
    """
    lead = pools[0].shape[:2]
    page = pools[0].shape[3]
    scales = tuple(p.ndim == 4 for p in pools)
    b, c = vals[0].shape[:2]
    n_blocks = block_table.shape[1]
    rows = _group_rows([p.dtype for p in pools], page)
    g = (c + rows - 2) // rows + 1  # groups a row's C positions can touch
    n_steps = b * g
    base = layer.astype(jnp.int32) * lead[1]

    starts = starts.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    j = jnp.arange(g, dtype=jnp.int32)[None, :]
    shift = starts % rows                                  # (B,)
    t0 = j * rows - shift[:, None]                         # (B, G) chunk index of row 0
    gpos = starts[:, None] + t0                            # (B, G) position of row 0
    room = n_blocks * page - gpos
    lo = jnp.clip(-t0, 0, rows)
    hi = jnp.clip(jnp.minimum(q_lens[:, None] - t0, room), 0, rows)
    live = (lo < hi).reshape(-1)
    logical = jnp.clip(gpos // page, 0, n_blocks - 1)
    pid = base + jnp.take_along_axis(block_table.astype(jnp.int32), logical, axis=1)
    grp = (gpos % page) // rows

    # A step with nothing to write takes the last live step's tiles (or the
    # layer's dummy page 0 before any), so the pipeline moves no data.
    step = jnp.arange(n_steps, dtype=jnp.int32)
    src = jax.lax.cummax(jnp.where(live, step, -1))
    held = src >= 0
    src = jnp.maximum(src, 0)
    pid = jnp.where(held, pid.reshape(-1)[src], base)
    grp = jnp.where(held, grp.reshape(-1)[src], 0)
    lo = jnp.where(live, lo.reshape(-1), 0)
    hi = jnp.where(live, hi.reshape(-1), 0)
    prev = lambda x: jnp.concatenate([x[:1] - 1, x[:-1]])
    fpage = (pid != prev(pid)).astype(jnp.int32)
    fgrp = ((pid != prev(pid)) | (grp != prev(grp))).astype(jnp.int32)

    # Each step's values, laid out as its tiles: group row r of step (b, j)
    # is chunk position t0[b, j] + r (masked in the kernel where invalid).
    t = jnp.clip(
        jnp.arange(g * rows, dtype=jnp.int32)[None, :] - shift[:, None], 0, c - 1
    )
    tiles = []
    for v, scale in zip(vals, scales):
        v = jnp.take_along_axis(v, t.reshape((b, g * rows) + (1,) * (v.ndim - 2)), axis=1)
        v = v.reshape((b * g, rows) + v.shape[2:])
        if scale:  # (S, rows, heads) -> (S, heads, page), column k at k % rows
            v = jnp.tile(v.transpose(0, 2, 1), (1, 1, page // rows))
        else:      # (S, rows, heads, width) -> (S, heads, rows, width)
            v = v.transpose(0, 2, 1, 3)
        tiles.append(v)

    flat = [p.reshape((-1,) + p.shape[2:]) for p in pools]

    def val_map(scale, i, page_ref, grp_ref, src_ref, *_):
        return (src_ref[i], 0, 0) if scale else (src_ref[i], 0, 0, 0)

    def pool_map(scale, i, page_ref, grp_ref, *_):
        return (page_ref[i], 0, 0) if scale else (page_ref[i], 0, grp_ref[i], 0)

    def spec(p, scale, index_map):
        block = (1,) + p.shape[1:] if scale else (1, p.shape[1], rows, p.shape[3])
        return pl.BlockSpec(block, functools.partial(index_map, scale))

    n = len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_steps,),
        in_specs=[spec(p, sc, val_map) for p, sc in zip(flat, scales)]
        + [spec(p, sc, pool_map) for p, sc in zip(flat, scales)],
        out_specs=[spec(p, sc, pool_map) for p, sc in zip(flat, scales)],
    )
    out = pl.pallas_call(
        functools.partial(_write_kernel, n=n, rows=rows, scales=scales),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in flat],
        input_output_aliases={7 + n + i: i for i in range(n)},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_write",
    )(pid, grp, src, lo, hi, fgrp, fpage, *tiles, *flat)
    return tuple(o.reshape(p.shape) for o, p in zip(out, pools))
