"""Speculative-decoding drafters for the continuous serve engine.

The engine's unified ragged mixed step (DESIGN.md §9) already verifies
arbitrary per-row ``q_len`` chunks with in-kernel causal masks — exactly
the primitive speculative decoding needs. A :class:`Drafter` proposes up
to K draft tokens per decode row each step boundary; the engine packs
``[cur, d_1..d_K]`` into that row as a ``q_len = K+1`` verification chunk
(the same shape a prefill chunk takes, so the two compiled step widths
survive), samples every chunk position in the one device step, commits the
longest draft prefix matching the sampled targets plus one bonus token,
and rolls the rejected tail back out of the KV pool
(``PagedKVPool.rollback`` — a host-side len decrement plus tail-page
release, no new kernel).

Two built-in drafters:

* :class:`NgramDrafter` — self-drafting prompt-lookup (PLD): the
  continuation of the most recent earlier occurrence of the row's trailing
  n-gram in its own prompt + generated stream. Pure host-side numpy, zero
  device cost, and strong on repetitive streams (summarization, code,
  templated output) where the model mostly re-emits what it has seen.

* :class:`ModelDrafter` — a small zoo model as draft, with its own
  :class:`~repro.serve.kv_pool.PagedKVPool` and its own two-width jitted
  ragged step (so the target engine's ``compiled_step_count()`` is
  untouched). The draft cache is synced lazily: before drafting, the
  longest common prefix of what the drafter has absorbed and the row's
  live committed stream is computed and the divergent tail — draft tokens
  the target rejected — is ``rollback``-ed, then the unabsorbed suffix is
  caught up chunk-wise and K greedy drafts are decoded. Drafting greedily
  is always sound: drafts are guesses, the target's verification sampling
  is what defines the output distribution.

Drafters are best-effort and stateless from the engine's point of view:
``draft_batch`` receives each row's full committed stream (prompt +
generated, including the last emitted token) and may return fewer than K
tokens (or none) for any row — the row then just runs as a plain
``q_len=1`` decode row.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import build_model
from repro.serve.kv_pool import PagedKVPool, assemble_cache_view

__all__ = ["Drafter", "NgramDrafter", "ModelDrafter", "make_drafter"]


class Drafter:
    """Draft-token proposer interface (one instance per engine).

    Lifecycle: ``reset()`` at each ``generate`` stream start, ``release(slot)``
    whenever the engine retires a slot (finish, preempt, failure), and
    ``draft_batch(items)`` once per step boundary with every eligible decode
    row. Per-slot state (the model drafter's cache bookkeeping) must key on
    the slot index — a released slot may be reused by a different request.
    """

    def reset(self) -> None:
        """A new generate stream begins; drop any per-slot state."""

    def release(self, slot: int) -> None:
        """``slot`` was retired; drop its state (the slot id will be reused)."""

    def draft(self, slot: int, context: np.ndarray, k: int) -> list[int]:
        """Propose up to ``k`` draft tokens continuing ``context`` (the
        row's full committed stream: prompt + generated, last token
        included). May return fewer, or ``[]`` to skip speculation."""
        raise NotImplementedError

    def draft_batch(
        self, items: Sequence[tuple[int, np.ndarray, int]]
    ) -> dict[int, list[int]]:
        """Draft for every ``(slot, context, k)`` row; default loops over
        :meth:`draft`. Batched drafters (one device pass for all rows)
        override this."""
        return {slot: self.draft(slot, ctx, k) for slot, ctx, k in items}


class NgramDrafter(Drafter):
    """Self-drafting n-gram / prompt-lookup drafter (no draft model).

    For the longest n in ``[ngram_min, ngram_max]`` whose trailing n-gram
    of ``context`` has an earlier occurrence, propose the tokens that
    followed the *most recent* such occurrence. Matching is exact and
    vectorized (one sliding-window comparison per n); cost is O(n_gram *
    len(context)) host work per row and no device work at all.
    """

    def __init__(self, *, ngram_max: int = 4, ngram_min: int = 1):
        if not 1 <= ngram_min <= ngram_max:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got [{ngram_min}, {ngram_max}]"
            )
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min

    def draft(self, slot: int, context: np.ndarray, k: int) -> list[int]:
        ctx = np.asarray(context, np.int32)
        n = len(ctx)
        if k < 1 or n < self.ngram_min + 1:
            return []
        for n_gram in range(min(self.ngram_max, n - 1), self.ngram_min - 1, -1):
            pat = ctx[-n_gram:]
            windows = np.lib.stride_tricks.sliding_window_view(ctx, n_gram)
            hits = np.nonzero((windows == pat).all(axis=1))[0]
            # Drop the trailing occurrence itself (lag 0).
            hits = hits[hits + n_gram < n]
            if hits.size:
                # Copy-from-lag: the most recent earlier occurrence ends L
                # tokens back; predict d_i = seq[n+i-L] with the read allowed
                # to run into the drafts themselves. On an L-periodic tail
                # (the regime this drafter exists for) that extends the
                # match's continuation cyclically to the full k instead of
                # stopping at the L (< k) tokens left before the stream end.
                lag = n - n_gram - int(hits[-1])
                seq = [int(t) for t in ctx]
                for i in range(k):
                    seq.append(seq[n + i - lag])
                return seq[n:]
        return []


class ModelDrafter(Drafter):
    """A small model drafting greedily from its own paged KV cache.

    ``lm``/``params`` must share the target's tokenizer/vocab (the classic
    draft-model requirement); ``lm`` must be a token-only full-attention
    family (the same eligibility as continuous serving). The drafter keeps
    one cache slot per engine slot in a private pool sized for the worst
    case (``admission="reserve"`` with full-capacity reservations), so
    draft-side growth can never fail mid-flight.

    Cache sync is lazy and dogfoods the pool's speculative rollback: at
    each ``draft_batch``, the longest common prefix of the tokens this
    drafter has absorbed and the row's live committed stream is kept,
    ``PagedKVPool.rollback`` disowns the divergent tail (drafts the target
    rejected), and the unabsorbed suffix is caught up in ``chunk``-token
    ragged rows — through the drafter's own two-width jitted step, which
    also decodes the K greedy drafts (the last catch-up chunk's final
    logits already yield d_1). Passing the *target's* ``lm``/``params``
    turns this into self-speculation: every greedy draft matches the
    target's greedy choice bitwise, a useful acceptance-machinery check.
    """

    def __init__(
        self,
        lm,
        params,
        *,
        n_slots: int,
        max_len: int,
        page_size: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
    ):
        cfg = lm.cfg
        if cfg.window is not None:
            raise ValueError("ModelDrafter needs full attention (window=None)")
        page = min(page_size or cfg.page_size or cfg.kv_block, max_len)
        self.lm = build_model(cfg.with_(kv_layout="paged", page_size=page))
        self.params = params
        self.n_slots = n_slots
        self.pool = PagedKVPool(
            cfg.with_(kv_layout="paged", page_size=page),
            cfg.n_layers,
            n_slots,
            max_len,
            prefix_sharing=False,
            admission="reserve",
        )
        self.chunk = max(1, min(prefill_chunk or 4 * page, max_len))
        self.pad = cfg.eos_id
        # slot -> tokens whose KV the draft cache holds (len == pool len)
        self._absorbed: dict[int, list[int]] = {}
        self._step = None
        self.steps = 0  # drafter device steps (bench accounting)

    # -- lifecycle -----------------------------------------------------------

    def reset(self) -> None:
        for slot in list(self._absorbed):
            self.release(slot)

    def release(self, slot: int) -> None:
        if slot in self._absorbed:
            self.pool.release(slot)
            del self._absorbed[slot]

    # -- the drafter's own ragged step (private jit cache, two widths) -------

    def _step_fn(self):
        if self._step is None:
            lm = self.lm
            n_layers = lm.cfg.n_layers

            def step(params, tokens, pages, bt, lens, qlens):
                caches = assemble_cache_view(pages, bt, lens, n_layers, qlens)
                logits, caches = lm.decode_step(params, tokens, caches)
                last = jnp.maximum(qlens - 1, 0)
                logits = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1
                )[:, 0]
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return toks, {name: caches[name] for name in pages}

            self._step = jax.jit(step)
        return self._step

    # -- drafting ------------------------------------------------------------

    def draft_batch(
        self, items: Sequence[tuple[int, np.ndarray, int]]
    ) -> dict[int, list[int]]:
        pool = self.pool
        step_fn = self._step_fn()
        pending: dict[int, list[int]] = {}
        need: dict[int, int] = {}
        out: dict[int, list[int]] = {}
        for slot, ctx, k in items:
            ctx = [int(t) for t in np.asarray(ctx, np.int32)]
            # Drafting d_1..d_k absorbs ctx + d_1..d_{k-1}: clamp k to the
            # drafter's own cache capacity.
            k = min(int(k), pool.capacity - len(ctx) + 1)
            if k < 1:
                continue
            absorbed = self._absorbed.get(slot)
            if absorbed is None:
                # Worst-case reservation (sharing off -> nothing adopted,
                # len stays 0): draft-side growth can never fail mid-round.
                if pool.admit(slot, np.asarray(ctx, np.int32), pool.capacity) is None:
                    continue  # draft pool full: skip speculation for the row
                absorbed = self._absorbed[slot] = []
            lcp = 0
            while (
                lcp < len(absorbed) and lcp < len(ctx)
                and absorbed[lcp] == ctx[lcp]
            ):
                lcp += 1
            if len(absorbed) > lcp:
                # Target rejected some of our drafts (or the stream was
                # restored differently): disown the divergent tail.
                pool.rollback(slot, len(absorbed) - lcp)
                del absorbed[lcp:]
            pending[slot] = ctx[lcp:]
            need[slot] = k
            out[slot] = []
        # Unified catch-up + draft rounds: rows still absorbing context feed
        # a chunk; rows with d_i in hand feed it back (q_len=1) for d_{i+1}.
        # The round width is 1 or ``chunk`` — the same two-width discipline
        # as the engine, so this private jit cache is bounded too.
        while True:
            feeds: dict[int, list[int]] = {}
            for slot in out:
                if pending[slot]:
                    feeds[slot] = pending[slot][: self.chunk]
                elif out[slot] and len(out[slot]) < need[slot]:
                    feeds[slot] = [out[slot][-1]]
            if not feeds:
                break
            width = 1 if all(len(f) == 1 for f in feeds.values()) else self.chunk
            tokens = np.full((self.n_slots, width), self.pad, np.int32)
            qlens = np.zeros((self.n_slots,), np.int32)
            for slot, seg in feeds.items():
                pool.ensure_writable(slot, len(seg))
                tokens[slot, : len(seg)] = seg
                qlens[slot] = len(seg)
            # Snapshots of the host tables (see the engine's dispatch): the
            # step may still read its arguments after ``toks`` is ready,
            # and ``advance`` below mutates ``pool.lens`` in place.
            toks, pages = step_fn(
                self.params,
                jnp.asarray(tokens),
                pool.pages,
                pool.block_tables.copy(),
                pool.lens.copy(),
                qlens,
            )
            pool.update_pages(pages)
            toks = np.asarray(toks)
            self.steps += 1
            for slot, seg in feeds.items():
                pool.advance(slot, len(seg))
                self._absorbed[slot].extend(seg)
                del pending[slot][: len(seg)]
                if not pending[slot]:
                    out[slot].append(int(toks[slot]))
        return {slot: d[: need[slot]] for slot, d in out.items()}


def make_drafter(
    kind: str,
    *,
    lm=None,
    params=None,
    n_slots: int = 8,
    max_len: int = 1024,
    ngram_max: int = 4,
    page_size: Optional[int] = None,
    prefill_chunk: Optional[int] = None,
) -> Optional[Drafter]:
    """Launcher-facing factory: ``none`` -> None, ``ngram`` ->
    :class:`NgramDrafter`, ``model`` -> :class:`ModelDrafter` (requires
    ``lm``/``params``)."""
    if kind in (None, "none"):
        return None
    if kind == "ngram":
        return NgramDrafter(ngram_max=ngram_max)
    if kind == "model":
        if lm is None or params is None:
            raise ValueError("drafter kind 'model' needs lm and params")
        return ModelDrafter(
            lm,
            params,
            n_slots=n_slots,
            max_len=max_len,
            page_size=page_size,
            prefill_chunk=prefill_chunk,
        )
    raise ValueError(f"unknown drafter kind {kind!r}")
