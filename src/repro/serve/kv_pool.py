"""Shared paged KV pool for continuous-batching serving, with refcounted
copy-on-write prefix sharing.

One physical page pool per layer (stacked on a leading L axis, matching the
scanned cache pytrees the models produce) is shared by every running
sequence; each decode slot owns a *block table* row mapping its logical
pages to physical pool pages. Page size equals the schedule's ``kv_block``
(see ``transformer.page_geometry``), so a block-table entry is exactly one
KV tile of the paper's traversal schedule and the decode kernels walk the
table in ``KVSchedule`` order (DESIGN.md §8).

Page 0 is a reserved dummy: free slots — and the invalid rows of a ragged
mixed step — point their writes at it, so the fixed-shape whole-batch step
can write masked-out tokens somewhere harmless.

**Prefix sharing.** Every physical page carries a refcount. Full prompt
pages are registered in a content-hash registry (a rolling hash over the
chain of page token contents, with exact token comparison on lookup, so
hash collisions are harmless): when a new prompt's leading pages match a
registered chain, ``admit`` *adopts* those pages — refcount bump, zero
prefill compute, zero copies — instead of recomputing and re-storing them.
A partially-matching tail page is adopted too (its extra positions are
masked by the row's ``len``); the first write into it triggers
copy-on-write in :meth:`PagedKVPool.ensure_writable` — fork to a fresh
page, decrement the shared page's refcount. ``release`` decrements
refcounts and frees+unregisters pages that hit zero, so sharing survives
the donor's retirement for as long as any adopter still holds the pages.

Allocation is lazy (a sequence materializes owned pages as its writes cross
page boundaries). Two admission disciplines (DESIGN.md §12):

* ``admission="reserve"`` (default) — worst-case reservation: a request is
  admitted only if the pool can cover its *non-shared* worst case — prompt
  + full ``max_new_tokens``, minus the adopted pages that can never be
  written — on top of every running sequence's outstanding reservation, so
  lazy growth and CoW forks never fail mid-flight.
* ``admission="optimistic"`` — only the *prompt's* pages are reserved;
  decode growth competes for the remaining pool, so the pool can be
  oversubscribed and mid-flight allocation can fail with a typed
  :class:`PoolExhausted` — the serve engine's pool-pressure preemption
  (victim selection + chunked re-prefill restore) is the recovery path.

Failures are typed: :class:`PoolExhausted` (allocation), ``AdmissionError``
(admission misuse); both keep their legacy base (``RuntimeError`` /
``ValueError``) for one release so existing ``except`` clauses still catch
them. ``release`` is idempotent — double-retiring a slot during preemption
cleanup is a no-op, never a refcount corruption. int8 pools
(``kv_cache_dtype='int8'``) carry the per-vector scales from
``repro.dist.compression`` as parallel page arrays and halve the pool's HBM
footprint.
"""

from __future__ import annotations

import functools
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as T

__all__ = [
    "PagePool",
    "PagedKVPool",
    "assemble_cache_view",
    "PoolError",
    "PoolExhausted",
    "AdmissionError",
]


class PoolError(RuntimeError):
    """Base of the serve pool's typed failures (``RuntimeError`` kept as a
    base for one release so legacy ``except RuntimeError`` still catches)."""


class PoolExhausted(PoolError):
    """Page allocation could not be satisfied from the free list.

    Under ``admission="reserve"`` this can only happen through fault
    injection; under ``admission="optimistic"`` it is the steady-state
    pressure signal the engine answers with preemption.
    """


class AdmissionError(PoolError, ValueError):
    """Admission-path misuse (occupied slot, unusable pool geometry).

    Inherits both legacy bases — these paths used to raise bare
    ``RuntimeError`` or ``ValueError`` depending on the call site.
    """


def assemble_cache_view(
    pages: dict, block_table, lens, n_layers: int, q_lens=None, order_group=None
) -> dict:
    """Splice block tables + lengths into a page pytree for ``decode_step``.

    Block tables and lengths are tiled across the layer axis because the
    scanned decode carries one copy per layer (a few KB — uniformity with
    the contiguous cache pytree is worth more than the bytes). ``q_lens``
    (B,) adds the ragged mixed step's per-row valid chunk counts
    (``transformer.attn_decode`` reads it as ``cache["q_len"]``);
    ``order_group`` a traced effective reversal-group scalar
    (``core.schedule.resolve_order_group``) that overrides the config's
    static traversal order for this step (``cache["order_group"]`` — the
    online order adaptation's rebind channel). Traceable: the engine calls
    this inside its fused jitted mixed step.
    """
    view = dict(pages)
    bt = jnp.asarray(block_table)
    ln = jnp.asarray(lens)
    view["block_table"] = jnp.broadcast_to(bt, (n_layers,) + bt.shape)
    view["len"] = jnp.broadcast_to(ln, (n_layers,) + ln.shape)
    if q_lens is not None:
        ql = jnp.asarray(q_lens)
        view["q_len"] = jnp.broadcast_to(ql, (n_layers,) + ql.shape)
    if order_group is not None:
        og = jnp.asarray(order_group, jnp.int32)
        view["order_group"] = jnp.broadcast_to(og, (n_layers,) + og.shape)
    return view


class PagePool:
    """Host-side free-list allocator over physical page ids.

    Page 0 is never handed out (reserved dummy). ``reserved`` tracks pages
    promised to admitted-but-not-yet-written sequences; ``available`` is
    what a new admission may claim. ``faults`` is the no-op fault-injection
    hook (``serve.faults.FaultPlan``): when attached, an ``alloc`` that the
    plan schedules to fail raises :class:`PoolExhausted` exactly as a real
    exhaustion would, so the engine's preemption path is testable on a pool
    that is not actually full.
    """

    def __init__(self, n_pages: int, *, faults=None):
        if n_pages < 2:
            raise AdmissionError(f"pool needs >= 2 pages (1 dummy), got {n_pages}")
        self.n_pages = n_pages
        self._free: list[int] = list(range(n_pages - 1, 0, -1))  # pop() -> low ids
        self.reserved = 0
        self.faults = faults

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def available(self) -> int:
        return self.free_count - self.reserved

    def alloc(self, n: int) -> list[int]:
        if self.faults is not None and self.faults.take("pool.alloc"):
            raise PoolExhausted(
                f"injected pool exhaustion: want {n}, free {self.free_count}"
            )
        if n > self.free_count:
            raise PoolExhausted(
                f"page pool exhausted: want {n}, free {self.free_count}"
            )
        return [self._free.pop() for _ in range(n)]

    def free(self, ids) -> None:
        self._free.extend(int(i) for i in ids)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(dst: jax.Array, src_id: jax.Array, dst_id: jax.Array) -> jax.Array:
    """dst (L, P, ...): physical page ``src_id`` copied onto ``dst_id``.

    The pool buffer is donated — callers always rebind ``pages[name]`` to
    the result — so a CoW fork updates in place (O(page) traffic) instead
    of cloning the whole pool per leaf (backends without donation fall back
    to the copy with a one-time warning)."""
    return dst.at[:, dst_id].set(dst[:, src_id])


def _hash_step(h: int, page_tokens: np.ndarray) -> int:
    """One link of the rolling prompt-page content hash. Collisions are
    harmless — every registry hit is verified by exact token comparison."""
    return zlib.crc32(np.ascontiguousarray(page_tokens, np.int32).tobytes(), h)


class PagedKVPool:
    """Device page pool + host block tables / lengths / refcounts / registry.

    The device side is a dict of stacked leaves shaped like the per-layer
    paged caches from ``transformer.init_cache`` with a leading layer axis,
    which is exactly what ``stack_decode`` carries — ``caches_view()`` splices
    the host block tables and lengths in, and ``update_pages()`` takes the
    written pages back after a mixed step. K/V values are *produced* by the
    engine's ragged mixed step writing at per-row offsets
    (``transformer._paged_write``); the pool itself never copies prefill
    caches — admission only adopts (shared) or reserves (owned) pages.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        n_layers: int,
        n_slots: int,
        max_len: int,
        *,
        dtype=None,
        prefix_sharing: bool = True,
        registry=None,
        admission: str = "reserve",
        n_pages: Optional[int] = None,
        faults=None,
    ):
        if cfg.window is not None:
            raise ValueError("paged KV pools require full attention (window=None)")
        if admission not in ("reserve", "optimistic"):
            raise AdmissionError(f"unknown admission discipline {admission!r}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.prefix_sharing = prefix_sharing
        self.admission = admission
        self.page, self.blocks_per_seq = T.page_geometry(cfg, max_len)
        self.capacity = self.blocks_per_seq * self.page
        # ``n_pages`` (allocatable pages, dummy excluded) defaults to the
        # full worst case — every slot at capacity. A smaller override is
        # the oversubscription knob: less HBM than the slots could demand,
        # with the engine's preemption absorbing the pressure. It must still
        # fit one capacity row, or some admissions could never succeed.
        if n_pages is None:
            n_pages = n_slots * self.blocks_per_seq
        if n_pages < self.blocks_per_seq:
            raise AdmissionError(
                f"pool of {n_pages} pages cannot fit one {self.blocks_per_seq}"
                f"-page capacity row"
            )
        self.alloc = PagePool(n_pages + 1, faults=faults)  # +1 dummy page 0
        self.faults = faults

        # The model names and shapes the page arrays (``T.init_pages``):
        # (L, n_pages, heads, page, width) each — K and V for llama
        # attention, one latent for latent attention — so a page is one
        # contiguous block, and its per-head (page, width) tiles are what
        # the paged kernel DMAs.
        self.n_layers = n_layers
        self.pages: dict[str, jax.Array] = T.init_pages(
            cfg, n_layers, self.alloc.n_pages, self.page, dtype=dtype
        )

        self.block_tables = np.zeros((n_slots, self.blocks_per_seq), np.int32)
        self.lens = np.zeros((n_slots,), np.int32)
        # Per-slot written high-water mark: the furthest position this slot
        # itself has made writable (``ensure_writable``). ``rollback`` moves
        # ``lens`` down but not ``_written`` — the gap is exactly the region
        # holding disowned (rejected-draft) KV, which the registry-coverage
        # invariant in ``check_invariants`` polices.
        self._written = np.zeros((n_slots,), np.int32)
        self._ref = np.zeros((self.alloc.n_pages,), np.int32)
        self._slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
        self._slot_reserved: list[int] = [0] * n_slots
        # Prefix registry: parent-chain-hash -> (physical page, its tokens).
        # Weak entries — a page is unregistered the moment it is freed or its
        # sole owner is about to overwrite it, so a registry hit (verified by
        # token equality) always points at live, correct KV.
        self._chain_next: dict[int, tuple[int, np.ndarray]] = {}
        self._page_parent: dict[int, int] = {}
        # Counters for benches/tests: pages / prompt tokens adopted instead
        # of recomputed, and CoW forks performed. With a ``repro.obs``
        # registry attached the same counts are published as ``pool.*``
        # counter series (and ``emit_gauges`` adds occupancy/refcount
        # gauges); the plain ints stay authoritative for registry-less use.
        self.shared_hits = 0
        self.shared_tokens = 0
        self.cow_forks = 0
        self._registry = registry
        if registry is not None:
            self._m_adopted = registry.counter("pool.pages_adopted")
            self._m_adopted_tokens = registry.counter("pool.tokens_adopted")
            self._m_cow = registry.counter("pool.cow_forks")
            # Pre-create the gauges so every pool series exists from step 0.
            self.emit_gauges()

    # ---- admission / lifecycle ----------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page)

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        """Worst-case admissibility ignoring prefix sharing (sharing only
        ever *reduces* the requirement; ``admit`` checks the exact one)."""
        worst = self.pages_for(min(prompt_len + max_new, self.capacity))
        return self.alloc.available >= worst

    def match_prefix(self, prompt: np.ndarray) -> tuple[int, list[int]]:
        """Longest registered prefix of ``prompt``: (tokens covered, pages).

        Walks the rolling-hash chain over full prompt pages, verifying token
        contents at every link; a final *partial* page match (the registered
        page's leading tokens equal the prompt's remaining tokens) is adopted
        too — its first write CoW-forks. Coverage is capped at
        ``len(prompt) - 1``: the last prompt token must always run through
        the model to produce the first sampled logit.
        """
        prompt = np.asarray(prompt, np.int32)
        if not self.prefix_sharing or len(prompt) <= 1:
            return 0, []
        page = self.page
        limit = min(len(prompt) - 1, self.capacity)
        h, covered, pids = 0, 0, []
        while covered < limit:
            ent = self._chain_next.get(h)
            if ent is None:
                break
            pid, ptoks = ent
            seg = prompt[covered : covered + page]
            if (
                len(seg) == page
                and covered + page <= limit
                and np.array_equal(ptoks, seg)
            ):
                pids.append(pid)
                covered += page
                h = _hash_step(h, ptoks)
                continue
            rem = prompt[covered:limit]
            if rem.size and np.array_equal(ptoks[: rem.size], rem):
                pids.append(pid)
                covered = limit
            break
        return covered, pids

    def admit(self, slot: int, prompt: np.ndarray, max_new: int) -> Optional[int]:
        """Admit a request into ``slot``: adopt the shared prefix, reserve
        the owned pages this discipline guarantees. Returns the number of
        prompt tokens whose KV was adopted (0 if none), or None when the
        pool lacks pages.

        ``admission="reserve"`` reserves the worst case (prompt + full
        ``max_new``); ``"optimistic"`` reserves only the prompt's pages —
        decode growth then competes for the leftover pool and can raise
        :class:`PoolExhausted` mid-flight, which the engine answers with
        preemption. No K/V is copied and nothing is prefilled here — the
        engine's ragged mixed step computes the non-shared tokens chunk by
        chunk, writing through the block table into lazily materialized
        owned pages.
        """
        if self._slot_pages[slot] or self._slot_reserved[slot] or self.lens[slot]:
            # A freshly admitted slot with no adopted prefix holds no pages
            # and has len 0 — its reservation is what marks it occupied.
            raise AdmissionError(f"slot {slot} is occupied")
        if self.faults is not None and self.faults.take("pool.admit"):
            return None  # injected admission pressure
        prompt = np.asarray(prompt, np.int32)
        prompt_len = min(len(prompt), self.capacity)
        covered, pids = self.match_prefix(prompt)
        # Adopted pages strictly below the write boundary are never touched
        # again; a partially covered tail page will be CoW-forked (one page
        # from the reservation) on its first write.
        n_safe = covered // self.page
        guaranteed = (
            prompt_len + max_new if self.admission == "reserve" else prompt_len
        )
        worst = self.pages_for(min(guaranteed, self.capacity))
        need = max(worst - n_safe, 0)
        if self.alloc.available < need:
            return None
        for pid in pids:
            self._ref[pid] += 1
        self.shared_hits += len(pids)
        self.shared_tokens += covered
        if self._registry is not None and pids:
            self._m_adopted.inc(len(pids))
            self._m_adopted_tokens.inc(covered)
        self._slot_pages[slot] = list(pids)
        self._slot_reserved[slot] = need
        self.alloc.reserved += need
        self.block_tables[slot] = 0
        self.block_tables[slot, : len(pids)] = pids
        self.lens[slot] = covered
        self._written[slot] = 0  # adopted prefix KV was written by the donor
        return covered

    def _take_page(self, slot: int) -> int:
        if self._slot_reserved[slot] > 0:
            (pid,) = self.alloc.alloc(1)
            self.alloc.reserved -= 1
            self._slot_reserved[slot] -= 1
        else:
            # Beyond the reservation: legal only under optimistic admission,
            # and only from the unreserved remainder — a take here must not
            # eat a page promised to another (reserve-guaranteed) slot.
            if self.admission == "reserve":
                raise AssertionError("allocation beyond reservation")
            if self.alloc.available < 1:
                raise PoolExhausted(
                    f"optimistic growth for slot {slot}: free "
                    f"{self.alloc.free_count}, reserved {self.alloc.reserved}"
                )
            (pid,) = self.alloc.alloc(1)
        self._ref[pid] = 1
        return pid

    def _unregister(self, pid: int) -> None:
        parent = self._page_parent.pop(pid, None)
        if parent is not None and self._chain_next.get(parent, (None,))[0] == pid:
            del self._chain_next[parent]

    def ensure_writable(self, slot: int, n: int = 1) -> None:
        """Make positions ``[len, len+n)`` of ``slot`` writable: materialize
        missing pages, copy-on-write-fork shared ones, unregister a sole-
        owned registered page about to diverge. Covered by the admission
        reservation, so allocation cannot fail within the worst-case budget.
        """
        start = int(self.lens[slot])
        end = min(start + n, self.capacity)
        if end <= start:
            return
        held = self._slot_pages[slot]
        for pg in range(start // self.page, (end - 1) // self.page + 1):
            if pg < len(held):
                pid = held[pg]
                if self._ref[pid] > 1:
                    nid = self._take_page(slot)
                    self.cow_forks += 1
                    if self._registry is not None:
                        self._m_cow.inc()
                    for name in self.pages:
                        self.pages[name] = _copy_page(
                            self.pages[name],
                            jnp.int32(pid),
                            jnp.int32(nid),
                        )
                    self._ref[pid] -= 1
                    held[pg] = nid
                    self.block_tables[slot, pg] = nid
                elif pid in self._page_parent:
                    # Sole owner writing a registered page: its content is
                    # about to diverge from the registered prompt chain.
                    self._unregister(pid)
            else:
                pid = self._take_page(slot)
                held.append(pid)
                self.block_tables[slot, pg] = pid
        self._written[slot] = max(int(self._written[slot]), end)

    def advance(self, slot: int, n: int = 1) -> None:
        """Record ``n`` written tokens (host mirror of the device len+q_len)."""
        self.lens[slot] = min(self.lens[slot] + n, self.capacity)

    def rollback(self, slot: int, n: int) -> int:
        """Disown the last ``n`` tokens of ``slot`` — the speculative-decoding
        reject path: a host-side ``lens`` decrement plus release of tail
        pages that no longer back any live token. Returns pages freed.

        Contract: only tokens the slot itself wrote (rejected draft tokens)
        may be rolled back. Those positions went through
        :meth:`ensure_writable`, whose CoW fork guarantees the backing pages
        are exclusively owned — dropping a page another slot still holds
        (refcount > 1) means the caller rolled back adopted prefix content
        and raises :class:`PoolError` before any state is mutated.

        Under ``admission="reserve"`` each freed page is returned to the
        slot's reservation, preserving the cannot-fail growth guarantee for
        a later re-draft over the same positions. The registry refresh then
        unregisters any still-held registered page whose coverage extends
        past the new live len into positions this slot wrote
        (``_written``) — without it, a later ``admit`` could adopt a page
        whose tail holds rejected draft KV.
        """
        n = min(int(n), int(self.lens[slot]))
        if n <= 0:
            return 0
        new_len = int(self.lens[slot]) - n
        keep = self.pages_for(new_len)
        held = self._slot_pages[slot]
        dropped = held[keep:]
        for pid in dropped:
            if self._ref[pid] > 1:
                raise PoolError(
                    f"rollback({slot}, {n}) would drop shared page {pid} "
                    f"(ref {int(self._ref[pid])}): only self-written tokens "
                    "may be rolled back"
                )
        for pid in dropped:
            self._ref[pid] -= 1
            self._unregister(pid)
            self.alloc.free([pid])
        del held[keep:]
        self.block_tables[slot, keep:] = 0
        self.lens[slot] = new_len
        if dropped and self.admission == "reserve":
            self._slot_reserved[slot] += len(dropped)
            self.alloc.reserved += len(dropped)
        for pg, pid in enumerate(held):
            end = (pg + 1) * self.page
            if (
                pid in self._page_parent
                and new_len < end <= int(self._written[slot])
            ):
                self._unregister(pid)
        return len(dropped)

    def register_prompt(self, slot: int, prompt: np.ndarray) -> None:
        """Publish ``slot``'s full prompt pages in the prefix registry.

        Call once, when the slot's prompt is fully in cache and before its
        first decode write. Only *frozen* pages are registered — the full
        pages strictly inside the prompt, which no decode write can ever
        touch. A chain link already registered with the same content is
        *refreshed* to point at this slot's copy when it owns a distinct
        one (so the chain survives the original donor's retirement as long
        as ANY same-prefix sequence is still running); a divergent chain
        occupying the hash link ends registration (first-wins).
        """
        if not self.prefix_sharing:
            return
        prompt = np.asarray(prompt, np.int32)
        page = self.page
        held = self._slot_pages[slot]
        h = 0
        for j in range(min(len(prompt) // page, len(held))):
            ptoks = prompt[j * page : (j + 1) * page]
            pid = held[j]
            ent = self._chain_next.get(h)
            if ent is not None and not np.array_equal(ent[1], ptoks):
                break
            if ent is None or ent[0] != pid:
                if ent is not None:
                    self._page_parent.pop(ent[0], None)
                self._chain_next[h] = (pid, ptoks.copy())
                self._page_parent[pid] = h
            h = _hash_step(h, ptoks)

    def shared_donor(self, slot: int) -> bool:
        """Whether ``slot`` holds any page other slots also hold (refcount >
        1). Releasing such a slot frees fewer pages than it holds — the
        preemption victim policy prefers non-donors for exactly that reason.
        """
        return any(self._ref[pid] > 1 for pid in self._slot_pages[slot])

    def occupancy(self) -> float:
        """Held fraction of the allocatable pool (admission watermarks)."""
        n_alloc = self.alloc.n_pages - 1
        return (n_alloc - self.alloc.free_count) / max(n_alloc, 1)

    def release(self, slot: int) -> None:
        """Release every page ``slot`` holds. Idempotent: releasing an
        already-free slot is a no-op, so a double-retire during preemption
        cleanup (engine retires, then a failure path retires again) cannot
        drive refcounts negative or free pages twice."""
        if (
            not self._slot_pages[slot]
            and not self._slot_reserved[slot]
            and not self.lens[slot]
        ):
            self.block_tables[slot] = 0
            return
        for pid in self._slot_pages[slot]:
            self._ref[pid] -= 1
            if self._ref[pid] == 0:
                self._unregister(pid)
                self.alloc.free([pid])
        self.alloc.reserved -= self._slot_reserved[slot]
        self._slot_pages[slot] = []
        self._slot_reserved[slot] = 0
        self.block_tables[slot] = 0
        self.lens[slot] = 0
        self._written[slot] = 0

    # ---- invariants (property tests / debugging) -----------------------------

    def _offslot_pages(self, slot: int) -> int:
        """Logical pages of ``slot`` living outside its device block table.

        Always 0 here; ``serve.tiering.TieredPagePool`` overrides it with
        the slot's host-resident page count so ``check_invariants`` can
        keep asserting full logical coverage across tiers."""
        return 0

    def check_invariants(self) -> None:
        """Assert the pool's conservation + consistency invariants:
        free + distinct-held == allocatable pages, per-page refcounts equal
        the number of slots holding them, reservations are consistent, and
        every block-table entry points at a held page (or the dummy)."""
        held: dict[int, int] = {}
        for pages in self._slot_pages:
            assert len(set(pages)) == len(pages), "slot holds a page twice"
            for pid in pages:
                held[pid] = held.get(pid, 0) + 1
        assert self.alloc.free_count + len(held) == self.alloc.n_pages - 1, (
            f"page leak: free={self.alloc.free_count} held={len(held)} "
            f"of {self.alloc.n_pages - 1}"
        )
        for pid, cnt in held.items():
            assert pid != 0, "dummy page held by a slot"
            assert self._ref[pid] == cnt, (pid, self._ref[pid], cnt)
        assert (self._ref >= 0).all(), "negative refcount"
        for pid in range(1, self.alloc.n_pages):
            if pid not in held:
                assert self._ref[pid] == 0, f"freed page {pid} has refs"
                assert pid not in self._page_parent, f"freed page {pid} registered"
        assert self.alloc.reserved == sum(self._slot_reserved) >= 0
        for slot in range(self.n_slots):
            n_logical = -(-int(self.lens[slot]) // self.page)
            assert (
                len(self._slot_pages[slot]) + self._offslot_pages(slot) >= n_logical
            ), (slot, len(self._slot_pages[slot]), self._offslot_pages(slot), n_logical)
            for pg, pid in enumerate(self._slot_pages[slot]):
                assert self.block_tables[slot, pg] == pid
                # Rollback hygiene: no registry entry may extend past the
                # slot's live len into positions the slot itself wrote —
                # such a page would advertise rejected-draft KV for adoption.
                end = (pg + 1) * self.page
                assert not (
                    pid in self._page_parent
                    and int(self.lens[slot]) < end <= int(self._written[slot])
                ), (
                    f"registered page {pid} of slot {slot} extends past live "
                    f"len {int(self.lens[slot])} into written tail "
                    f"(page end {end}, written {int(self._written[slot])})"
                )
            for pg in range(len(self._slot_pages[slot]), self.blocks_per_seq):
                assert self.block_tables[slot, pg] == 0
        for parent, (pid, _) in self._chain_next.items():
            assert self._page_parent.get(pid) == parent

    # ---- telemetry -----------------------------------------------------------

    def emit_gauges(self, registry=None) -> None:
        """Publish the pool's occupancy/sharing state as ``pool.*`` gauges:
        free/reserved page counts, occupancy fraction of the allocatable
        pool, pages currently shared (refcount > 1) and registered in the
        prefix registry. Cheap (a handful of numpy reductions); the engine
        calls it once per mixed step."""
        registry = registry if registry is not None else self._registry
        if registry is None:
            return
        n_alloc = self.alloc.n_pages - 1  # dummy page 0 excluded
        held = n_alloc - self.alloc.free_count
        registry.gauge("pool.pages_free").set(self.alloc.free_count)
        registry.gauge("pool.pages_reserved").set(self.alloc.reserved)
        registry.gauge("pool.occupancy_frac").set(held / max(n_alloc, 1))
        registry.gauge("pool.shared_pages").set(int((self._ref > 1).sum()))
        registry.gauge("pool.registered_pages").set(len(self._page_parent))

    # ---- step plumbing -------------------------------------------------------

    def caches_view(self, q_lens=None) -> dict:
        """Cache pytree for ``decode_step``: pages + current tables/lens
        (host-authoritative), via :func:`assemble_cache_view`."""
        return assemble_cache_view(
            self.pages, self.block_tables, self.lens, self.n_layers, q_lens
        )

    def update_pages(self, caches: dict) -> None:
        """Take back the page leaves written by a mixed step."""
        for name in self.pages:
            self.pages[name] = caches[name]
