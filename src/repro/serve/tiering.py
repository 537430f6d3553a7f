"""Tiered KV memory: a host-offload page tier under the device pool
(DESIGN.md §13).

At 100k+ contexts the capacity-bound regime of the paper reappears one
level up: HBM itself becomes the tier whose footprint the wavefront
overflows. This module layers a bounded host-memory page store under
``PagedKVPool`` so the device pool becomes a *cache* over a larger host
tier. The serve engine's pressure resolution gains a middle rung —
shed → **spill** → preempt — because parking a cold slot's pages on the
host preserves its computed KV (resume is a memcpy), while preemption
throws the work away (restore is a full chunked re-prefill).

Design points:

* **Full-slot spill.** ``spill_slot`` moves *all* of a slot's device pages
  to host rows (every pool leaf — int8 payloads and their scale planes
  mirror alike), releases its device pages and reservation, and marks the
  slot *suspended*: its logical length (``lens``) is retained, its block
  table is dummied out, and the scheduler excludes it from step plans.
  Shared (refcount > 1) pages get a private host copy plus a refcount
  decrement, so prefix donors keep serving adopters.
* **Known-future prefetch.** The Traversal IR makes the access sequence of
  a resuming row *exact*, not heuristic: ``core.schedule.
  future_visit_window`` gives the next step's page visit order, and the
  engine streams host rows back in that order, ``prefetch_depth`` pages
  per step boundary, issuing the ``device_put`` transfers while the
  current mixed step is still in flight (the double-buffered overlap the
  ``tier.overlap_frac`` gauge measures).
* **Atomic re-admission.** Staged device rows live outside the pool until
  every page of the slot is host→device resident; only then does
  ``complete_resume`` allocate physical pages, splice the rows in, restore
  the block table and reservation, and hand the slot back to the planner.
  Pool invariants therefore never see a half-resident slot — they see a
  suspended slot whose logical pages are accounted by ``_offslot_pages``.
* **Reuse-distance eviction.** ``select_spill_victim`` ranks candidates by
  ``cache_sim.slot_reuse_stats`` — the slot whose page stream carries the
  largest LRU stack distances is the one an LLC-sized device tier was
  going to miss anyway — instead of plain last-touch LRU.

Prefetch accounting: every successfully staged page counts one
``tier.fetches``; it becomes a ``tier.prefetch_hits`` when the resumed
slot advances (the fetched KV was attended) or a ``tier.prefetch_wasted``
when the slot is released first — so ``hits + wasted == fetches`` once a
stream drains, and ``check_invariants`` asserts the running version
(``hits + wasted + pending == fetches``) continuously.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.kv_pool import PagedKVPool, PoolExhausted

__all__ = ["HostPageStore", "TieredPagePool", "select_spill_victim"]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_pages(dst: jax.Array, rows: jax.Array, dst_ids: jax.Array) -> jax.Array:
    """dst (L, P, ...): a staged chunk of rows (L, k, ...) scattered onto
    physical pages ``dst_ids`` (k,) in one call.

    Donated like ``kv_pool._copy_page`` — the splice updates the pool
    buffer in place instead of cloning the whole leaf per fetched page.
    One dispatch per leaf per staged chunk (not per page): the chunk is
    whatever ``issue_fetches`` staged together, so splice cost scales with
    transfer batches, not pages."""
    return dst.at[:, dst_ids].set(rows)


def select_spill_victim(candidates) -> Optional[int]:
    """Spill victim policy (DESIGN.md §13): pick from ``candidates`` —
    tuples ``(slot, priority, shared_donor, mean_reuse_distance)`` — the
    slot with the lowest priority, preferring non-donors (spilling a donor
    host-copies pages that stay device-resident anyway), then the LARGEST
    mean reuse distance (the coldest page stream — the device tier was
    missing those pages regardless), slot index as the deterministic
    tiebreak. Returns None when there is nothing to spill."""
    if not candidates:
        return None
    return min(candidates, key=lambda c: (c[1], bool(c[2]), -c[3], c[0]))[0]


class HostPageStore:
    """Bounded host-memory store of spilled page rows.

    A row is one physical page across every pool leaf — ``{leaf name ->
    (L, ...) ndarray}``, the leaf indexed at that page — so int8 pools
    mirror their payloads and float32 scale planes together. Handles are
    opaque monotonically
    increasing ints; capacity is counted in pages (rows), matching the
    device pool's accounting unit.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"host tier needs >= 1 page, got {capacity}")
        self.capacity = int(capacity)
        self._rows: dict[int, dict[str, np.ndarray]] = {}
        self._next = 0

    @property
    def used(self) -> int:
        return len(self._rows)

    @property
    def free(self) -> int:
        return self.capacity - len(self._rows)

    @property
    def nbytes(self) -> int:
        return sum(
            a.nbytes for row in self._rows.values() for a in row.values()
        )

    def put(self, row: dict) -> int:
        if self.free <= 0:
            raise PoolExhausted(
                f"host page tier full: capacity {self.capacity}"
            )
        h = self._next
        self._next += 1
        self._rows[h] = row
        return h

    def get(self, handle: int) -> dict:
        return self._rows[handle]

    def pop(self, handle: int) -> dict:
        return self._rows.pop(handle)


@dataclasses.dataclass
class _Suspended:
    """Host-side state of one spilled slot."""

    handles: list[int]            # host handle per logical page (in order)
    reserved: int                 # device reservation to restore on resume
    queue: list[int] = dataclasses.field(default_factory=list)
                                  # logical pages awaiting fetch, visit-order
    staged: set[int] = dataclasses.field(default_factory=set)
                                  # logical pages already staged on device
    chunks: list = dataclasses.field(default_factory=list)
                                  # [(logical pgs, {leaf -> (L, k, ...)
                                  # device stack})] — one device_put batch
                                  # per leaf per issue_fetches call

    @property
    def started(self) -> bool:
        return bool(self.queue or self.staged)


class TieredPagePool(PagedKVPool):
    """``PagedKVPool`` over a :class:`HostPageStore`: the device pool as a
    cache tier.

    New lifecycle verbs (all host-side; the engine drives them at step
    boundaries): :meth:`spill_slot` parks a slot on the host,
    :meth:`start_resume` fixes its fetch order, :meth:`issue_fetches`
    stages ``device_put`` transfers (overlappable with an in-flight step),
    :meth:`complete_resume` splices fully staged slots back in. ``advance``
    and ``release`` are overridden only to classify pending prefetches as
    hits/wasted; every inherited operation (admit/CoW/registry/…) is
    unchanged and fully interoperates with suspended slots.
    """

    def __init__(self, *args, host_pages: int, **kwargs):
        super().__init__(*args, **kwargs)
        self.host = HostPageStore(host_pages)
        self._suspended: dict[int, _Suspended] = {}
        self._pending: dict[int, int] = {}  # slot -> staged, unclassified fetches
        # Plain-int twins of the tier.* registry series (registry-less use).
        self.spills = 0
        self.fetches = 0
        self.prefetch_hits = 0
        self.prefetch_wasted = 0
        self.fetch_failures = 0
        self.spill_bytes = 0
        self.fetch_bytes = 0
        self._overlapped = 0
        if self._registry is not None:
            r = self._registry
            self._t_spills = r.counter("tier.spills")
            self._t_fetches = r.counter("tier.fetches")
            self._t_hits = r.counter("tier.prefetch_hits")
            self._t_wasted = r.counter("tier.prefetch_wasted")
            self._t_fetch_fail = r.counter("tier.fetch_failures")
            self._t_spill_b = r.counter("tier.spill_bytes")
            self._t_fetch_b = r.counter("tier.fetch_bytes")
            self.emit_gauges()  # tier.* gauges exist from step 0

    # ---- queries -------------------------------------------------------------

    def suspended_slots(self) -> list[int]:
        return sorted(self._suspended)

    def is_suspended(self, slot: int) -> bool:
        return slot in self._suspended

    def shielded(self, slot: int) -> bool:
        """Slot has staged-but-unclassified prefetches (just resumed, has
        not stepped yet). The engine excludes shielded slots from spill
        victim candidacy — re-spilling before one step both wastes the
        fetches and invites spill/resume ping-pong."""
        return slot in self._pending

    def fetch_backlog(self) -> int:
        """Host pages still queued for fetch across all resuming slots."""
        return sum(len(s.queue) for s in self._suspended.values())

    def resume_ready(self, slot: int) -> bool:
        sus = self._suspended.get(slot)
        return (
            sus is not None
            and not sus.queue
            and len(sus.staged) == len(sus.handles)
        )

    def resume_need(self, slot: int) -> int:
        """Device pages ``complete_resume`` will claim (pages + reservation)."""
        sus = self._suspended[slot]
        return len(sus.handles) + sus.reserved

    def can_spill(self, slot: int) -> bool:
        return (
            slot not in self._suspended
            and bool(self._slot_pages[slot])
            and self.host.free >= len(self._slot_pages[slot])
        )

    # ---- spill ---------------------------------------------------------------

    def spill_slot(self, slot: int) -> bool:
        """Move every device page of ``slot`` to the host tier and suspend
        it. Returns False (slot untouched) when the slot holds no pages,
        the host tier lacks room, or an injected ``tier.spill`` fault
        models a stalled host writer — the engine then falls through to
        preemption.

        Shared pages are host-copied privately and ref-decremented: the
        surviving holders (and the prefix registry, while any holder
        lives) keep serving; the resumed slot comes back with private
        copies, exactly as if CoW had forked them."""
        if not self.can_spill(slot):
            return False
        if self.faults is not None and self.faults.take("tier.spill"):
            return False
        pids = list(self._slot_pages[slot])
        # One gather + one D2H per leaf for the whole slot (not per page);
        # the per-page host rows are views into the transferred block.
        idx = jnp.asarray(pids, dtype=jnp.int32)
        cols = {
            name: np.asarray(jnp.take(leaf, idx, axis=1))
            for name, leaf in self.pages.items()
        }
        handles = []
        for j in range(len(pids)):
            row = {name: col[:, j] for name, col in cols.items()}
            handles.append(self.host.put(row))
            nbytes = sum(a.nbytes for a in row.values())
            self.spill_bytes += nbytes
            if self._registry is not None:
                self._t_spill_b.inc(nbytes)
        for pid in pids:
            self._ref[pid] -= 1
            if self._ref[pid] == 0:
                self._unregister(pid)
                self.alloc.free([pid])
        res = self._slot_reserved[slot]
        self.alloc.reserved -= res
        self._slot_reserved[slot] = 0
        self._slot_pages[slot] = []
        self.block_tables[slot] = 0
        # lens[slot] is retained: it is the suspended row's logical length
        # (check_invariants covers it through _offslot_pages) and the
        # resume target.
        self._suspended[slot] = _Suspended(handles=handles, reserved=res)
        self.spills += 1
        if self._registry is not None:
            self._t_spills.inc()
        return True

    # ---- fetch / resume ------------------------------------------------------

    def start_resume(self, slot: int, order=None) -> None:
        """Fix the fetch order of suspended ``slot`` and open its queue.

        ``order`` is a (possibly partial) permutation of the slot's
        logical pages — the engine passes the next step's visit window
        (``core.schedule.future_visit_window``), so pages come back in
        exactly the order the resumed row will attend them; unnamed pages
        follow in logical order. Idempotent for already staged pages."""
        sus = self._suspended[slot]
        n = len(sus.handles)
        head = [int(p) for p in (order or []) if 0 <= int(p) < n]
        seen = set(head)
        full = head + [p for p in range(n) if p not in seen]
        sus.queue = [p for p in full if p not in sus.staged]

    def issue_fetches(self, slot: int, depth: int, *, overlapped: bool = False) -> int:
        """Stage up to ``depth`` queued host pages of ``slot`` as device
        rows (async ``device_put`` — the H2D copies queue behind whatever
        step is in flight, which is the whole point of calling this while
        one is). Returns pages staged. An injected ``tier.fetch`` fault
        drops the transfer — the host copy is untouched, the page stays
        queued, and the next boundary retries, so the row resumes late but
        bitwise-intact."""
        sus = self._suspended.get(slot)
        if sus is None:
            return 0
        pgs: list[int] = []
        while sus.queue and len(pgs) < depth:
            if self.faults is not None and self.faults.take("tier.fetch"):
                self.fetch_failures += 1
                if self._registry is not None:
                    self._t_fetch_fail.inc()
                break  # faulted page stays queued; next boundary retries
            pgs.append(sus.queue.pop(0))
        if not pgs:
            return 0
        # The whole window ships as one stacked H2D transfer per leaf; the
        # accounting (fetches, pending, bytes) stays per page.
        rows = [self.host.get(sus.handles[pg]) for pg in pgs]
        stack = {}
        nbytes = 0
        for name in rows[0]:
            h = np.stack([r[name] for r in rows], axis=1)  # (L, k, ...)
            stack[name] = jax.device_put(h)
            nbytes += h.nbytes
        sus.chunks.append((pgs, stack))
        sus.staged.update(pgs)
        n = len(pgs)
        self.fetches += n
        self.fetch_bytes += nbytes
        self._pending[slot] = self._pending.get(slot, 0) + n
        if overlapped:
            self._overlapped += n
        if self._registry is not None:
            self._t_fetches.inc(n)
            self._t_fetch_b.inc(nbytes)
        return n

    def complete_resume(self, slot: int) -> bool:
        """Splice a fully staged slot back into the device tier: allocate
        its physical pages, write every staged row, restore the block
        table and reservation, drop the host copies. Atomic — returns
        False (nothing changes, retried next boundary) when the device
        pool cannot cover pages + reservation right now."""
        sus = self._suspended[slot]
        if sus.queue or len(sus.staged) < len(sus.handles):
            return False
        n = len(sus.handles)
        if self.alloc.available < n + sus.reserved:
            return False
        try:
            pids = self.alloc.alloc(n)
        except PoolExhausted:  # injected pool.alloc fault: retry later
            return False
        for pg in range(n):
            self._ref[pids[pg]] = 1
            self.block_tables[slot, pg] = pids[pg]
        # One scatter per leaf per staged chunk: each chunk's rows land on
        # the physical pages its logical pages were assigned.
        for pgs, stack in sus.chunks:
            ids = jnp.asarray([pids[pg] for pg in pgs], dtype=jnp.int32)
            for name, rows in stack.items():
                self.pages[name] = _write_pages(self.pages[name], rows, ids)
        self._slot_pages[slot] = list(pids)
        self._slot_reserved[slot] = sus.reserved
        self.alloc.reserved += sus.reserved
        for h in sus.handles:
            self.host.pop(h)
        del self._suspended[slot]
        # _pending stays: classified as hits on the slot's first advance.
        return True

    # ---- lifecycle overrides (prefetch classification) -----------------------

    def advance(self, slot: int, n: int = 1) -> None:
        super().advance(slot, n)
        if slot not in self._suspended:
            pend = self._pending.pop(slot, 0)
            if pend:
                self.prefetch_hits += pend
                if self._registry is not None:
                    self._t_hits.inc(pend)

    def release(self, slot: int) -> None:
        sus = self._suspended.pop(slot, None)
        if sus is not None:
            for h in sus.handles:
                self.host.pop(h)
        pend = self._pending.pop(slot, 0)
        if pend:
            self.prefetch_wasted += pend
            if self._registry is not None:
                self._t_wasted.inc(pend)
        super().release(slot)

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        """Admissibility against the *combined* capacity: a request whose
        worst case overflows the device tier is still admissible when the
        host tier can absorb the overflow via spills."""
        worst = self.pages_for(min(prompt_len + max_new, self.capacity))
        return self.alloc.available + self.host.free >= worst

    # ---- invariants ----------------------------------------------------------

    def _offslot_pages(self, slot: int) -> int:
        sus = self._suspended.get(slot)
        return 0 if sus is None else len(sus.handles)

    def check_invariants(self) -> None:
        super().check_invariants()
        all_handles: list[int] = []
        for slot, sus in self._suspended.items():
            assert not self._slot_pages[slot], (
                f"suspended slot {slot} still holds device pages"
            )
            assert self._slot_reserved[slot] == 0, (
                f"suspended slot {slot} still holds a reservation"
            )
            n = len(sus.handles)
            all_handles.extend(sus.handles)
            assert set(sus.staged).isdisjoint(sus.queue)
            if sus.started:
                assert sorted(sus.queue + list(sus.staged)) == list(range(n))
        assert len(all_handles) == len(set(all_handles)), "host handle aliased"
        assert self.host.used == len(all_handles), (
            f"host tier leak: stored {self.host.used}, "
            f"referenced {len(all_handles)}"
        )
        assert all(v > 0 for v in self._pending.values())
        assert (
            self.fetches
            == self.prefetch_hits
            + self.prefetch_wasted
            + sum(self._pending.values())
        ), "prefetch accounting drift"

    # ---- telemetry -----------------------------------------------------------

    def emit_gauges(self, registry=None) -> None:
        super().emit_gauges(registry)
        registry = registry if registry is not None else self._registry
        if registry is None or not hasattr(self, "host"):
            return  # parent __init__ pre-creates pool.* before the tier exists
        n_alloc = self.alloc.n_pages - 1
        registry.gauge("tier.device_pages").set(n_alloc - self.alloc.free_count)
        registry.gauge("tier.host_pages").set(self.host.used)
        registry.gauge("tier.suspended_slots").set(len(self._suspended))
        registry.gauge("tier.overlap_frac").set(
            self._overlapped / max(self.fetches, 1)
        )
