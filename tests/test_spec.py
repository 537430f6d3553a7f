"""Speculative decoding on the unified ragged step (DESIGN.md §14).

* Kernel-level verification parity: a q_len=K+1 chunk through the ragged
  paged attention (reference and interpret-mode kernel) must equal K+1
  sequential q_len=1 decode steps over the same pools — across traversal
  orders, SWA windows, GQA grouping, and shuffled block tables.
* Engine stream parity: speculative-on (n-gram and draft-model drafters)
  must produce bitwise the non-speculative engine's streams — greedy AND
  sampled (the per-accepted-token PRNG stream accounting), across
  traversal orders and int8 KV pages — with exactly two compiled step
  widths and draft/accept/rollback counter conservation.
* ``PagedKVPool.rollback``: reservation restore under "reserve",
  page free under "optimistic", the shared-page (refcount > 1) guard, and
  the prefix-registry refresh (a rolled-back tail must never be adoptable)
  — plus the extended ``check_invariants`` that pins the registry rule.
* Scheduler: ``plan_step(draft_lens)`` clamping (chunk width, token
  budget, decode-row guarantee).
* Hypothesis random walks: accept/rollback ops against pool invariants on
  the plain pool, and interleaved with tiering spill/resume suspensions.
* Drafters: n-gram copy-from-lag extrapolation; draft-model
  self-speculation accepting ~everything on greedy streams.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.attention import mha_reference, paged_decode_attention
from repro.core.schedule import Order
from repro.kernels.flash_decode import paged_flash_decode_fwd
from repro.models import build_model
from repro.serve import (
    ContinuousScheduler,
    FaultPlan,
    ModelDrafter,
    NgramDrafter,
    PagedKVPool,
    PoolError,
    Request,
    ServeEngine,
    TieredPagePool,
    make_drafter,
)

SETTINGS = settings(max_examples=15, deadline=None)


@pytest.fixture(scope="module")
def deepseek_lm():
    cfg = get_config("deepseek-7b").reduced()
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(0))


# ---- kernel-level chunk-vs-sequential verification parity -------------------


def _verify_problem(seed=0, b=3, hq=8, hkv=2, d=16, page=8, nb=4, c=6):
    """Ragged verification step: GQA heads, shuffled block tables, one
    decode row (q_len 1) next to two verification chunks (q_len 6 and 4)."""
    rng = np.random.default_rng(seed)
    n_pages = b * nb + 1
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    perm = rng.permutation(np.arange(1, n_pages))[: b * nb].reshape(b, nb)
    bt = jnp.asarray(perm, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)).astype(np.float32))
    lens = jnp.asarray([9, 21, nb * page], jnp.int32)  # valid KV incl chunk
    qls = jnp.asarray([1, c, 4], jnp.int32)
    return q, kp, vp, bt, lens, qls


@pytest.mark.parametrize("order", list(Order))
@pytest.mark.parametrize("window", [None, 11])
def test_verification_chunk_matches_sequential_decode(order, window):
    """One q_len=K+1 chunk == K+1 sequential q_len=1 steps, per position.

    The speculative path's whole correctness story: verifying K draft
    tokens as one ragged chunk must score exactly what K+1 one-token decode
    steps over the same pools would score. Checked for the reference ragged
    attention AND the interpret-mode flash kernel, across traversal orders
    (the online-softmax page order must not leak into the result), SWA
    windows, GQA grouping, and shuffled block tables."""
    q, kp, vp, bt, lens, qls = _verify_problem()
    kw = dict(order=order, window=window)
    if order is Order.BLOCK_SNAKE:
        kw["snake_group"] = 2
    chunk_ref = np.asarray(
        paged_decode_attention(q, kp, vp, lens, bt, q_lens=qls, **kw)
    )
    chunk_kern = np.asarray(
        paged_flash_decode_fwd(
            q, kp, vp, lens, bt, q_lens=qls, interpret=True, **kw
        )
    )
    for i in range(q.shape[0]):
        for t in range(int(qls[i])):
            # Sequential stand-in: the chunk's position t as a plain
            # one-token decode at the KV length it would see.
            pos_len = jnp.asarray(
                [int(lens[i]) - int(qls[i]) + t + 1], jnp.int32
            )
            seq = np.asarray(
                paged_decode_attention(
                    q[i : i + 1, t : t + 1],
                    kp,
                    vp,
                    pos_len,
                    bt[i : i + 1],
                    q_lens=jnp.asarray([1], jnp.int32),
                    **kw,
                )
            )[0, 0]
            np.testing.assert_allclose(chunk_ref[i, t], seq, atol=2e-5)
            np.testing.assert_allclose(chunk_kern[i, t], seq, atol=2e-5)


# ---- engine stream parity ----------------------------------------------------


def _spec_requests(max_new=32, temperature=0.0, seeds=(5, 8)):
    """The decode-heavy repetitive stream the bench asserts on: short
    cyclic prompts whose greedy continuations prompt-lookup can draft."""
    reqs = []
    for i, s in enumerate(seeds):
        rng = np.random.default_rng(s)
        toks = np.tile(rng.integers(5, 20, size=4), 6).astype(np.int32)
        reqs.append(
            Request(
                tokens=toks,
                max_new_tokens=max_new,
                temperature=temperature,
                rid=i,
                seed=i,
            )
        )
    return reqs


def _engine(lm, params, drafter=None, draft_len=4, **kw):
    return ServeEngine(
        lm,
        params,
        batch_size=2,
        max_len=128,
        scheduler="continuous",
        page_size=8,
        prefill_chunk=8,
        drafter=drafter,
        draft_len=draft_len,
        **kw,
    )


def _assert_conservation(eng):
    v = eng.obs.value
    drafted = v("serve.spec.draft_tokens")
    assert drafted > 0, "speculative engine never drafted"
    assert v("serve.spec.accepted_tokens") + v("serve.spec.rollback_tokens") == drafted


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("kind", ["ngram", "model"])
def test_engine_stream_parity(deepseek_lm, kind, temperature):
    """Speculative-on == speculative-off, bitwise, greedy and sampled.

    Sampled parity is the PRNG satellite: the engine folds (seed, sample
    index) once per *accepted* position, so the K+1 keys of a verification
    chunk are exactly the keys K+1 sequential steps would have drawn."""
    lm, params = deepseek_lm
    base = _engine(lm, params).generate(_spec_requests(temperature=temperature))
    drafter = make_drafter(
        kind,
        lm=lm,
        params=params,
        n_slots=2,
        max_len=128,
        page_size=8,
        prefill_chunk=8,
    )
    eng = _engine(lm, params, drafter=drafter)
    got = eng.generate(_spec_requests(temperature=temperature))
    for a, b in zip(base, got):
        assert np.array_equal(a.tokens, b.tokens), f"rid {a.rid} diverged"
    _assert_conservation(eng)
    assert eng.compiled_step_count() == 2


@pytest.mark.parametrize("order", ["sawtooth", "block_snake"])
def test_engine_parity_across_orders(order):
    """The verification chunk rides the same traced ``order_group`` operand
    as plain decode — parity must hold under every traversal order."""
    cfg = get_config("deepseek-7b").reduced().with_(
        attn_order=order, snake_group=2 if order == "block_snake" else None
    )
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    base = _engine(lm, params).generate(_spec_requests(max_new=24))
    eng = _engine(lm, params, drafter=NgramDrafter(ngram_max=4))
    got = eng.generate(_spec_requests(max_new=24))
    for a, b in zip(base, got):
        assert np.array_equal(a.tokens, b.tokens), f"rid {a.rid} diverged"
    _assert_conservation(eng)
    assert eng.compiled_step_count() == 2


def test_engine_parity_int8_pages():
    """Quantized KV pages quantize identically whether written by a
    verification chunk or sequential decode steps — streams stay bitwise."""
    cfg = get_config("deepseek-7b").reduced().with_(kv_cache_dtype="int8")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    base = _engine(lm, params).generate(_spec_requests(max_new=24))
    eng = _engine(lm, params, drafter=NgramDrafter(ngram_max=4))
    got = eng.generate(_spec_requests(max_new=24))
    for a, b in zip(base, got):
        assert np.array_equal(a.tokens, b.tokens), f"rid {a.rid} diverged"
    _assert_conservation(eng)


@pytest.mark.parametrize("draft_len", [2, 7])
def test_speculative_keeps_two_compiled_steps(deepseek_lm, draft_len):
    """The regression pin: verification chunks pad into the *prefill*
    width, so a speculative run — prefill chunks, full K+1 verification
    chunks, clamped tails, plain decode steps — compiles exactly the same
    two step variants as a non-speculative one. A third compiled width
    here means the padding contract broke."""
    lm, params = deepseek_lm
    eng = _engine(
        lm, params, drafter=NgramDrafter(ngram_max=4), draft_len=draft_len
    )
    eng.generate(_spec_requests())
    assert eng.compiled_step_count() == 2
    # A second stream through the same engine reuses both traces.
    eng.generate(_spec_requests(max_new=16))
    assert eng.compiled_step_count() == 2


def test_chaos_step_fault_mid_verification(deepseek_lm):
    """A transient device-step failure mid-verification retries once via
    the resilience path; drafts of the failed step are re-verified and the
    stream is bitwise unchanged, with conservation intact."""
    lm, params = deepseek_lm
    base = _engine(lm, params).generate(_spec_requests())
    plan = FaultPlan(seed=0).fail_device_step(6)
    eng = _engine(lm, params, drafter=NgramDrafter(ngram_max=4), faults=plan)
    got = eng.generate(_spec_requests())
    assert eng.obs.value("serve.step_retries") == 1
    for a, b in zip(base, got):
        assert np.array_equal(a.tokens, b.tokens), f"rid {a.rid} diverged"
    _assert_conservation(eng)
    eng.last_pool.check_invariants()


# ---- pool rollback -----------------------------------------------------------


def _pool(admission="reserve", n_slots=3, max_len=32, **kw):
    cfg = get_config("deepseek-7b").reduced().with_(
        kv_layout="paged", page_size=4
    )
    return PagedKVPool(
        cfg, 1, n_slots, max_len=max_len, admission=admission, **kw
    )


def _grow(pool, slot, n):
    pool.ensure_writable(slot, n)
    pool.advance(slot, n)


def test_rollback_reserve_restores_reservation():
    pool = _pool("reserve", n_slots=1, max_len=16)  # capacity 16 = 4 pages
    prompt = np.arange(2, 8, dtype=np.int32)  # 6 tokens
    assert pool.admit(0, prompt, 10) == 0
    _grow(pool, 0, 6)
    _grow(pool, 0, 9)  # 15 tokens, 4 pages held
    held = len(pool._slot_pages[0])
    freed = pool.rollback(0, 7)  # back to 8 tokens = 2 pages
    assert int(pool.lens[0]) == 8
    assert freed == held - 2 and len(pool._slot_pages[0]) == 2
    # Freed pages return to the reservation: regrowth over the same
    # positions cannot fail (the "reserve" guarantee survives rollback).
    _grow(pool, 0, 8)
    assert int(pool.lens[0]) == 16
    pool.check_invariants()


def test_rollback_optimistic_frees_pages():
    pool = _pool("optimistic", n_slots=2, max_len=16, n_pages=6)
    assert pool.admit(0, np.arange(2, 6, dtype=np.int32), 12) == 0
    _grow(pool, 0, 4)
    _grow(pool, 0, 11)  # 15 tokens = 4 pages
    free_before = pool.alloc.free_count
    freed = pool.rollback(0, 10)  # 5 tokens = 2 pages
    assert freed == 2
    assert pool.alloc.free_count == free_before + 2
    assert int(pool.lens[0]) == 5
    pool.check_invariants()


def test_rollback_refuses_shared_pages():
    """Dropping a refcount>1 page means the caller is rolling back adopted
    prefix content, not self-written drafts — PoolError, state untouched."""
    pool = _pool("reserve", n_slots=2, max_len=16)
    # 9 tokens: two full (registrable) pages + a one-token tail, so the
    # adopter's own writes land on its private tail page and the adopted
    # pages stay shared (no CoW fork in the way of the guard).
    prompt = np.append(
        np.tile(np.arange(2, 6, dtype=np.int32), 2), np.int32(6)
    )
    assert pool.admit(0, prompt, 4) is not None
    _grow(pool, 0, 9)
    pool.register_prompt(0, prompt)
    adopted = pool.admit(1, prompt, 4)  # adopts the two registered pages
    assert adopted and adopted >= 8
    _grow(pool, 1, len(prompt) - int(pool.lens[1]) + 2)  # past the prompt
    assert any(pool._ref[pid] > 1 for pid in pool._slot_pages[1])
    lens_before = int(pool.lens[1])  # 11: pages [shared, shared, own]
    assert pool.rollback(1, 2) == 0  # own-page rollback is fine
    with pytest.raises(PoolError, match="shared page"):
        pool.rollback(1, int(pool.lens[1]) - 4)  # would drop a shared page
    assert int(pool.lens[1]) == lens_before - 2
    pool.check_invariants()


def test_rollback_refreshes_prefix_registry():
    """A rollback cutting into a registered page unregisters it — a later
    same-content admit must NOT adopt a page whose tail held rejected
    draft KV — and ``check_invariants`` pins exactly that rule."""
    pool = _pool("reserve", n_slots=2, max_len=32)
    prompt = np.tile(np.arange(2, 6, dtype=np.int32), 3)  # 12 tokens, 3 pages
    assert pool.admit(0, prompt, 12) == 0
    _grow(pool, 0, 12)
    pool.register_prompt(0, prompt)
    registered = [
        pid for pid in pool._slot_pages[0] if pid in pool._page_parent
    ]
    assert len(registered) == 3
    # Roll back into the last prompt page (len 12 -> 10): its registered
    # content now extends past the live len over self-written positions.
    assert pool.rollback(0, 2) == 0  # no page freed (10 tokens still 3 pages)
    assert registered[-1] not in pool._page_parent, (
        "rolled-back tail still adoptable"
    )
    assert registered[0] in pool._page_parent  # untouched pages stay shared
    pool.check_invariants()
    # A same-prefix admit now adopts only the still-valid pages: 8 tokens
    # (two pages), never the rolled-back third.
    assert pool.admit(1, prompt, 4) == 8
    shared = sum(1 for pid in pool._slot_pages[1] if pool._ref[pid] > 1)
    assert shared == 2
    pool.check_invariants()


def test_check_invariants_catches_registry_overhang():
    """The new invariant actually fires: force the illegal state (a
    registered page covering rolled-back self-written positions) by
    bypassing ``rollback``'s refresh and expect the assertion."""
    pool = _pool("reserve", n_slots=1, max_len=16)
    prompt = np.tile(np.arange(2, 6, dtype=np.int32), 2)  # 8 tokens, 2 pages
    assert pool.admit(0, prompt, 8) == 0
    _grow(pool, 0, 8)
    pool.register_prompt(0, prompt)
    pool.check_invariants()
    pool.lens[0] = 6  # raw len cut, no registry refresh: now invalid
    with pytest.raises(AssertionError):
        pool.check_invariants()


def test_rollback_noop_and_clamp():
    pool = _pool("reserve", n_slots=1, max_len=16)
    assert pool.admit(0, np.arange(2, 6, dtype=np.int32), 8) == 0
    _grow(pool, 0, 4)
    assert pool.rollback(0, 0) == 0
    assert pool.rollback(0, -3) == 0
    pool.rollback(0, 99)  # clamped to the live len
    assert int(pool.lens[0]) == 0
    pool.check_invariants()


# ---- scheduler draft planning ------------------------------------------------


def test_plan_step_clamps_draft_lens():
    """Draft upgrades are best-effort: clamped to the wide width
    (prefill_chunk - 1) and to the budget spare after every decode row's
    guaranteed token, so speculation can never evict a decode row."""
    sched = ContinuousScheduler(4, token_budget=8, prefill_chunk=4)
    prompt = np.arange(2, 6, dtype=np.int32)
    for i in range(3):
        # prompt_pos == len(prompt): past prefill, i.e. a decode row.
        sched.place(
            i,
            Request(tokens=prompt, rid=i),
            eos_id=1,
            new_limit=8,
            prompt=prompt,
            prompt_pos=len(prompt),
        )
    plan = sched.plan_step({0: 10, 1: 2, 2: 1})
    by_slot = {it.slot: it for it in plan}
    # Slot 0 wants 10: chunk clamps to 3, budget spare (8 - 3 rows = 5)
    # allows it. Slot 1 gets the remaining spare (2), slot 2 gets 0.
    assert by_slot[0].q_len == 4 and by_slot[0].n_draft == 3
    assert by_slot[1].q_len == 3 and by_slot[1].n_draft == 2
    assert by_slot[2].q_len == 1 and by_slot[2].n_draft == 0
    assert sum(it.q_len for it in plan) <= 8
    # No draft_lens -> plain decode plan, bit-identical to the old planner.
    plain = sched.plan_step()
    assert all(it.q_len == 1 and it.n_draft == 0 for it in plain)


# ---- hypothesis random walks -------------------------------------------------


@SETTINGS
@given(seed=st.integers(0, 10_000))
def test_accept_rollback_walk_preserves_invariants(seed):
    """Random admit/grow/rollback/release walk with a host-side ledger:
    rollback only ever covers self-written tokens (the engine's contract),
    lens track the ledger exactly, and ``check_invariants`` holds after
    every op — including the registry rule the walk exercises by
    registering every finished prompt."""
    rng = np.random.default_rng(seed)
    admission = "reserve" if seed % 2 else "optimistic"
    pool = _pool(admission, n_slots=3, max_len=32)
    live: dict[int, dict] = {}  # slot -> {len, written (self), total}
    for _ in range(60):
        op = rng.integers(0, 5)
        free = [s for s in range(3) if s not in live]
        if op == 0 and free:
            slot = int(rng.choice(free))
            plen = int(rng.integers(1, 12))
            prompt = rng.integers(2, 5, size=plen).astype(np.int32)
            max_new = int(rng.integers(1, 12))
            if pool.admit(slot, prompt, max_new) is not None:
                live[slot] = {
                    "len": int(pool.lens[slot]),
                    "written": 0,
                    "total": min(plen + max_new, pool.capacity),
                    "prompt": prompt,
                }
        elif op == 1 and live:  # grow (prefill or accepted decode tokens)
            slot = int(rng.choice(list(live)))
            room = live[slot]["total"] - live[slot]["len"]
            n = min(int(rng.integers(1, 6)), room)
            if n <= 0:
                continue
            _grow(pool, slot, n)
            live[slot]["len"] += n
            live[slot]["written"] += n
            if live[slot]["len"] == len(live[slot]["prompt"]):
                pool.register_prompt(slot, live[slot]["prompt"])
        elif op == 2 and live:  # reject drafts: roll back self-written only
            slot = int(rng.choice(list(live)))
            n = min(int(rng.integers(1, 6)), live[slot]["written"])
            if n <= 0:
                continue
            pool.rollback(slot, n)
            live[slot]["len"] -= n
            live[slot]["written"] -= n
        elif op == 3 and live:
            slot = int(rng.choice(list(live)))
            del live[slot]
            pool.release(slot)
        pool.check_invariants()
        for slot, led in live.items():
            assert int(pool.lens[slot]) == led["len"]
    for slot in list(live):
        pool.release(slot)
    pool.check_invariants()
    assert pool.alloc.free_count == pool.alloc.n_pages - 1


@SETTINGS
@given(seed=st.integers(0, 10_000))
def test_rollback_interleaves_with_tiering_walk(seed):
    """Accept/rollback interleaved with spill/resume: a slot can be
    spilled mid-stream, resumed, and immediately rolled back (rejected
    drafts re-verified after restore) — both tiers' invariants and the
    ledger must survive every interleaving."""
    rng = np.random.default_rng(seed)
    cfg = get_config("deepseek-7b").reduced().with_(
        kv_layout="paged", page_size=4
    )
    pool = TieredPagePool(
        cfg, 1, 3, max_len=32, admission="optimistic",
        n_pages=13, host_pages=12,
    )
    live: dict[int, dict] = {}
    for _ in range(70):
        op = rng.integers(0, 6)
        free = [s for s in range(3) if s not in live]
        active = [s for s in live if not pool.is_suspended(s)]
        if op == 0 and free:
            slot = int(rng.choice(free))
            plen = int(rng.integers(1, 12))
            prompt = rng.integers(2, 5, size=plen).astype(np.int32)
            if pool.admit(slot, prompt, int(rng.integers(1, 10))) is not None:
                live[slot] = {"len": int(pool.lens[slot]), "written": 0}
        elif op == 1 and active:  # grow, spill a victim on pressure
            slot = int(rng.choice(active))
            n = int(rng.integers(1, 5))
            if live[slot]["len"] + n > pool.capacity:
                continue
            try:
                pool.ensure_writable(slot, n)
            except Exception:  # PoolExhausted: spill or drop a victim
                victim = next((v for v in active if pool.can_spill(v)), None)
                if victim is not None:
                    assert pool.spill_slot(victim)
                else:
                    victim = active[0]
                    del live[victim]
                    pool.release(victim)
                pool.check_invariants()
                continue
            pool.advance(slot, n)
            live[slot]["len"] += n
            live[slot]["written"] += n
        elif op == 2 and active:  # reject drafts on a live device slot
            slot = int(rng.choice(active))
            n = min(int(rng.integers(1, 6)), live[slot]["written"])
            if n <= 0:
                continue
            pool.rollback(slot, n)
            live[slot]["len"] -= n
            live[slot]["written"] -= n
        elif op == 3 and active:
            slot = int(rng.choice(active))
            if pool.can_spill(slot):
                assert pool.spill_slot(slot)
        elif op == 4:  # resume progress (then rollback becomes legal again)
            sus = pool.suspended_slots()
            if not sus:
                continue
            slot = int(rng.choice(sus))
            if not pool._suspended[slot].started:
                pool.start_resume(slot)
            pool.issue_fetches(slot, int(rng.integers(1, 4)))
            if pool.resume_ready(slot):
                pool.complete_resume(slot)  # may refuse under pressure
        elif op == 5 and live:
            slot = int(rng.choice(list(live)))
            del live[slot]
            pool.release(slot)
        pool.check_invariants()
        for slot, led in live.items():
            assert int(pool.lens[slot]) == led["len"]
    for slot in list(live):
        pool.release(slot)
    pool.check_invariants()


# ---- drafters ----------------------------------------------------------------


def test_ngram_drafter_copy_from_lag():
    """Prompt-lookup with copy-from-lag: after the n-gram match the
    drafter extends by copying at the matched lag *including its own
    drafts*, so a period-4 stream yields K tokens of continuation, not
    just the suffix that happened to exist in the context."""
    d = NgramDrafter(ngram_max=4)
    ctx = np.tile(np.arange(1, 5, dtype=np.int32), 3)  # 1 2 3 4 x3
    assert d.draft(0, ctx, 6) == [1, 2, 3, 4, 1, 2]
    # Lag extrapolation reaches past one period indefinitely.
    assert d.draft(0, ctx, 10) == [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    # No repeated n-gram in the context -> no draft, never a guess.
    assert d.draft(0, np.arange(1, 9, dtype=np.int32), 4) == []
    # Too-short context drafts nothing.
    assert d.draft(0, np.asarray([7], dtype=np.int32), 4) == []


def test_model_drafter_self_speculation_accepts_everything(deepseek_lm):
    """Self-speculation (draft model == target): on a greedy stream with
    no EOS truncation every drafted token matches the target's argmax, so
    acceptance is ~100% and the engine's step count collapses.

    The bound rests on two things. The drafter runs the target's weights
    through the same paged step, so draft and verification logits differ
    only by float reassociation between step widths (about 2e-6 here,
    against a smallest top-2 logit gap of about 0.02). And no stream may
    end early: drafts past a sampled EOS are discarded and booked as
    rolled back, so EOS is made unreachable (an id outside the vocab)."""
    lm, params = deepseek_lm

    def requests():
        reqs = _spec_requests()
        for r in reqs:
            r.eos_id = lm.cfg.vocab
        return reqs

    base = _engine(lm, params)
    res0 = base.generate(requests())
    steps0 = base.last_stats.mixed_steps
    eng = _engine(
        lm,
        params,
        drafter=ModelDrafter(
            lm, params, n_slots=2, max_len=128, page_size=8, prefill_chunk=8
        ),
        draft_len=7,
    )
    res1 = eng.generate(requests())
    for a, b in zip(res0, res1):
        assert np.array_equal(a.tokens, b.tokens)
    st_ = eng.last_stats
    assert st_.draft_tokens > 0
    assert st_.acceptance_rate >= 0.99
    assert st_.mixed_steps < steps0 / 2
