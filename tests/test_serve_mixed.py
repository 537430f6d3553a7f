"""Unified ragged serve step: chunked prefill, token budgets, prefix sharing.

Covers the serve stack's mixed-step refactor end to end:

* ragged paged attention parity (XLA + Pallas interpret) against a per-row
  oracle — GQA, SWA windows, shuffled block tables, all traversal orders;
* O(1) compilation across arbitrary prompt-length streams (the regression
  that killed the per-bucket prefill jit cache);
* chunked-prefill greedy parity with the static path at prompt lengths that
  straddle chunk and page boundaries;
* prefix sharing: bitwise-identical greedy streams with the pool's page
  dedup on vs off, and copy-on-write isolation between sibling rows;
* pool invariants under a random admit/progress/release/CoW walk
  (hypothesis property test);
* token-budget step planning (decode priority, chunk preemption,
  round-robin fairness);
* the step-level shared-page visit order and its cache_sim/traffic models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.attention import mha_reference, paged_decode_attention
from repro.core.cache_sim import simulate_shared_prefix_decode
from repro.core.schedule import Order, step_page_visits
from repro.kernels.flash_decode import paged_flash_decode_fwd
from repro.kernels.traffic import shared_prefix_llc_model
from repro.models import build_model
from repro.serve import ContinuousScheduler, PagedKVPool, Request, ServeEngine

SETTINGS = settings(max_examples=20, deadline=None)


@pytest.fixture(scope="module")
def deepseek_lm():
    cfg = get_config("deepseek-7b").reduced()
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(0))


# ---- ragged paged attention parity ------------------------------------------


def _ragged_problem(seed=0, b=3, hq=8, hkv=2, d=16, page=8, nb=4, c=5):
    rng = np.random.default_rng(seed)
    n_pages = b * nb + 1
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, page, d)).astype(np.float32))
    perm = rng.permutation(np.arange(1, n_pages))[: b * nb].reshape(b, nb)
    bt = jnp.asarray(perm, jnp.int32)  # shuffled block tables
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)).astype(np.float32))
    lens = jnp.asarray([7, 20, nb * page], jnp.int32)   # total valid incl chunk
    qls = jnp.asarray([1, c, 3], jnp.int32)             # ragged chunk rows
    kc = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * page, hkv, d)
    vc = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * page, hkv, d)
    return q, kp, vp, bt, lens, qls, kc, vc


def _ragged_reference(q, kc, vc, lens, qls, window):
    """Per-(row, query) oracle: query t of row b at absolute position
    lens[b]-qls[b]+t attends over kv[:pos+1] (SWA-trimmed)."""
    b, c, hq, d = q.shape
    out = np.zeros((b, c, hq, d), np.float32)
    for i in range(b):
        L, Q = int(lens[i]), int(qls[i])
        for t in range(Q):
            pos = L - Q + t
            lo = 0 if window is None else max(0, pos - window + 1)
            out[i, t] = np.asarray(
                mha_reference(
                    q[i : i + 1, t : t + 1],
                    kc[i : i + 1, lo : pos + 1],
                    vc[i : i + 1, lo : pos + 1],
                )
            )[0, 0]
    return out


@pytest.mark.parametrize("order", list(Order))
@pytest.mark.parametrize("window", [None, 11])
def test_ragged_paged_attention_matches_oracle(order, window):
    q, kp, vp, bt, lens, qls, kc, vc = _ragged_problem()
    ref = _ragged_reference(q, kc, vc, lens, qls, window)
    got = np.asarray(
        paged_decode_attention(
            q, kp, vp, lens, bt, q_lens=qls, order=order, window=window
        )
    )
    kern = np.asarray(
        paged_flash_decode_fwd(
            q, kp, vp, lens, bt, q_lens=qls, order=order, window=window,
            interpret=True,
        )
    )
    c = q.shape[1]
    for i in range(q.shape[0]):
        n = int(qls[i])
        np.testing.assert_allclose(got[i, :n], ref[i, :n], atol=2e-5)
        np.testing.assert_allclose(kern[i, :n], ref[i, :n], atol=2e-5)
        if n < c:  # invalid chunk rows are exact zeros, not NaN
            assert np.abs(got[i, n:]).max() == 0.0
            assert np.abs(kern[i, n:]).max() == 0.0


def test_ragged_zero_qlen_rows_are_zero():
    q, kp, vp, bt, lens, _, _, _ = _ragged_problem()
    qls = jnp.asarray([0, 2, 0], jnp.int32)
    out = np.asarray(paged_decode_attention(q, kp, vp, lens, bt, q_lens=qls))
    assert not np.isnan(out).any()
    assert np.abs(out[0]).max() == 0.0 and np.abs(out[2]).max() == 0.0


# ---- O(1) compilation -------------------------------------------------------


def test_mixed_step_compiles_o1_over_prompt_lengths(deepseek_lm):
    """20 distinct prompt lengths through the continuous path must compile
    at most two mixed-step variants (decode width 1 + chunk width) — the
    per-bucket prefill jit cache regression test."""
    lm, params = deepseek_lm
    eng = ServeEngine(
        lm, params, batch_size=4, max_len=128, scheduler="continuous",
        page_size=16, prefill_chunk=24,
    )
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            tokens=rng.integers(2, lm.cfg.vocab, size=5 + 3 * i).astype(np.int32),
            max_new_tokens=3,
            rid=i,
        )
        for i in range(20)
    ]
    res = eng.generate(reqs)
    assert all(r.steps >= 1 for r in res)
    assert eng.compiled_step_count() <= 2
    assert not hasattr(eng, "_prefill_buckets")  # the unbounded cache is gone


# ---- chunked prefill parity -------------------------------------------------


@pytest.mark.parametrize("plen", [3, 16, 17, 33, 47])
def test_chunked_prefill_matches_static_greedy(deepseek_lm, plen):
    """Greedy parity with the static path at prompt lengths straddling page
    (16) and chunk (16) boundaries — the chunk decomposition must be
    invisible in the token stream."""
    lm, params = deepseek_lm
    prompt = (np.arange(plen, dtype=np.int32) * 7 + 2) % lm.cfg.vocab
    a = ServeEngine(lm, params, batch_size=1, max_len=96).generate(
        [Request(tokens=prompt, max_new_tokens=6)]
    )[0]
    b = ServeEngine(
        lm, params, batch_size=1, max_len=96, scheduler="continuous",
        page_size=16, prefill_chunk=16,
    ).generate([Request(tokens=prompt, max_new_tokens=6)])[0]
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_mixed_stream_rows_isolated(deepseek_lm):
    """Every request in a ragged mixed stream (staggered arrivals, ragged
    lengths, mid-stream admissions) decodes exactly what it decodes solo —
    chunked prefill neighbors and shared pages must be invisible."""
    lm, params = deepseek_lm
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(2, lm.cfg.vocab, size=int(n)).astype(np.int32)
        for n in [5, 21, 34, 9, 21, 13]
    ]
    prompts[4] = prompts[1].copy()  # exact duplicate: shares + CoW-forks
    eng = ServeEngine(
        lm, params, batch_size=2, max_len=96, scheduler="continuous",
        page_size=8, prefill_chunk=16,
    )
    reqs = [
        Request(tokens=p, max_new_tokens=5, rid=i, arrival=i // 2)
        for i, p in enumerate(prompts)
    ]
    batch = eng.generate(reqs)
    for i, p in enumerate(prompts):
        solo = eng.generate([Request(tokens=p, max_new_tokens=5)])[0]
        np.testing.assert_array_equal(batch[i].tokens, solo.tokens)


# ---- prefix sharing correctness --------------------------------------------


def _shared_stream(vocab, rng, n=6):
    sysp = rng.integers(2, vocab, size=40).astype(np.int32)
    reqs = []
    for i in range(n):
        if i == 3:
            tokens = sysp[:30].copy()  # mid-page prefix-only: CoW fork path
        else:
            tail = rng.integers(2, vocab, size=3 + i).astype(np.int32)
            tokens = np.concatenate([sysp, tail])
        reqs.append(Request(tokens=tokens, max_new_tokens=5, rid=i, arrival=i))
    return reqs


def test_prefix_sharing_greedy_bitwise_identical(deepseek_lm):
    """The pool's hash-dedup + CoW must be invisible: greedy token streams
    with sharing on and off are identical, request by request."""
    lm, params = deepseek_lm
    rng = np.random.default_rng(7)
    reqs = _shared_stream(lm.cfg.vocab, rng)
    mk = lambda sharing: ServeEngine(
        lm, params, batch_size=2, max_len=96, scheduler="continuous",
        page_size=8, prefill_chunk=16, prefix_sharing=sharing,
    )
    eng_on = mk(True)
    on = eng_on.generate([Request(**vars(r)) for r in reqs])
    off = mk(False).generate([Request(**vars(r)) for r in reqs])
    assert eng_on.last_stats["pages_adopted"] > 0  # sharing actually engaged
    assert eng_on.last_stats["cow_forks"] > 0      # ...including a CoW fork
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_cow_isolation_between_siblings(deepseek_lm):
    """A row decoding past a shared prefix must never perturb a sibling
    that shares its pages: serve A alone, then A next to prefix-sharing
    siblings — A's stream is bit-identical."""
    lm, params = deepseek_lm
    rng = np.random.default_rng(11)
    sysp = rng.integers(2, lm.cfg.vocab, size=32).astype(np.int32)
    a_req = lambda: Request(tokens=sysp.copy(), max_new_tokens=6, rid=0)
    # Siblings arrive after A's two prefill chunks have completed (and its
    # prompt pages are registered), so they adopt A's pages.
    sib = lambda i: Request(
        tokens=sysp.copy(), max_new_tokens=6, rid=i, arrival=2, temperature=1.5
    )
    eng = ServeEngine(
        lm, params, batch_size=3, max_len=96, scheduler="continuous",
        page_size=8, prefill_chunk=16,
    )
    solo = eng.generate([a_req()])[0]
    paired = eng.generate([a_req(), sib(1), sib(2)])
    assert eng.last_stats["cow_forks"] > 0  # siblings forked shared pages
    np.testing.assert_array_equal(solo.tokens, paired[0].tokens)


# ---- pool invariants under a random walk (property test) --------------------


@SETTINGS
@given(seed=st.integers(0, 2**16))
def test_pool_invariants_random_walk(seed):
    """Random admissions / chunked progress / CoW forks / releases: no page
    leaks (free + distinct-held == allocatable), refcounts consistent and
    non-negative, block tables always pointing at held-or-dummy pages,
    reservations conserved. Prompts from a tiny alphabet so prefix matches
    (and forks) happen constantly."""
    cfg = get_config("deepseek-7b").reduced().with_(kv_layout="paged", page_size=4)
    rng = np.random.default_rng(seed)
    n_slots = 3
    pool = PagedKVPool(cfg, cfg.n_layers, n_slots, max_len=32)
    state: dict[int, dict] = {}  # slot -> {prompt, left, registered}

    for _ in range(60):
        op = rng.integers(0, 3)
        if op == 0:  # admit into a free slot
            free = [s for s in range(n_slots) if s not in state]
            if not free:
                continue
            slot = int(rng.choice(free))
            plen = int(rng.integers(1, 28))
            prompt = rng.integers(2, 5, size=plen).astype(np.int32)
            max_new = int(rng.integers(1, 8))
            shared = pool.admit(slot, prompt, max_new)
            if shared is not None:
                total = min(plen + max_new, pool.capacity)
                state[slot] = {
                    "prompt": prompt,
                    "left": total - 1 - shared,  # tokens still to write
                    "registered": False,
                }
        elif op == 1:  # progress: write a chunk (prefill or decode)
            busy = [s for s in state if state[s]["left"] > 0]
            if not busy:
                continue
            slot = int(rng.choice(busy))
            n = int(rng.integers(1, min(state[slot]["left"], 6) + 1))
            pool.ensure_writable(slot, n)
            pool.advance(slot, n)
            state[slot]["left"] -= n
            st_ = state[slot]
            if not st_["registered"] and pool.lens[slot] >= len(st_["prompt"]):
                pool.register_prompt(slot, st_["prompt"])
                st_["registered"] = True
        else:  # release
            if not state:
                continue
            slot = int(rng.choice(list(state)))
            pool.release(slot)
            del state[slot]
        pool.check_invariants()

    for slot in list(state):
        pool.release(slot)
    pool.check_invariants()
    assert pool.alloc.free_count == pool.alloc.n_pages - 1
    assert pool.alloc.reserved == 0


# ---- token-budget step planning ---------------------------------------------


def _place(sched, slot, plen, pos=0, new_limit=4):
    sched.place(
        slot,
        object(),
        eos_id=1,
        new_limit=new_limit,
        prompt=np.arange(plen, dtype=np.int32),
        prompt_pos=pos,
    )


def test_plan_step_decode_priority_and_chunking():
    sched = ContinuousScheduler(4, token_budget=10, prefill_chunk=6)
    _place(sched, 0, plen=4, pos=4)    # decoding
    _place(sched, 1, plen=20)          # long prefill
    _place(sched, 2, plen=3)           # short prefill
    plan = {it.slot: it for it in sched.plan_step()}
    assert plan[0].q_len == 1 and not plan[0].is_prefill
    # 9 tokens left after decode: chunk 6 to one prefill, 3 to the other.
    assert plan[1].is_prefill and plan[2].is_prefill
    assert plan[1].q_len + plan[2].q_len == 9
    assert not plan[1].finishes_prompt
    assert plan[2].q_len == 3 and plan[2].finishes_prompt


def test_plan_step_preempts_long_prefill():
    """A long prompt advances in chunks while decode rows keep emitting —
    it never monopolizes a step beyond the leftover budget."""
    sched = ContinuousScheduler(4, token_budget=8, prefill_chunk=8)
    for s in range(3):
        _place(sched, s, plen=2, pos=2)  # three decode rows
    _place(sched, 3, plen=40)            # one long prefill
    plan = {it.slot: it for it in sched.plan_step()}
    assert [plan[s].q_len for s in range(3)] == [1, 1, 1]
    assert plan[3].q_len == 5  # leftover budget, not the full chunk
    st = sched.slots[3]
    steps = 0
    while st.prefilling and steps < 20:
        for it in sched.plan_step():
            if it.slot == 3:
                st.prompt_pos += it.q_len
        steps += 1
    assert st.prompt_pos == 40 and steps == 8  # 5 + 7*5 tokens


def test_plan_step_round_robin_fairness():
    sched = ContinuousScheduler(3, token_budget=4, prefill_chunk=4)
    for s in range(3):
        _place(sched, s, plen=30)
    first = {it.slot for it in sched.plan_step()}
    sched.slots[next(iter(first))].prompt_pos += 4
    second = {it.slot for it in sched.plan_step()}
    assert first != second  # cursor rotated to a different slot


def test_plan_step_decode_saturated_budget():
    sched = ContinuousScheduler(4, token_budget=2, prefill_chunk=8)
    for s in range(2):
        _place(sched, s, plen=2, pos=2)
    _place(sched, 2, plen=10)
    plan = sched.plan_step()
    assert len(plan) == 2 and all(not it.is_prefill for it in plan)


# ---- step-level shared-page visit order + models ----------------------------


@SETTINGS
@given(
    order=st.sampled_from(list(Order)),
    n_rows=st.integers(1, 5),
    seed=st.integers(0, 1000),
)
def test_step_page_visits_is_rowwise_permutation(order, n_rows, seed):
    rng = np.random.default_rng(seed)
    row_pages = [
        list(rng.integers(0, 50, size=int(rng.integers(1, 7))))
        for _ in range(n_rows)
    ]
    parities = [int(rng.integers(0, 100)) for _ in range(n_rows)]
    visits = list(step_page_visits(order, row_pages, parities))
    for b in range(n_rows):
        mine = [p for (row, p) in visits if row == b]
        assert sorted(mine) == sorted(row_pages[b])
    # lock-step: the first n_active visits are inner step 0, row-ordered
    first = [row for row, _ in visits[:n_rows]]
    assert first == sorted(first)


def test_shared_prefix_reuse_distance_beats_private():
    for order in ("cyclic", "sawtooth"):
        sh = simulate_shared_prefix_decode(order, 6, 4, [8] * 6, 12, 16, shared=True)
        pr = simulate_shared_prefix_decode(order, 6, 4, [8] * 6, 12, 16, shared=False)
        assert sh["mean_reuse_distance"] < pr["mean_reuse_distance"]


def test_shared_prefix_llc_model_misses_drop():
    shared = shared_prefix_llc_model("sawtooth", shared=True)
    private = shared_prefix_llc_model("sawtooth", shared=False)
    assert shared.cold_misses < private.cold_misses   # dedup: fewer compulsory
    assert shared.misses < private.misses             # and fewer total bytes
