"""repro.obs: metrics registry, span tracing, sinks, and the modeled-LLC
sampler — plus the serve engine's use of all of them.

* registry semantics: get-or-create handles, label-rendered series,
  histogram bucket placement / cumulative snapshot / NaN exclusion;
* tracer: span nesting by timestamp containment, exception-safe close,
  ring-buffer cap, Chrome-trace JSON schema validity (strict JSON);
* export: schema_version-stamped JSONL roundtrip, append_jsonl stamping;
* LLC sampler: ``llc.modeled_miss_bytes{order=...}`` gauge parity with a
  direct ``fwd_llc_model`` call at the same footprint, via the public
  ``fwd_spec_for``;
* engine integration: serve-stream metrics conservation (sum of per-step
  token counters == total tokens generated), NaN TPOT for single-token
  generations, the StepStats deprecation shim, and live llc gauges for
  >= 2 traversal orders.
"""

import json
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.traffic import fwd_llc_model
from repro.models import build_model
from repro.obs import (
    LLCSampler,
    Registry,
    Tracer,
    append_jsonl,
    load_jsonl,
    metric_records,
    write_metrics_jsonl,
)
from repro.obs.export import SCHEMA_VERSION
from repro.obs.metrics import render_series
from repro.serve import Request, ServeEngine, StepStats


# ---- registry ----------------------------------------------------------------


def test_render_series_sorts_labels():
    assert render_series("x", {}) == "x"
    assert render_series("x", {"b": 2, "a": 1}) == "x{a=1,b=2}"


def test_counter_get_or_create_and_labels():
    reg = Registry()
    c1 = reg.counter("serve.step.tokens", kind="decode")
    c2 = reg.counter("serve.step.tokens", kind="prefill")
    assert c1 is reg.counter("serve.step.tokens", kind="decode")
    assert c1 is not c2
    c1.inc()
    c1.inc(3)
    assert reg.value("serve.step.tokens", kind="decode") == 4
    assert reg.value("serve.step.tokens", kind="prefill") == 0
    assert reg.value("no.such.series", default=-1) == -1
    with pytest.raises(ValueError):
        c1.inc(-1)


def test_kind_conflict_rejected():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_bucket_semantics():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 0.2, 0.5))
    for v in (0.05, 0.1, 0.2, 0.3, 9.0):  # bounds are inclusive upper edges
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]  # [<=0.1]=2 (0.05, 0.1), overflow=1
    assert h.count == 5
    assert h.sum == pytest.approx(9.65)
    snap = reg.snapshot()["histograms"]["lat"]
    assert snap["buckets"] == [[0.1, 2], [0.2, 3], [0.5, 4], ["+Inf", 5]]
    # Cumulative counts are monotone and end at count.
    cums = [c for _, c in snap["buckets"]]
    assert cums == sorted(cums) and cums[-1] == snap["count"]


def test_histogram_nan_dropped():
    reg = Registry()
    h = reg.histogram("tpot")
    h.observe(0.01)
    h.observe(math.nan)
    assert h.count == 1 and h.nan_count == 1
    assert h.sum == pytest.approx(0.01)
    assert not math.isnan(h.quantile(0.5))


def test_histogram_quantile_and_conflicting_buckets():
    reg = Registry()
    h = reg.histogram("q", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 0.5, 1.5, 4.0):
        h.observe(v)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == 5.0
    assert math.isnan(reg.histogram("empty").quantile(0.9))
    with pytest.raises(ValueError):
        reg.histogram("q", buckets=(1.0, 2.0))


def test_snapshot_is_strict_json():
    reg = Registry()
    reg.counter("c", a="1").inc()
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(1e9)  # lands in the +Inf overflow bucket
    # Strict JSON (no Infinity/NaN literals) must accept the snapshot.
    json.loads(json.dumps(reg.snapshot(), allow_nan=False))


# ---- tracer ------------------------------------------------------------------


def test_span_nesting_by_containment():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    inner, outer = tr.events()  # inner closes (appends) first
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.ts_ns <= inner.ts_ns
    assert inner.end_ns <= outer.end_ns


def test_span_closes_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("step crashed")
    (ev,) = tr.events()
    assert ev.name == "boom" and ev.dur_ns >= 0


def test_ring_buffer_caps_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant("e", i=i)
    evs = tr.events()
    assert len(evs) == 4
    assert tr.dropped == 6
    assert [e.args["i"] for e in evs] == [6, 7, 8, 9]  # most recent kept


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("serve.step", step=0):
        tr.instant("serve.compile", width=4)
    path = tmp_path / "trace.json"
    tr.write(str(path))
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        assert isinstance(ev["ts"], float)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    by_ph = {e["ph"]: e for e in events}
    assert by_ph["X"]["dur"] >= 0
    assert by_ph["i"]["s"] == "t"
    assert by_ph["i"]["args"] == {"width": 4}


def test_record_appends_a_span_with_given_times():
    tr = Tracer()
    tr.record("serve.queued", 1_000, 250, rid=3)
    (ev,) = tr.events()
    assert (ev.name, ev.ts_ns, ev.dur_ns, ev.end_ns) == ("serve.queued", 1_000, 250, 1_250)
    assert ev.args == {"rid": 3}


def test_spans_reach_the_profiler_trace(tmp_path):
    """Each ``span`` is also a profiler annotation under its own name, on
    the host thread's line; ``record`` writes none."""
    from jax.profiler import ProfileData

    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("serve.step", step=0):
            with tr.span("serve.dispatch"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
        tr.record("serve.queued", 0, 10, rid=0)
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {
        e.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert {"serve.step", "serve.dispatch"} <= names
    assert "serve.queued" not in names


# ---- export sinks ------------------------------------------------------------


def test_metrics_jsonl_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("serve.steps", width="wide").inc(3)
    reg.gauge("pool.occupancy_frac").set(0.5)
    reg.histogram("serve.ttft_s").observe(0.02)
    path = tmp_path / "metrics.jsonl"
    n = write_metrics_jsonl(reg, str(path), extra={"arch": "t"})
    recs = load_jsonl(str(path))
    assert n == len(recs) == 3
    by_series = {r["series"]: r for r in recs}
    assert set(by_series) == {
        "serve.steps{width=wide}", "pool.occupancy_frac", "serve.ttft_s",
    }
    for r in recs:
        assert r["schema_version"] == SCHEMA_VERSION
        assert r["arch"] == "t"
        assert r["labels"] == ({"width": "wide"} if "{" in r["series"] else {})
    assert by_series["serve.steps{width=wide}"]["value"] == 3
    hist = by_series["serve.ttft_s"]
    assert hist["count"] == 1 and hist["buckets"][-1] == ["+Inf", 1]
    # The records iterator stamps a shared ts.
    (r1, r2, r3) = metric_records(reg, ts=123.0)
    assert r1["ts"] == r2["ts"] == r3["ts"] == 123.0


def test_append_jsonl_stamps(tmp_path):
    path = tmp_path / "sub" / "cache.jsonl"  # parent dir auto-created
    append_jsonl(str(path), {"key": {"arch": "a"}, "winner": 1}, kind="order_sweep")
    append_jsonl(str(path), {"key": {"arch": "b"}, "winner": 2}, kind="order_sweep")
    recs = load_jsonl(str(path))
    assert [r["winner"] for r in recs] == [1, 2]
    for r in recs:
        assert r["schema_version"] == SCHEMA_VERSION
        assert r["kind"] == "order_sweep"
        assert r["ts"] > 0


# ---- LLC sampler -------------------------------------------------------------


class FakePool:
    """The three pool attributes the sampler's footprint probe reads."""

    def __init__(self, lens, slot_pages, refs):
        self.lens = lens
        self._slot_pages = slot_pages
        self._ref = refs


def _sampler(reg, **kw):
    kw.setdefault("page", 16)
    kw.setdefault("n_heads", 8)
    kw.setdefault("n_kv_heads", 2)
    kw.setdefault("head_dim", 32)
    kw.setdefault("elem_bytes", 2)
    kw.setdefault("current_order", "sawtooth")
    kw.setdefault("every", 1)
    return LLCSampler(reg, **kw)


def test_llc_gauge_parity_with_direct_model_call():
    reg = Registry()
    s = _sampler(reg)
    refs = np.ones(16, np.int64)
    pool = FakePool([70, 33, 0], [[1, 2, 3, 4, 5], [6, 7, 8], []], refs)
    assert s.sample(pool)
    assert s.orders[0] == "sawtooth" and len(s.orders) >= 2
    spec = s.fwd_spec_for(70)  # longest live row, page-rounded inside
    assert spec.seq_kv == 80  # 70 tokens -> 5 pages of 16
    for order in s.orders:
        direct = fwd_llc_model(
            spec, order, n_workers=s.n_workers, capacity_bytes=s.capacity_bytes
        )
        gauge = reg.value("llc.modeled_miss_bytes", order=order, model="fwd")
        assert gauge == direct.misses
    assert reg.value("llc.footprint_bytes") == pytest.approx(
        2 * 8 * 16 * 2 * 32 * 2  # K+V * 8 distinct pages * page * hkv * d * bytes
    )
    assert reg.value("llc.active_rows") == 2
    assert reg.value("llc.samples") == 1
    best = int(reg.value("llc.best_order_index"))
    misses = [
        reg.value("llc.modeled_miss_bytes", order=o, model="fwd") for o in s.orders
    ]
    assert misses[best] == min(misses)


def test_llc_sampler_gating_and_empty_pool():
    reg = Registry()
    s = _sampler(reg, every=4)
    pool = FakePool([32], [[1, 2]], np.ones(4, np.int64))
    assert not s.maybe_sample(3, pool)  # off-period
    assert s.maybe_sample(4, pool)
    assert not _sampler(reg, every=0).maybe_sample(0, pool)  # disabled
    assert not s.sample(FakePool([0], [[]], np.ones(1)))  # nothing resident
    s2 = _sampler(Registry(), current_order="cyclic")
    assert s2.orders[0] == "cyclic" and "sawtooth" in s2.orders


def test_llc_shared_prefix_gauges_emitted_when_pages_shared():
    reg = Registry()
    s = _sampler(reg)
    refs = np.ones(16, np.int64)
    refs[1] = refs[2] = 3  # pages 1, 2 shared by all three rows
    pool = FakePool(
        [40, 40, 40], [[1, 2, 3], [1, 2, 4], [1, 2, 5]], refs
    )
    assert s.sample(pool)
    for order in s.orders:
        assert reg.find(
            "llc.modeled_miss_bytes", order=order, model="shared_prefix"
        ) is not None
    assert reg.value("llc.shared_pages") == 2
    # The history entry carries the shared-model readings + live shared
    # fraction (the adaptation controller's blend inputs): 2 of 5 distinct
    # resident pages are shared here.
    entry = s.history[-1]
    assert set(entry["shared_miss"]) == set(s.orders)
    assert entry["shared_frac"] == pytest.approx(2 / 5)


# ---- engine integration ------------------------------------------------------


@pytest.fixture(scope="module")
def deepseek_lm():
    cfg = get_config("deepseek-7b").reduced()
    lm = build_model(cfg)
    return lm, lm.init(jax.random.PRNGKey(0))


def _requests(vocab, lens_and_maxnew):
    rng = np.random.default_rng(7)
    return [
        Request(
            tokens=rng.integers(2, vocab, size=n).astype(np.int32),
            max_new_tokens=m,
            rid=i,
        )
        for i, (n, m) in enumerate(lens_and_maxnew)
    ]


def test_serve_stream_metrics_conservation(deepseek_lm):
    lm, params = deepseek_lm
    eng = ServeEngine(
        lm, params, batch_size=3, max_len=96, scheduler="continuous",
        page_size=16, llc_every=2,
    )
    spec = [(5, 4), (19, 6), (33, 3), (9, 1), (12, 5)]
    reqs = _requests(lm.cfg.vocab, spec)
    results = eng.generate(reqs)
    v = eng.obs.value

    # Conservation: every generated token was produced by exactly one step.
    total = sum(r.steps for r in results)
    assert v("serve.tokens.generated") == total
    # First token of each request comes from its last prefill chunk; the
    # rest are decode-step tokens.
    assert v("serve.step.tokens", kind="decode") == sum(
        max(r.steps - 1, 0) for r in results
    )
    # Every prompt token was either prefilled through the mixed step or
    # adopted from a registered shared prefix.
    assert v("serve.step.tokens", kind="prefill") + v("pool.tokens_adopted") == sum(
        n for n, _ in spec
    )
    assert v("serve.requests", event="finished") == len(spec)
    # One TTFT sample per request; NaN TPOTs (single-token generations) are
    # excluded from the histogram but tallied.
    ttft = eng.obs.find("serve.ttft_s")
    tpot = eng.obs.find("serve.tpot_s")
    assert ttft.count == len(spec)
    n_single = sum(1 for r in results if r.steps <= 1)
    assert tpot.nan_count == n_single
    assert tpot.count == len(spec) - n_single
    # Step counters match the engine's own deterministic tallies.
    st = eng.last_stats
    assert v("serve.steps", width="wide") == st.wide_steps
    assert (
        v("serve.steps", width="wide") + v("serve.steps", width="narrow")
        == st.mixed_steps
    )
    # llc sampler ran and emitted modeled misses for >= 2 traversal orders.
    assert v("llc.samples") >= 1
    orders = {
        m.labels["order"]
        for m in eng.obs.series()
        if m.name == "llc.modeled_miss_bytes" and m.labels.get("model") == "fwd"
    }
    assert len(orders) >= 2
    # Pool gauges exist from init (step-0 dashboards aren't blank).
    assert eng.obs.find("pool.occupancy_frac") is not None
    # Trace captured the step hierarchy.
    names = {e.name for e in eng.tracer.events()}
    assert {"serve.step", "serve.plan_step", "serve.device_step"} <= names


def test_tpot_nan_for_single_token_generation(deepseek_lm):
    lm, params = deepseek_lm
    eng = ServeEngine(
        lm, params, batch_size=2, max_len=64, scheduler="continuous",
        page_size=16,
    )
    reqs = _requests(lm.cfg.vocab, [(6, 1), (6, 4)])
    one, several = eng.generate(reqs)
    assert one.steps == 1 and math.isnan(one.tpot_s)
    if several.steps > 1:
        assert not math.isnan(several.tpot_s)


def test_step_stats_shim_warns(deepseek_lm):
    lm, params = deepseek_lm
    eng = ServeEngine(
        lm, params, batch_size=2, max_len=64, scheduler="continuous",
        page_size=16,
    )
    eng.generate(_requests(lm.cfg.vocab, [(6, 3), (8, 2)]))
    st = eng.last_stats
    assert isinstance(st, StepStats)
    assert st.mixed_steps > 0
    with pytest.warns(DeprecationWarning):
        assert st["mixed_steps"] == st.mixed_steps
    assert set(st.keys()) == set(st.as_dict()) == set(iter(st))
    assert st.get("wide_steps") == st.wide_steps
    assert st.get("nope", -1) == -1


def test_static_path_records_latency_metrics(deepseek_lm):
    lm, params = deepseek_lm
    eng = ServeEngine(lm, params, batch_size=2, max_len=64, scheduler="static")
    reqs = _requests(lm.cfg.vocab, [(6, 3), (8, 4)])
    results = eng.generate(reqs)
    v = eng.obs.value
    assert v("serve.tokens.generated") == sum(r.steps for r in results)
    assert eng.obs.find("serve.ttft_s").count == len(reqs)
    assert v("serve.step.tokens", kind="prefill") > 0
    names = {e.name for e in eng.tracer.events()}
    assert "serve.prefill" in names


PHASES = ("serve.admit", "serve.plan_step", "serve.dispatch", "serve.wait_tokens", "serve.commit")


def _within(outer, events):
    return [e for e in events if outer.ts_ns <= e.ts_ns and e.end_ns <= outer.end_ns]


def test_continuous_step_phases_queue_waits_and_kv_tokens(deepseek_lm):
    lm, params = deepseek_lm
    chunk, slots = 16, 3
    # A budget of a whole chunk per slot: every prefill row takes min(chunk,
    # what is left of its prompt), so the KV count below follows from the
    # lengths alone.
    eng = ServeEngine(
        lm, params, batch_size=slots, max_len=96, scheduler="continuous",
        page_size=16, prefill_chunk=chunk, token_budget=slots * chunk,
    )
    reqs = _requests(lm.cfg.vocab, [(5, 4), (19, 6), (33, 3), (9, 1), (12, 5)])
    reqs[4].arrival = 5
    results = eng.generate(reqs)
    assert all(r.status == "ok" for r in results)
    evs = eng.tracer.events()

    # Every step that dispatched runs the five phases once each, in order,
    # with the launch and the token wait inside the device step.
    steps = [e for e in evs if e.name == "serve.step"]
    dispatched = 0
    for s in steps:
        inner = sorted(_within(s, evs), key=lambda e: e.ts_ns)
        dev = [e for e in inner if e.name == "serve.device_step"]
        if not dev:
            continue
        dispatched += 1
        assert [e.name for e in inner if e.name in PHASES] == list(PHASES)
        assert sorted(e.name for e in _within(dev[0], inner) if e is not dev[0]) == [
            "serve.dispatch", "serve.wait_tokens"]
    assert dispatched == eng.last_stats.mixed_steps > 0

    # One queue wait per request, from the stream's start (arrival step 0)
    # or the boundary that reached its arrival step, to its admission.
    queued = {e.args["rid"]: e for e in evs if e.name == "serve.queued"}
    assert sorted(queued) == [r.rid for r in reqs]
    t0_ns = queued[0].ts_ns
    assert all(queued[r.rid].ts_ns == t0_ns for r in reqs[:4])
    for r in results:
        assert queued[r.rid].end_ns <= t0_ns + r.ttft_s * 1e9
    reached = min((s for s in steps if s.args["step"] >= reqs[4].arrival),
                  key=lambda s: s.ts_ns)
    (admit,) = [e for e in _within(reached, evs) if e.name == "serve.admit"]
    assert admit.ts_ns <= queued[4].ts_ns <= admit.end_ns

    # Keys attended: each prefill chunk attends the prompt up to its end,
    # each of the n - 1 decode steps the prompt and the tokens so far.
    want = 0
    for r, res in zip(reqs, results):
        p, n = len(r.tokens), res.steps
        want += sum(min(end, p) for end in range(chunk, p + chunk, chunk))
        want += sum(p + j for j in range(1, n))
    assert eng.obs.value("serve.step.kv_tokens") == want
