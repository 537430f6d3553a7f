"""The readers of the engine's phase spans, queue waits and KV counter: the
idle split on a hand-built trace whose answers are known (and whose shifted
span copies would give other answers), the paged kernel's roofline share on
hand-set numbers, and the queue wait in a traced run at reduced size."""

import time

import jax
import pytest

from bench import boundary, harness, serve
from bench import trace as T
from bench.harness import load_reader
from repro.obs import SpanEvent

SPEC = harness.load_spec()
DS7B = harness.load_config("deepseek-7b")["model"]


def readings(*, trace=None, spans=(), kv_tokens=0.0, peak=None):
    return harness.Readings(
        model=DS7B, mix={}, chips=1, peak=peak, window_s=1.0, spans=list(spans),
        counter=lambda name, **lb: kv_tokens if name == "serve.step.kv_tokens" else 0.0,
        requests=[], trace=trace)


# Two steps in a 1000 ns window; device ops (busy 430 ns, idle 570 ns):
OPS = [(0, 90), (180, 300), (310, 350), (600, 700), (720, 800)]
NATIVE = [  # (name, start, end) as the profiler records the engine's spans
    ("serve.step", 100, 400), ("serve.dispatch", 150, 170), ("serve.wait_tokens", 200, 390),
    ("serve.step", 450, 850), ("serve.dispatch", 560, 580), ("serve.wait_tokens", 590, 830),
]
DEVICE_STEPS = [("serve.device_step", 140, 395), ("serve.device_step", 555, 840)]
SHIFT = 10_000  # the Tracer's clock runs this far ahead of the trace's
IDLE = ("launch_idle_ms_per_step", "readback_idle_ms_per_step", "host_idle_ms_per_step")


def hand_built(native=True):
    ops = [T.Op(f"fusion.{i}", "bf16[4,4096]", s, e) for i, (s, e) in enumerate(OPS)]
    host = [("bench.burst", 0, 1000), ("np.asarray(jax.Array)", 210, 380)]
    if native:
        host += NATIVE + DEVICE_STEPS
    tr = T.Trace({"/device:TPU:0": ops}, host, (0, 1000))
    spans = [SpanEvent(n, s + SHIFT, e - s, 1, {"width": 1} if n == "serve.device_step" else None)
             for n, s, e in NATIVE + DEVICE_STEPS]
    # The harness's one-offset shift lands the copies 25 ns late: a reader
    # that took them would read other numbers.
    tr = T.with_spans(tr, [(e.name, e.ts_ns, e.dur_ns) for e in spans], (25, SHIFT))
    return readings(trace=tr, spans=spans)


def test_idle_split_on_a_hand_built_trace():
    r = hand_built()
    # Step 1 idle [100,180) [300,310) [350,400): launch [150,180) 30, readback
    # after the last op end in the wait, [350,390) 40, host 70. Step 2 idle
    # [450,600) [700,720) [800,850): launch [560,600) 40, readback [800,830)
    # 30, host 150. Outside both steps: [90,100) [400,450) [850,1000) 210.
    assert boundary.split(r) == {"launch": 70, "readback": 70, "host": 220, "outside": 210,
                                 "steps": 2}
    got = {m: load_reader(m)(r) for m in IDLE}
    assert got == pytest.approx({"launch_idle_ms_per_step": 35e-6,
                                 "readback_idle_ms_per_step": 35e-6,
                                 "host_idle_ms_per_step": 110e-6})
    # The split closes against the device's idle share of the window.
    idle_ms = load_reader("idle_frac.serve")(r) / 100 * T.window_s(r.trace) * 1e3
    assert sum(got.values()) * 2 + 210e-6 == pytest.approx(idle_ms)


def test_idle_split_needs_the_native_spans():
    """A program whose Tracer does not annotate leaves only the shifted
    copies: the readers find nothing, and do not raise."""
    r = hand_built(native=False)
    assert boundary.split(r) is None
    for m in IDLE:
        assert load_reader(m)(r) is None
        assert load_reader(m)(readings(spans=r.spans)) is None  # no device trace


def test_paged_attn_roofline_on_hand_set_numbers():
    ops = [T.Op("paged_flash_decode_fwd.7", "bf16[128,8,128]", 0, 1_200_000_000),
           T.Op("fusion.1", "bf16[4,11008]", 1_200_000_000, 1_300_000_000)]
    tr = T.Trace({"/device:TPU:0": ops}, [], (0, 2_000_000_000))
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # deepseek-7b: 30 layers x K and V x 32 heads x 128 x 2 B = 491,520 B a key.
    r = readings(trace=tr, kv_tokens=1e6, peak=peak)
    read = load_reader("paged_attn_roofline")
    assert read(r) == pytest.approx(100 * 491_520e6 / (1.2 * 819e9))
    assert read(readings(trace=tr, peak=peak)) is None  # no counter
    assert read(readings(trace=tr, kv_tokens=1e6)) is None  # no peak


def test_queue_wait_median_of_the_queued_spans():
    spans = [SpanEvent("serve.queued", 0, ms * 1_000_000, 1, {"rid": i})
             for i, ms in enumerate([10, 1, 3, 2])] + [SpanEvent("serve.step", 0, 5, 1)]
    assert load_reader("queue_wait_p50_ms")(readings(spans=spans)) == pytest.approx(2.5)
    assert load_reader("queue_wait_p50_ms")(readings(spans=spans[-1:])) is None


def test_traced_run_reports_queue_wait():
    """Spans alone give the queue wait on any backend."""
    cell = harness.find_cell(SPEC, "ds7b-chat")
    cfg = serve.model_config(DS7B).reduced()
    line = serve.run(cell=cell, spec=SPEC, seed=2**31 + 13, seconds=0.0, traced=True,
                     t_start=time.perf_counter(), devices=jax.devices(), cfg=cfg, bursts=1)
    assert line["correct"]
    assert line["metrics"]["queue_wait_p50_ms"]["value"] > 0
    assert line["metrics"]["queue_wait_p50_ms"]["unit"] == "ms"
