"""The trace reduction on a hand-built trace whose answers are known, and
on a piece of a traced chip run (deepseek-7b serving 8 slots x 256 on one
TPU v5e)."""

from pathlib import Path

import numpy as np
import pytest

from bench import trace as T

FIXTURE = Path(T.__file__).resolve().parent / "fixtures" / "ds7b_chat_cut.json"


def hand_built():
    a = [T.Op("while.1", "s32[]", 100, 500), T.Op("fusion.1", "bf16[8,4096]", 100, 200),
         T.Op("paged_flash_decode_fwd.3", "bf16[256,8,128]", 250, 400),
         T.Op("fusion.2", "bf16[8,4096]", 400, 500), T.Op("all-reduce.2", "f32[4096]", 600, 750),
         T.Op("fusion.3", "bf16[8,4096]", 700, 800), T.Op("fusion.4", "bf16[8,4096]", 900, 1100)]
    b = [T.Op("fusion.9", "bf16[8,4096]", 0, 500)]
    host = [("bench.burst", 0, 1000), ("serve.plan_step", 520, 580)]
    return T.Trace({"/device:TPU:0": a, "/device:TPU:1": b}, host, (0, 1000))


def test_hand_built_trace():
    tr = hand_built()
    assert T.window_s(tr) == 1e-6
    # device 0: [100,500) + [600,800) + [900,1000) = 700 ns; device 1: 500 ns.
    assert T.busy_s(tr) == pytest.approx(600e-9)
    assert T.op_seconds(tr, "paged_flash_decode_fwd") == pytest.approx(75e-9)  # 150 ns / 2 devices
    assert T.collective_exposed_s(tr) == pytest.approx(100e-9)
    assert [o.name for o in T.leaves(tr.devices["/device:TPU:0"])] == [
        "fusion.1", "paged_flash_decode_fwd.3", "fusion.2", "all-reduce.2", "fusion.3", "fusion.4"]
    top = dict(T.top_ops(tr))
    assert top["paged_flash_decode_fwd.3 bf16[256,8,128]"] == pytest.approx(75e-9)
    assert top["fusion.9 bf16[8,4096]"] == pytest.approx(250e-9)
    assert "while.1 s32[]" not in top
    gaps = dict(T.idle_gaps(tr))
    assert gaps == {"bench.burst": pytest.approx(200e-9), "serve.plan_step": pytest.approx(100e-9)}


def test_program_spans_move_onto_the_trace_clock():
    tr = T.with_spans(hand_built(), [("serve.step", 10_500, 100)], (500, 10_000))
    assert ("serve.step", 1000, 1100) in tr.host


def test_parse_hlo_name():
    text = ("%paged_flash_decode_fwd.7 = bf16[256,256,128]{2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[8,2]{1,0:T(8,128)S(1)} %get-tuple-element.477)")
    assert T.parse_hlo_name(text) == ("paged_flash_decode_fwd.7", "bf16[256,256,128]")
    assert T.base_name("paged_flash_decode_fwd.7") == "paged_flash_decode_fwd"
    tup = ("%fusion.217 = (f32[8,256]{1,0:T(8,128)S(1)}, s32[8,256]{1,0:T(8,128)}) "
           "fusion(bf16[8,256,102400]{2,1,0} %x), kind=kLoop")
    assert T.parse_hlo_name(tup) == ("fusion.217", "(f32[8,256], s32[8,256])")


def test_cut_of_a_chip_trace():
    tr = T.load(str(FIXTURE))
    (ops,) = tr.devices.values()
    lo, hi = tr.window
    mask = np.zeros(hi - lo, bool)  # 1 ns resolution, brute force
    for o in ops:
        mask[max(o.start, lo) - lo:min(o.end, hi) - lo] = True
    assert T.busy_s(tr) == pytest.approx(mask.sum() / 1e9)
    assert 0 < T.busy_s(tr) <= T.window_s(tr)
    kernel = [o for o in ops if o.name.startswith("paged_flash_decode_fwd.")]
    assert kernel and T.op_seconds(tr, "paged_flash_decode_fwd") == pytest.approx(
        sum(o.end - o.start for o in kernel) / 1e9)
    leaf = T.leaves(ops)
    assert not any(o.name.startswith("while") for o in leaf)
    assert any(o.name.startswith("while") for o in ops)
    assert sum(s for _, s in T.top_ops(tr, n=10**6)) == pytest.approx(
        sum(o.end - o.start for o in leaf) / 1e9)
    idle = sum(s for _, s in T.idle_gaps(tr, n=10**6))
    assert idle == pytest.approx(T.window_s(tr) - T.busy_s(tr))
