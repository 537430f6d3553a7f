"""The device's idle time in a traced serve window, split at each step
boundary by the engine's phase spans.

The engine's Tracer annotates every span for the profiler, so the trace
holds the spans natively, on the clock of the device's ops. The harness
then appends, with ``bench.trace.with_spans``, one copy of each Tracer span
of the run shifted onto that clock by one offset: those copies are the last
``len(r.spans)`` host entries, and this module reads only the entries
before them. On a program whose Tracer does not annotate, no native span is
found and every reading is None.

For each native ``serve.step`` span, on the first device, where idle is
the complement of the union of the device's op intervals over the window
(as in ``idle_frac.serve``):

  launch    idle from the start of each ``serve.dispatch`` to the first op
            that starts at or after it (operand upload and launch);
  readback  idle from the end of the last op that ends inside each
            ``serve.wait_tokens`` to the span's end (the sampled tokens'
            copy to the host); where no op ends inside the span, from its
            start; never before the launch part's end;
  host      the rest of the step's idle time (commit, admission, drafting
            and planning).

The three are disjoint intervals inside the step, so their sum over the
steps plus the idle time outside every ``serve.step`` is the window's idle
time exactly.
"""

from __future__ import annotations

import bisect

from bench import trace as btrace

STEP, DISPATCH, WAIT = "serve.step", "serve.dispatch", "serve.wait_tokens"


def native_spans(r) -> list:
    """The host spans the profiler recorded, without the shifted copies."""
    return r.trace.host[: len(r.trace.host) - len(r.spans)]


class _Idle:
    """Idle nanoseconds of one device's op line over [lo, hi)."""

    def __init__(self, ops, lo: int, hi: int):
        self.lo, self.hi = lo, hi
        self.merged = btrace._union(((o.start, o.end) for o in ops), lo, hi)
        self.starts = [s for s, _ in self.merged]
        self.cum = [0]
        for s, e in self.merged:
            self.cum.append(self.cum[-1] + e - s)

    def _busy_before(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0
        s, e = self.merged[i]
        return self.cum[i] + min(t, e) - s

    def between(self, a: int, b: int) -> int:
        a, b = max(a, self.lo), min(b, self.hi)
        if b <= a:
            return 0
        return (b - a) - (self._busy_before(b) - self._busy_before(a))


def split(r) -> dict | None:
    """{"launch", "readback", "host", "outside"}: idle ns summed over the
    window, with "steps", the count of ``serve.device_step`` spans; None
    without a device trace, native ``serve.step`` spans or device steps."""
    if r.trace is None or not r.trace.devices:
        return None
    steps = sum(e.name == "serve.device_step" for e in r.spans)
    spans = {n: [] for n in (STEP, DISPATCH, WAIT)}
    for name, s, e in native_spans(r):
        if name in spans:
            spans[name].append((s, e))
    if not steps or not spans[STEP]:
        return None
    for v in spans.values():
        v.sort()
    lo, hi = r.trace.window
    ops = r.trace.devices[sorted(r.trace.devices)[0]]
    idle = _Idle(ops, lo, hi)
    op_starts = [o.start for o in ops]  # the line is sorted by start
    op_ends = sorted(o.end for o in ops)

    def inside(kind, s0, s1):
        v = spans[kind]
        i = bisect.bisect_left(v, (s0,))
        while i < len(v) and v[i][0] < s1:
            yield v[i]
            i += 1

    out = {"launch": 0, "readback": 0, "host": 0}
    in_steps = 0
    for s0, s1 in spans[STEP]:
        step_idle = idle.between(s0, s1)
        in_steps += step_idle
        launch_end = s0
        for ds, _ in inside(DISPATCH, s0, s1):
            i = bisect.bisect_left(op_starts, ds)
            first = op_starts[i] if i < len(op_starts) else hi
            launch_end = min(max(first, ds), s1)
            out["launch"] += idle.between(ds, launch_end)
        for ws, we in inside(WAIT, s0, s1):
            i = bisect.bisect_right(op_ends, we) - 1
            last = op_ends[i] if i >= 0 else lo
            out["readback"] += idle.between(max(ws, last, launch_end), we)
        out["host"] += step_idle
    out["host"] -= out["launch"] + out["readback"]
    out["outside"] = idle.between(lo, hi) - in_steps
    out["steps"] = steps
    return out
