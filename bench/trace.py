"""Profiler trace capture and its reduction to device metrics.

``capture`` wraps the measured window in a JAX profiler session and reads
the written ``.xplane.pb`` back into a :class:`Trace`: per device the ops
of its ``XLA Ops`` line, plus host spans on the same clock. The reduction
functions below work on a ``Trace`` alone, so tests drive them from small
recorded or hand-built traces (``bench/fixtures``).

On a TPU the ``XLA Ops`` line nests: a ``while`` op (a scanned layer
stack) spans the ops of its body. Busy time is the union of all
intervals, so nesting does not count twice; per-op time and the
breakdown use leaf ops only (ops that contain no other op).
"""

from __future__ import annotations

import collections
import contextlib
import json
import re
import shutil
import tempfile
import time
from pathlib import Path
from typing import NamedTuple

WINDOW = "bench.window"
SYNC = "bench.sync"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$"
)


class Op(NamedTuple):
    name: str   # short HLO name, e.g. "fusion.198" or "paged_flash_decode_fwd.7"
    kind: str   # result type, e.g. "bf16[8,1,4096]"
    start: int  # ns
    end: int    # ns


class Trace(NamedTuple):
    devices: dict        # device plane name -> list[Op], sorted by start
    host: list           # [(label, start, end)] host spans on the trace clock
    window: tuple        # (start, end) ns of the measured window


def parse_hlo_name(text: str) -> tuple[str, str]:
    """("fusion.198", "bf16[8,4096]") from "%fusion.198 = bf16[8,4096]{1,0...} ..."."""
    head, _, rest = text.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)
    kind = rest[: rest.find(")") + 1] if rest.startswith("(") else rest.split(" ", 1)[0]
    return head.lstrip("%"), kind


def base_name(name: str) -> str:
    """The op name without its instance suffix: "paged_flash_decode_fwd.7" ->
    "paged_flash_decode_fwd"."""
    return re.sub(r"\.\d+$", "", name)


# ------------------------------------------------------------------ capture


@contextlib.contextmanager
def capture(out: dict):
    """Profile the body; afterwards ``out["trace"]`` holds the :class:`Trace`
    and ``out["sync"]`` the (trace ns, perf_counter ns) pair that maps the
    program's own spans onto the trace clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events: they slow the host
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(SYNC):
                perf_sync = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        paths = sorted(Path(tmp).rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        out["trace"], trace_sync = load_xplane(str(paths[-1]))
        out["sync"] = (trace_sync, perf_sync)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load_xplane(path: str) -> tuple[Trace, int]:
    """A :class:`Trace` from an xplane file, and the start of its sync span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    window = sync = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops = []
                for e in line.events:
                    name, kind = parse_hlo_name(e.name)
                    ops.append(Op(name, kind, int(e.start_ns), int(e.start_ns + e.duration_ns)))
                ops.sort(key=lambda o: (o.start, -o.end))
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    if e.name == WINDOW:
                        window = span[1:]
                    elif e.name == SYNC:
                        sync = span[1]
                    elif line.name == "python":
                        host.append(span)
    if window is None or sync is None:
        raise RuntimeError("the trace holds no bench.window or bench.sync span")
    return Trace(devices, host, window), sync


def with_spans(trace: Trace, spans, sync: tuple[int, int]) -> Trace:
    """``trace`` with the program's spans ((name, perf_counter ns start,
    duration ns), as its Tracer records them) moved onto the trace clock."""
    shift = sync[0] - sync[1]
    moved = [(n, s + shift, s + shift + d) for n, s, d in spans if d >= 0]
    return trace._replace(host=trace.host + moved)


# ------------------------------------------------------------------ fixtures


def load(path: str) -> Trace:
    """A :class:`Trace` stored as JSON: ``{"devices": {plane: [[name, kind,
    start, end], ...]}, "host": [[label, start, end], ...], "window": [start,
    end]}`` (the test fixtures under ``bench/fixtures``)."""
    with open(path) as f:
        d = json.load(f)
    return Trace({k: [Op(*o) for o in v] for k, v in d["devices"].items()},
                 [tuple(h) for h in d["host"]], tuple(d["window"]))


# ------------------------------------------------------------------ reduction


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def leaves(ops: list[Op]) -> list[Op]:
    """Ops that contain no other op. The line is sorted by start (longer
    first on ties), so a container is followed by an op inside it."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= o.end or nxt.end > o.end:
            out.append(o)
    return out


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which some op ran, averaged over devices."""
    lo, hi = trace.window
    per = [_length(_union([(o.start, o.end) for o in ops], lo, hi))
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_seconds(trace: Trace, base: str) -> float:
    """Device seconds of leaf ops named ``base`` (any instance), averaged
    over devices."""
    lo, hi = trace.window
    per = [sum(min(o.end, hi) - max(o.start, lo) for o in leaves(ops)
               if base_name(o.name) == base and o.start < hi and o.end > lo)
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def collective_exposed_s(trace: Trace, device: str | None = None) -> float:
    """Seconds in which a collective runs on ``device`` (default: the first)
    and no other op does. Waits for the training cell (PERF.md, Open
    questions, row 0): no one-chip cell runs a collective."""
    lo, hi = trace.window
    ops = trace.devices[device or sorted(trace.devices)[0]]
    coll = _union([(o.start, o.end) for o in ops if COLLECTIVE.match(o.name)], lo, hi)
    comp = _union([(o.start, o.end) for o in leaves(ops) if not COLLECTIVE.match(o.name)], lo, hi)
    exposed, j = 0, 0
    for s, e in coll:
        covered = 0
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
        exposed += (e - s) - covered
    return exposed / 1e9


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` leaf ops (by name and result type) that took most device
    time in the window, in seconds averaged over devices."""
    lo, hi = trace.window
    tot: collections.Counter = collections.Counter()
    for ops in trace.devices.values():
        for o in leaves(ops):
            if o.start < hi and o.end > lo:
                tot[f"{o.name} {o.kind}"] += min(o.end, hi) - max(o.start, lo)
    nd = max(len(trace.devices), 1)
    return [[k, v / nd / 1e9] for k, v in tot.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle device time on the first device, summed by what the host was
    doing: each gap goes to the shortest host span that covers its middle.
    The ``n`` largest sums, in seconds."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    ops = trace.devices[sorted(trace.devices)[0]]
    busy = _union([(o.start, o.end) for o in ops], lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    host = sorted(trace.host, key=lambda h: h[2] - h[1])
    tot: collections.Counter = collections.Counter()
    for s, e in gaps:
        mid = (s + e) // 2
        label = next((h[0] for h in host if h[1] <= mid < h[2]), "no host span")
        tot[label] += e - s
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
