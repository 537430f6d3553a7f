"""What every cell shares: the spec, the files found by name, the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limits are in
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding any of them is adding a file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def load_spec(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return load_json(path)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; known: {[w['name'] for w in spec['workloads']]}")


def load_config(name: str, root: Path = BENCH / "configs") -> dict:
    return load_json(root / f"{name}.json")


def load_limits(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")


def metrics_for(spec: dict, cell: str, group: str) -> list[dict]:
    """The ``group`` ("end_to_end" or "per_layer") metrics the cell reports."""
    return [m for m in spec[group] if cell in m.get("workloads", [cell])]


def load_reader(name: str, root: Path = BENCH / "metrics") -> Callable:
    """``read`` of ``<root>/<name>.py``: Readings -> number, or None when
    the run has nothing for it to read."""
    path = root / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} at {path}")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Readings:
    """Everything a per-layer reader may read about one traced window."""

    model: dict                 # the configuration's "model" dict
    mix: dict                   # the traffic mix
    chips: int
    peak: dict                  # bench.peaks entry of the device kind
    window_s: float             # host-clock length of the window
    spans: list                 # program Tracer events inside the window
    counter: Callable[..., float]   # (name, **labels) -> increase over the window
    requests: list              # (prompt_len, tokens_generated) of every request
    trace: Any = None           # bench.trace.Trace, or None


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def device_info(devices, used: int) -> dict:
    """Platform, kind and count as JAX reports them, with the peak bytes in
    use on the fullest of the ``used`` devices."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:used]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
         checks: dict, breakdown: Optional[dict] = None) -> dict:
    """Print the compared numbers as the last lines on stderr and the result
    as the last line on stdout; ``checks`` comes last in the line."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    line: dict = {"correct": correct, "attempted": attempted, "failed": failed,
                  "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line
