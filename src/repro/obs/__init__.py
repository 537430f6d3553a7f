"""``repro.obs`` — unified telemetry: metrics registry, span traces, sinks,
and the live modeled-LLC sampler.

Layering (DESIGN.md §10): hot paths record into a :class:`Registry` and a
:class:`Tracer` (cheap, in-process, no I/O); sinks (``repro.obs.export``)
pull snapshots into JSONL / Chrome-trace files on demand; consumers are CI
schema checks (``benchmarks/check_metrics.py``), trace viewers, and —
next — the online traversal-order adaptation that reads
``llc.modeled_miss_bytes`` (ROADMAP item 4).

Engines and the train loop carry their own instances, so streams don't
interleave.
"""

from repro.obs.autotune import (
    canonicalize_key,
    load_autotune_cache,
    lookup_order_winner,
    normalize_autotune_key,
)
from repro.obs.export import (
    SCHEMA_VERSION,
    append_jsonl,
    load_jsonl,
    metric_records,
    write_metrics_jsonl,
)
from repro.obs.llc import DEFAULT_CAPACITY_BYTES, LLCSampler
from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from repro.obs.trace import SpanEvent, Tracer

__all__ = [
    "SCHEMA_VERSION",
    "append_jsonl",
    "canonicalize_key",
    "load_autotune_cache",
    "load_jsonl",
    "lookup_order_winner",
    "normalize_autotune_key",
    "metric_records",
    "write_metrics_jsonl",
    "DEFAULT_CAPACITY_BYTES",
    "LLCSampler",
    "LATENCY_BUCKETS_S",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "SpanEvent",
    "Tracer",
]
