"""Serve cells: the program's ``ServeEngine`` under back-to-back bursts.

Set-up makes the weights on the device from the seed (the program's own
jitted ``init_params``), builds the continuous-batching engine with the
mix's slots, ``max_len``, page and prefill chunk, and sends one request
that compiles (or loads from the compile cache) both widths of the mixed
step. The window then sends bursts, one ``generate()`` call each, until
``seconds`` have passed: every request of the last burst carries the
window's remaining time as its deadline, so the window closes within one
step of ``seconds``.

What is measured, on the host clock:
  output_tok_per_s  every token generated in the window, over the window
  ttft_p50_ms       median time to first token, from the burst's start
  tpot_p50_ms       median over requests of (last - first token time)/(n - 1)
Latencies come from the bursts that ended before the window closed; the
requests the window cut are neither samples nor failures.

What is compared: a sample of the finished requests, drawn from the seed
with the longest among them. The plain reference (``bench/reference``)
runs once over each prompt with its served tokens, after the engine has
left the chip, and reads by how much each served token's logit lies below
its best logit at that position (greedy decoding serves the program's best
token, so a sound program reads only its rounding). The cell's limits file
(``bench/limits/<cell>.json``) names the numbers compared, the widest or
the mean of those gaps, and their limits.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import sys
import time
import typing

import numpy as np

from bench import harness, traffic
from bench import trace as btrace

NO_EOS = -1  # outputs run to the mix's lengths: no sampled id ends a request


def model_config(model: dict):
    """The program's ModelConfig for a configuration's ``model`` dict: each
    dict under a field typed as a dataclass (``moe``, ``ssm``, ...) becomes
    that dataclass."""
    from repro.configs.base import ModelConfig

    return _from_dict(ModelConfig, model)


def _from_dict(cls, d: dict):
    hints = typing.get_type_hints(cls)
    kw = {}
    for k, v in d.items():
        t = hints.get(k)  # an unknown field is left for the constructor to refuse
        sub = [c for c in (t, *typing.get_args(t)) if dataclasses.is_dataclass(c)]
        kw[k] = _from_dict(sub[0], v) if sub and isinstance(v, dict) else v
    return cls(**kw)


def model_dict(cfg) -> dict:
    """The ``model`` dict of a program ModelConfig (for reduced test sizes)."""
    return dataclasses.asdict(cfg)


@dataclasses.dataclass
class Served:
    burst: int
    prompt: np.ndarray
    max_new: int
    status: str
    tokens: np.ndarray
    ttft_s: float
    tpot_s: float


@dataclasses.dataclass
class Window:
    served: list
    window_s: float
    complete: set  # indices of bursts that ended before the window closed
    compiles: int  # backend compilations inside the window
    burst_s: list  # wall seconds of each burst


def build_engine(cfg, mix: dict, seed: int, *, registry=None, tracer=None, phases=None):
    """Weights from the seed, the engine, and a warm-up request that brings
    both mixed-step widths (chunk and 1) into memory. ``phases``, if given,
    gets the seconds of each part."""
    import jax

    from repro.launch.serve import init_params
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    t = time.perf_counter()
    lm = build_model(cfg)
    params = jax.block_until_ready(init_params(lm, seed))
    t_weights = time.perf_counter()
    eng = ServeEngine(lm, params, batch_size=mix["slots"], max_len=mix["max_len"],
                      scheduler="continuous", page_size=mix["page"],
                      prefill_chunk=mix["chunk"], registry=registry, tracer=tracer)
    rng = np.random.default_rng([seed, 1])
    warm = rng.integers(traffic.FIRST_ID, cfg.vocab, size=mix["prompt"]["min"]).astype(np.int32)
    (res,) = eng.generate([Request(tokens=warm, max_new_tokens=2, rid=-1, eos_id=NO_EOS)])
    if res.status != "ok" or eng.compiled_step_count() != 2:
        raise RuntimeError(f"warm-up ended {res.status} with {eng.compiled_step_count()} widths")
    if phases is not None:
        phases.update(weights=t_weights - t, warm_up=time.perf_counter() - t_weights)
    return eng


def run_window(eng, mix: dict, vocab: int, seed: int, seconds: float,
               bursts: int | None = None) -> Window:
    """Bursts until ``seconds`` have passed, the last one cut at the close;
    or, given ``bursts``, exactly that many whole bursts."""
    import jax

    from repro.serve import Request

    compiles = []

    def listener(event, *_, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    gen = traffic.bursts(mix, vocab, seed)
    served, complete, burst_s = [], set(), []
    t0 = time.perf_counter()
    try:
        b = 0
        while b < bursts if bursts is not None else time.perf_counter() - t0 < seconds:
            deadline = None if bursts is not None else seconds - (time.perf_counter() - t0)
            reqs = next(gen)
            requests = [Request(tokens=r.prompt, max_new_tokens=r.max_new, rid=b * len(reqs) + i,
                                eos_id=NO_EOS, deadline_s=deadline) for i, r in enumerate(reqs)]
            tb = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.burst"):
                results = eng.generate(requests)
            burst_s.append(round(time.perf_counter() - tb, 3))
            if all(res.status == "ok" for res in results):
                complete.add(b)
            served += [Served(b, r.prompt, r.max_new, res.status, res.tokens, res.ttft_s, res.tpot_s)
                       for r, res in zip(reqs, results)]
            b += 1
        window_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return Window(served, window_s, complete, len(compiles), burst_s)


def end_to_end(w: Window) -> dict:
    done = [s for s in w.served if s.burst in w.complete]
    tokens = sum(len(s.tokens) for s in w.served)
    out = {"output_tok_per_s": tokens / w.window_s}
    if done:
        out["ttft_p50_ms"] = harness.percentile([s.ttft_s for s in done], 50) * 1e3
    tpots = [s.tpot_s for s in done if len(s.tokens) >= 2]
    if tpots:
        out["tpot_p50_ms"] = harness.percentile(tpots, 50) * 1e3
    return out


def counts(w: Window) -> tuple[int, int]:
    """(attempted, failed): requests not cut by the window, and those of
    them that did not end ``ok``."""
    cut = [s for s in w.served if s.burst not in w.complete and s.status == "deadline"]
    attempted = len(w.served) - len(cut)
    failed = sum(s.status != "ok" for s in w.served) - len(cut)
    return attempted, failed


def draw_sample(w: Window, seed: int, want_tokens: int) -> list[Served]:
    """Finished requests, drawn from the seed, the longest first, until
    they hold ``want_tokens`` served tokens."""
    ok = [s for s in w.served if s.status == "ok" and len(s.tokens)]
    if not ok:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(range(len(ok)), key=lambda i: len(ok[i].prompt) + len(ok[i].tokens))
    order = [longest] + [int(i) for i in rng.permutation(len(ok)) if i != longest]
    pick, n = [], 0
    for i in order:
        pick.append(ok[i])
        n += len(ok[i].tokens)
        if n >= want_tokens:
            break
    return pick


def reference_gaps(config: dict, model: dict, seed: int, sample: list, bucket: int,
                   modes=("f32",)) -> dict:
    """Logit gaps against the float32 reference over ``sample`` (see the
    reference's ``logit_gaps``). Each request is one row, right-padded to a
    multiple of ``bucket`` positions, so that few row lengths compile."""
    ref = harness.load_reference(config["reference"])
    tokens, targets = [], []
    for s in sample:
        seq = np.concatenate([s.prompt, s.tokens[:-1]])
        n = -(-len(seq) // bucket) * bucket
        tokens.append(np.zeros(n, np.int32))
        tokens[-1][: len(seq)] = seq
        targets.append(np.full(n, -1, np.int32))
        targets[-1][len(s.prompt) - 1 : len(seq)] = s.tokens
    return ref.logit_gaps(model, seed, tokens, targets, ("f32",) + tuple(
        m for m in modes if m != "f32"))


def judge(gaps: dict | None, limits: dict, *, failed: int = 0, short: int = 0,
          prefix: str = "") -> tuple[bool, dict]:
    """(correct, checks): every gap the cell's limits name (read from
    ``gaps`` under ``prefix``, "" for the program and "fp8." for the
    control) within its limit, enough tokens compared, no request failed
    and none served short of its length."""
    checks = {name: {"value": None if gaps is None else gaps[prefix + name],
                     "limit": lim["limit"]} for name, lim in limits["checks"].items()}
    checks["tokens_compared"] = {"value": 0 if gaps is None else gaps["tokens"],
                                 "limit": limits["sample_tokens"]}
    checks["short_outputs"] = {"value": short, "limit": 0}
    correct = (gaps is not None and failed == 0 and short == 0
               and gaps["tokens"] >= limits["sample_tokens"]
               and all(checks[name]["value"] <= lim["limit"]
                       for name, lim in limits["checks"].items()))
    return correct, checks


def run(*, cell: dict, spec: dict, seed: int, seconds: float, traced: bool, t_start: float,
        devices, cfg=None, bursts=None, phases=None) -> dict:
    """One run of a serve cell; prints and returns the result line.

    ``cfg`` replaces the configuration's model and ``bursts`` the window's
    length by a count of whole bursts (tests pass the program's reduced
    size and one burst, which a busy CPU takes long to serve); ``phases``
    holds the seconds of set-up already spent, by part, for the set-up line
    on stderr."""
    from repro.obs import Registry, Tracer
    from repro.obs.metrics import render_series

    from bench import peaks

    config = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    limits = harness.load_limits(cell["name"])
    peak = peaks.peak_for(devices[0].device_kind) if devices[0].platform == "tpu" else None
    cfg = cfg or model_config(config["model"])
    model = model_dict(cfg)

    registry, tracer = Registry(), Tracer(capacity=1 << 20)
    phases = dict(phases or {})
    phases["other_start"] = time.perf_counter() - t_start - sum(phases.values())
    eng = build_engine(cfg, mix, seed, registry=registry, tracer=tracer, phases=phases)
    setup_s = time.perf_counter() - t_start
    print("set-up " + ", ".join(f"{k} {v:.2f} s" for k, v in phases.items()), file=sys.stderr)

    before = registry.snapshot()["counters"]
    tracer.clear()
    cap: dict = {}
    with btrace.capture(cap) if traced else contextlib.nullcontext():
        w = run_window(eng, mix, cfg.vocab, seed, seconds, bursts)
    device = harness.device_info(devices, cell["chips"])
    counter = lambda name, **lb: registry.value(name, **lb) - before.get(render_series(name, lb), 0.0)
    spans = [e for e in tracer.events() if e.dur_ns >= 0]
    del eng  # the weights and pool leave the chip before the reference runs
    gc.collect()

    attempted, failed = counts(w)
    steps = {wd: [e.dur_ns / 1e6 for e in spans if e.name == "serve.device_step"
                  and (e.args["width"] > 1) == wd] for wd in (False, True)}
    print(f"window {w.window_s:.3f} s, {len(w.served)} requests in {len(w.complete)} whole "
          f"bursts and {max(s.burst for s in w.served) + 1 - len(w.complete)} cut, "
          f"{w.compiles} compilations inside it; bursts took {w.burst_s} s; device steps "
          f"narrow {len(steps[False])} x {np.mean(steps[False] or [0]):.2f} ms, "
          f"wide {len(steps[True])} x {np.mean(steps[True] or [0]):.2f} ms; host load average "
          f"{os.getloadavg()[0]:.2f} on {os.cpu_count()} cores", file=sys.stderr)
    short = sum(len(s.tokens) != s.max_new for s in w.served if s.status == "ok")
    sample = draw_sample(w, seed, limits["sample_tokens"])
    t = time.perf_counter()
    gaps = reference_gaps(config, model, seed, sample, mix["chunk"]) if sample else None
    print(f"reference over {len(sample)} requests: {time.perf_counter() - t:.1f} s",
          file=sys.stderr)
    correct, checks = judge(gaps, limits, failed=failed, short=short)

    e2e = dict(end_to_end(w), setup_s=setup_s)
    metrics: dict = {}
    breakdown = None
    if not traced:
        for m in harness.metrics_for(spec, cell["name"], "end_to_end"):
            if m["name"] not in e2e:
                raise RuntimeError(f"{cell['name']}: the window gave no {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        tr = btrace.with_spans(cap["trace"], [(e.name, e.ts_ns, e.dur_ns) for e in spans],
                               cap["sync"])
        r = harness.Readings(model=model, mix=mix, chips=cell["chips"], peak=peak,
                             window_s=w.window_s, spans=spans, counter=counter,
                             requests=[(len(s.prompt), len(s.tokens)) for s in w.served],
                             trace=tr, config=config)
        for m in harness.metrics_for(spec, cell["name"], "per_layer"):
            v = harness.load_reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = btrace.busy_s(tr)
        device["window_s"] = btrace.window_s(tr)
        breakdown = btrace.breakdown(tr)
    return harness.emit(correct=correct, attempted=attempted, failed=failed, metrics=metrics,
                        device=device, checks=checks, breakdown=breakdown)
