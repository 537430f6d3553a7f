"""Readings for a serve cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed, in one process: the cell's weights and engine, the first
whole bursts of the cell's traffic that serve as many tokens as a run
compares, the sample a run draws from it, and then the
plain reference twice over that sample: in float32, and as the
control in float8 e4m3 (``bench/reference``). Each side is judged by the
predicate a run uses for ``correct`` (``serve.judge``) against the cell's
limits file: the program's gaps as served, the control's (``fp8.*``) as
the gaps of the token the control puts first. Prints one JSON line per
seed with both gaps and both verdicts, then for each number the largest
program reading and the smallest control reading. Exits non-zero when a
program seed is not correct or a control seed is. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, serve, traffic
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU chip", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    limits = harness.load_limits(cell["name"])
    cfg = serve.model_config(config["model"])
    bursts = traffic.bursts_serving(mix, limits["sample_tokens"])
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t = time.perf_counter()
        eng = serve.build_engine(cfg, mix, seed)
        w = serve.run_window(eng, mix, cfg.vocab, seed, 0.0, bursts=bursts)
        del eng
        gc.collect()
        sample = serve.draw_sample(w, seed, limits["sample_tokens"])
        gaps = serve.reference_gaps(config, config["model"], seed, sample, mix["chunk"],
                                    modes=("f32", "fp8"))
        short = sum(len(s.tokens) != s.max_new for s in w.served if s.status == "ok")
        failed = sum(s.status != "ok" for s in w.served)
        correct, _ = serve.judge(gaps, limits, failed=failed, short=short)
        control_correct, checks = serve.judge(gaps, limits, prefix="fp8.")
        row = dict(seed=seed, requests=len(sample), **gaps, correct=correct,
                   control_correct=control_correct, seconds=round(time.perf_counter() - t, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
        for name, c in checks.items():
            print(f"control seed {seed} check {name}: {c['value']!r} limit {c['limit']!r}",
                  file=sys.stderr)
    for name in ("logit_gap", "mean_logit_gap"):
        print(json.dumps({"workload": cell["name"], "number": name, "seeds": len(rows),
                          "lower": max(r[name] for r in rows),
                          "upper": min(r["fp8." + name] for r in rows)}), flush=True)
    sound = all(r["correct"] for r in rows)
    caught = not any(r["control_correct"] for r in rows)
    print(json.dumps({"workload": cell["name"], "program_correct_on_every_seed": sound,
                      "control_correct_on_no_seed": caught}), flush=True)
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
