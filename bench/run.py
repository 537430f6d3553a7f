"""Run one benchmark cell and print its result as the last line of stdout.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a profiled window. The run needs as many TPU chips as the cell asks
for; without them it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)

    t = time.perf_counter()
    import jax

    t_jax = time.perf_counter()
    devices = jax.devices()
    phases = {"import_jax": t_jax - t, "chip": time.perf_counter() - t_jax}
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import serve

    line = serve.run(cell=cell, spec=spec, seed=args.seed, seconds=args.seconds,
                     traced=bool(args.trace), t_start=T_START, devices=devices,
                     phases=phases)
    return 0 if line is not None else 1


if __name__ == "__main__":
    sys.exit(main())
