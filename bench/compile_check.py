"""Compile every one-chip cell's programs at their real sizes for a
described TPU v5e, with no chip: a rehearsal before chip time.

    JAX_PLATFORMS=cpu python bench/compile_check.py [--workload <cell> ...]

For each cell: the jitted weight initialisation, the engine's mixed step at
both widths (prefill chunk and 1) with the cell's slots, pages and donated
pool (shaped by the program's own pool), and the programs the
configuration's reference lists in ``compile_probes`` (a reference without
them is skipped, with one line saying so). Prints one JSON line per
program with the bytes the compiler places in HBM (arguments + outputs +
temporaries - aliased). Nothing runs; the figures are the compiler's, not
a chip's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
GiB = 2**30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, serve, traffic
    from repro.models import build_model
    from repro.serve import ServeEngine
    from repro.serve.kv_pool import PagedKVPool
    from repro.serve.tiering import TieredPagePool

    # A compile for a described chip cannot be read back from the cache.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec_of = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    spec = harness.load_spec()
    cells = [w for w in spec["workloads"] if w["chips"] == 1
             and (args.workload is None or w["name"] in args.workload)]

    def report(cell, program, fn, *specs):
        t = time.perf_counter()
        compiled = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*specs).compile()
        m = compiled.memory_analysis()
        hbm = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
               - m.alias_size_in_bytes)
        print(json.dumps({"workload": cell, "program": program,
                          "args_gib": round(m.argument_size_in_bytes / GiB, 3),
                          "temp_gib": round(m.temp_size_in_bytes / GiB, 3),
                          "alias_gib": round(m.alias_size_in_bytes / GiB, 3),
                          "in_hbm_gib": round(hbm / GiB, 3),
                          "kernel": "tpu_custom_call" in compiled.as_text(),
                          "compile_s": round(time.perf_counter() - t, 1)}), flush=True)

    for cell in cells:
        config = harness.load_config(cell["config"])
        mix = traffic.load_mix(cell["traffic"])
        cfg = serve.model_config(config["model"]).with_(attn_impl="pallas")
        lm = build_model(cfg)
        report(cell["name"], "init", lm.init, spec_of((2,), jnp.uint32))
        params = jax.tree.map(lambda x: spec_of(x.shape, x.dtype),
                              jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
        eng = ServeEngine(lm, None, batch_size=mix["slots"], max_len=mix["max_len"],
                          scheduler="continuous", page_size=mix["page"],
                          prefill_chunk=mix["chunk"])
        slots = mix["slots"]
        geometry = {}

        def pool_pages():
            # The pool generate() builds, from the engine's own settings.
            kw = dict(prefix_sharing=eng.prefix_sharing, registry=eng.obs,
                      admission=eng.admission, n_pages=eng.pool_pages, faults=eng.faults)
            c = eng.lm.cfg
            if eng.host_pages:
                pool = TieredPagePool(c, c.n_layers, slots, eng._cap, host_pages=eng.host_pages,
                                      **kw)
            else:
                pool = PagedKVPool(c, c.n_layers, slots, eng._cap, **kw)
            geometry["block_tables"] = pool.block_tables.shape
            return pool.pages

        pages = jax.tree.map(lambda x: spec_of(x.shape, x.dtype), jax.eval_shape(pool_pages))
        for width in (mix["chunk"], 1):
            report(cell["name"], f"mixed step, width {width}", eng._mixed_step_fn(), params,
                   spec_of((slots, width)), pages, spec_of(geometry["block_tables"]),
                   spec_of((slots,)), spec_of((slots,)), spec_of(()),
                   spec_of((slots,), jnp.float32), spec_of((slots,)), spec_of((slots,)))
        ref = harness.load_reference(config["reference"])
        if not hasattr(ref, "compile_probes"):
            print(f"{cell['name']}: reference {config['reference']!r} lists no compile_probes; "
                  "not sized", flush=True)
            continue
        for program, fn, *shapes in ref.compile_probes(config["model"], mix["max_len"]):
            report(cell["name"], program, fn,
                   *jax.tree.map(lambda x: spec_of(x.shape, x.dtype), shapes))
    return 0


if __name__ == "__main__":
    sys.exit(main())
