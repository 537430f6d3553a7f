"""Compile every one-chip cell's programs at their real sizes for a
described TPU v5e, with no chip: a rehearsal before chip time.

    JAX_PLATFORMS=cpu python bench/compile_check.py [--workload <cell> ...]

For each cell: the jitted weight initialisation, the engine's mixed step at
both widths (prefill chunk and 1) with the cell's slots, pages and donated
pool, and the reference's per-layer programs in float32 and in the fp8
control. Prints one JSON line per program with the bytes the compiler
places in HBM (arguments + outputs + temporaries - aliased). Nothing runs;
the figures are the compiler's, not a chip's.
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
    from bench.reference import decoder
    from repro.models import build_model
    from repro.serve import ServeEngine

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
        slots, page = mix["slots"], mix["page"]
        blocks = -(-mix["max_len"] // page)
        pages = spec_of((cfg.n_layers, slots * blocks + 1, cfg.n_kv_heads, page, cfg.hd),
                        jnp.bfloat16)
        for width in (mix["chunk"], 1):
            report(cell["name"], f"mixed step, width {width}", eng._mixed_step_fn(), params,
                   spec_of((slots, width)), {"k_pages": pages, "v_pages": pages},
                   spec_of((slots, blocks)), spec_of((slots,)), spec_of((slots,)), spec_of(()),
                   spec_of((slots,), jnp.float32), spec_of((slots,)), spec_of((slots,)))
        dm = decoder.Dims.of(config["model"])
        key = spec_of((2,), jnp.uint32)
        report(cell["name"], "reference layer weights",
               lambda k: decoder._layer_weights(k, dm), key)
        w = jax.tree.map(lambda x: spec_of(x.shape, x.dtype),
                         jax.eval_shape(lambda k: decoder._layer_weights(k, dm),
                                        jax.random.PRNGKey(0)))
        x = spec_of((mix["max_len"], dm.d), jnp.float32)
        for mode in ("f32", "fp8"):
            report(cell["name"], f"reference layer, {mode}",
                   lambda x, w, mode=mode: decoder._layer(x, w, dm, mode), x, w)
        report(cell["name"], "reference logits",
               lambda x, h: decoder._logits(x, h, dm, "f32"), x,
               spec_of((dm.d, dm.vocab), jnp.float32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
