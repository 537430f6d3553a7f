"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --reduced \
      --steps 100 --batch 8 --seq 256 --mesh 1x1

Full-size configs target the production mesh (run under a real TPU runtime);
--reduced runs the same code path end-to-end on CPU (examples/train_lm.py
drives a ~100M-param variant through a few hundred steps).
"""

from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import ParallelConfig, TrainConfig, get_config
from repro.core.schedule import Order
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import build_model
from repro.train.fault_tolerance import FailureInjector
from repro.train.loop import run_training


def parse_mesh(s: str):
    if s == "production":
        return make_production_mesh()
    if s == "multipod":
        return make_production_mesh(multi_pod=True)
    parts = [int(x) for x in s.split("x")]
    assert len(parts) == 2, "mesh must be DxM, 'production', or 'multipod'"
    return make_local_mesh(*parts)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=None, help="override width")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adamw_factored"])
    ap.add_argument("--attn-order", default="sawtooth",
                    choices=[o.value for o in Order],
                    help="KV traversal order (core/schedule.py Traversal IR)")
    ap.add_argument("--snake-group", type=int, default=None,
                    help="block_snake reversal window in KV tiles "
                    "(default: schedule default; sweep with "
                    "benchmarks/hillclimb.py --sweep-orders)")
    ap.add_argument(
        "--attn-impl",
        default=None,
        choices=["auto", "pallas", "pallas_interpret", "xla", "jnp", "reference"],
        help="attention impl; fused flash backward for pallas*/xla, "
        "'jnp' keeps the recompute-VJP fallback",
    )
    ap.add_argument("--bwd-q-block", type=int, default=None,
                    help="fused-backward q tile (default: q_block)")
    ap.add_argument("--bwd-kv-block", type=int, default=None,
                    help="fused-backward kv tile (default: kv_block)")
    ap.add_argument("--crash-at", type=int, default=None, help="inject failure (FT demo)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the obs metrics registry as JSONL here "
                         "(step time/throughput/loss/grad-norm series)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write step/checkpoint spans as Chrome-trace JSON")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    overrides = {"attn_order": args.attn_order, "snake_group": args.snake_group}
    if args.attn_impl:
        overrides.update(attn_impl=args.attn_impl)
    if args.bwd_q_block:
        overrides.update(bwd_q_block=args.bwd_q_block)
    if args.bwd_kv_block:
        overrides.update(bwd_kv_block=args.bwd_kv_block)
    if args.d_model:
        overrides.update(d_model=args.d_model)
    if args.layers:
        overrides.update(n_layers=args.layers)
    cfg = cfg.with_(**overrides)

    lm = build_model(cfg)
    mesh = parse_mesh(args.mesh)
    tcfg = TrainConfig(
        lr=args.lr,
        total_steps=args.steps,
        warmup_steps=max(args.steps // 20, 1),
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir,
        optimizer=args.optimizer,
        seed=args.seed,
    )
    pcfg = ParallelConfig(
        fsdp_axes=("data",), data_axes=("data",), microbatches=args.microbatches
    )
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=args.seed)
    injector = FailureInjector(crash_at=(args.crash_at,)) if args.crash_at else None
    res = run_training(
        lm, tcfg, pcfg, mesh, steps=args.steps, data_cfg=dcfg, injector=injector
    )
    print(
        f"done: final_step={res.final_step} resumed_from={res.resumed_from} "
        f"first_loss={res.losses[0] if res.losses else None} "
        f"last_loss={res.losses[-1] if res.losses else None}"
    )
    if args.metrics_out and res.registry is not None:
        from repro.obs import write_metrics_jsonl

        n = write_metrics_jsonl(
            res.registry, args.metrics_out, extra={"arch": args.arch}
        )
        print(f"wrote {n} metric series -> {args.metrics_out}")
    if args.trace_out and res.tracer is not None:
        res.tracer.write(args.trace_out)
        print(f"wrote {len(res.tracer.events())} trace events -> {args.trace_out}")


if __name__ == "__main__":
    main()
