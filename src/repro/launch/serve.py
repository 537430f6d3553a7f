"""Serving launcher: load (or init) params and serve synthetic batched
requests through the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
      --requests 16 --max-new 24

``--scheduler continuous`` serves over the paged KV pool with continuous
batching (token-only full-attention archs); ``auto`` picks it when the
arch supports it and falls back to the static-group path otherwise.

Telemetry (``repro.obs``): ``--metrics-out metrics.jsonl`` dumps the
engine's registry (TTFT/TPOT histograms, per-kind token counters, pool and
scheduler gauges, ``llc.modeled_miss_bytes{order=...}``) one JSON line per
series, and ``--trace-out trace.json`` writes the step spans as
Chrome-trace JSON — open it in ``chrome://tracing`` or Perfetto. The
``llc.*`` gauges sample every ``--llc-every`` mixed steps (0 disables);
``--log-every`` prints a periodic one-line stats summary mid-stream.

``--attn-order auto`` turns on online traversal-order adaptation
(``repro.serve.adapt``): the engine seeds its initial order from the
hillclimb autotune cache (``--autotune-cache``) and then, every
``--adapt-epoch`` mixed steps, re-picks the order from the live modeled-LLC
gauges (hysteresis via ``--adapt-hysteresis`` / ``--adapt-confirm``).
Switches rebind the step's ``order_group`` operand — zero recompiles.

Resilience (DESIGN.md §12): ``--admission optimistic`` oversubscribes the
pool (mid-flight exhaustion is answered by victim preemption + chunked
re-prefill restore, bounded by ``--max-preemptions``), ``--max-queue``
load-sheds the newest arrived requests, ``--admit-watermark`` pauses
admission under pool pressure, and ``--deadline-s`` gives every synthetic
request a wall-clock deadline. Every request resolves with a typed
``status`` (ok/deadline/cancelled/shed/failed) instead of raising; the
launcher exits non-zero when any request ends ``failed``, and an error out
of the device step other than an injected ``StepFault`` propagates.

Tiered KV memory (DESIGN.md §13): ``--host-pages N`` backs the device pool
with an N-page host tier — at ``--spill-watermark`` occupancy the engine
spills the coldest slot (largest modeled reuse distance) to the host
instead of preempting it, and streams pages back ``--prefetch-depth`` per
step in the traversal's visit order, overlapped with in-flight steps.

Speculative decoding (DESIGN.md §14): ``--draft ngram`` turns on
self-drafting prompt-lookup speculation — every decode row plans up to
``--draft-len`` draft tokens into the same ragged mixed step as a
q_len=K+1 verification chunk; accepted tokens commit, rejected drafts
roll the row's KV length back (host-side, no new kernel, still exactly
two compiled step widths). ``--draft model`` uses a draft *model* with
its own paged cache instead (``--draft-model ARCH``; defaults to the
serving model itself — self-speculation). Output streams are bitwise
identical to ``--draft none`` for greedy and sampled decoding alike.
``--chaos-step-fail N`` injects one transient device-step failure at
mixed step N (the CI speculative chaos smoke: the step retries once and
the stream is unchanged).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.schedule import Order
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serve import FaultPlan, Request, ServeEngine, supports_continuous
from repro.train.checkpoint import latest_step, restore_pytree


def pick_scheduler(choice: str, cfg) -> str:
    if choice != "auto":
        return choice
    ok = supports_continuous(cfg)
    if not ok:
        print(
            f"scheduler=auto: {cfg.name} (family={cfg.family}, window={cfg.window}) "
            "does not support continuous batching; using static groups"
        )
    return "continuous" if ok else "static"


def init_params(lm, seed: int):
    """Random weights from ``seed``, built on the device under jit: no f32
    draw of a stacked leaf is ever materialised (eagerly, one deepseek-7b
    MLP leaf alone is a 5 GiB f32 draw plus its scaled copy)."""
    return jax.jit(lm.init)(jax.random.PRNGKey(seed))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--ckpt-dir", default=None, help="restore params from here")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-order", default="sawtooth",
                    choices=[o.value for o in Order] + ["auto"],
                    help="KV traversal order (core/schedule.py Traversal IR); "
                         "'auto' enables online adaptation: seed from the "
                         "autotune cache, then re-pick from the live "
                         "modeled-LLC gauges every --adapt-epoch steps")
    ap.add_argument("--snake-group", type=int, default=None,
                    help="block_snake reversal window in KV tiles")
    ap.add_argument("--adapt-epoch", type=int, default=8,
                    help="adaptation decision cadence in mixed steps "
                         "(--attn-order auto)")
    ap.add_argument("--adapt-hysteresis", type=float, default=0.05,
                    help="minimum fractional modeled-miss-byte improvement "
                         "before an order switch (--attn-order auto)")
    ap.add_argument("--adapt-confirm", type=int, default=2,
                    help="consecutive qualifying samples required before "
                         "switching (--attn-order auto)")
    ap.add_argument("--autotune-cache",
                    default="artifacts/hillclimb/autotune_cache.jsonl",
                    metavar="PATH",
                    help="hillclimb autotune-cache JSONL consulted at engine "
                         "start to seed the initial order (--attn-order auto; "
                         "missing file is fine)")
    ap.add_argument(
        "--scheduler", default="auto", choices=["auto", "static", "continuous"]
    )
    ap.add_argument("--page-size", type=int, default=None, help="KV page rows")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="tokens per ragged mixed step (decode rows + prefill "
                         "chunks; default: batch size + one chunk)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per prefill chunk (default: 4 pages)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the paged pool's content-hash prefix "
                         "sharing / copy-on-write page dedup")
    ap.add_argument("--admission", default="reserve",
                    choices=["reserve", "optimistic"],
                    help="pool admission discipline: 'reserve' guarantees "
                         "the worst case up front; 'optimistic' reserves "
                         "only prompts and answers mid-flight exhaustion "
                         "with victim preemption + chunked re-prefill")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound on the arrived waiting queue; newest "
                         "requests beyond it are load-shed (status=shed)")
    ap.add_argument("--admit-watermark", type=float, default=None,
                    help="pool-occupancy fraction at which admission "
                         "pauses (default 0.9 optimistic / 1.0 reserve)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline from engine "
                         "start; expired requests resolve status=deadline "
                         "with their partial tokens")
    ap.add_argument("--max-preemptions", type=int, default=2,
                    help="preemption bound per request before it resolves "
                         "status=failed")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="allocatable KV pool pages (default: every slot's "
                         "worst case; smaller = oversubscribed pool)")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-offload page tier capacity in pages "
                         "(DESIGN.md §13); enables the TieredPagePool so "
                         "cold slots spill to host instead of being "
                         "preempted (default: tiering off)")
    ap.add_argument("--spill-watermark", type=float, default=None,
                    help="device-pool occupancy fraction at which the "
                         "coldest slot (largest modeled reuse distance) "
                         "spills to the host tier (default: "
                         "min(0.85, admit watermark); needs --host-pages)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="host pages staged back per step boundary while a "
                         "spilled slot resumes, in the next step's "
                         "traversal visit order (needs --host-pages)")
    ap.add_argument("--draft", default="none",
                    choices=["none", "ngram", "model"],
                    help="speculative decoding drafter (DESIGN.md §14): "
                         "'ngram' self-drafts via prompt lookup; 'model' "
                         "runs a draft model with its own paged cache "
                         "(continuous scheduler only)")
    ap.add_argument("--draft-len", type=int, default=4, metavar="K",
                    help="draft tokens planned per decode row per step "
                         "(verified as one q_len=K+1 ragged chunk; clamped "
                         "to the prefill chunk and the token budget)")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    help="arch for --draft model (reduced like the target; "
                         "default: the serving model itself — "
                         "self-speculation)")
    ap.add_argument("--chaos-step-fail", type=int, default=0, metavar="N",
                    help="inject one transient device-step failure at mixed "
                         "step N (retried once; the CI speculative chaos "
                         "smoke)")
    ap.add_argument("--chaos-fetch-fail", type=int, default=0, metavar="N",
                    help="inject N tier.fetch faults (dropped host->device "
                         "transfers; the prefetcher requeues and retries) — "
                         "the CI tiering chaos smoke (needs --host-pages)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the obs metrics registry as JSONL here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the span trace as Chrome-trace JSON here")
    ap.add_argument("--llc-every", type=int, default=8,
                    help="sample modeled-LLC gauges every N mixed steps "
                         "(continuous path; 0 disables)")
    ap.add_argument("--llc-capacity-mib", type=float, default=None,
                    help="modeled LLC capacity for the llc.* gauges (MiB; "
                         "default matches hillclimb --sweep-orders)")
    ap.add_argument("--log-every", type=int, default=0, metavar="STEPS",
                    help="print a one-line stats summary every N mixed steps")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.attn_order == "block_snake" and args.snake_group is None:
        valid = ", ".join(repr(o.value) for o in Order) + ", 'auto'"
        ap.error(
            f"traversal order 'block_snake' needs --snake-group (the reversal "
            f"window in KV tiles); valid orders are: {valid}"
        )
    adapt = args.attn_order == "auto"

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not adapt:
        # 'auto' keeps the arch's configured order as the pre-seed starting
        # point; the controller re-seeds/re-picks it from there.
        cfg = cfg.with_(attn_order=args.attn_order)
    cfg = cfg.with_(snake_group=args.snake_group)
    lm = build_model(cfg)
    params = init_params(lm, 0)
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, step = restore_pytree({"params": params}, args.ckpt_dir)
        params = state["params"]
        print(f"restored params from step {step}")

    drafter = None
    if args.draft != "none":
        from repro.serve import make_drafter

        draft_lm, draft_params = lm, params
        if args.draft == "model" and args.draft_model:
            draft_cfg = get_config(args.draft_model)
            if args.reduced:
                draft_cfg = draft_cfg.reduced()
            draft_lm = build_model(draft_cfg)
            draft_params = init_params(draft_lm, 1)
        drafter = make_drafter(
            args.draft,
            lm=draft_lm,
            params=draft_params,
            n_slots=args.batch_size,
            max_len=args.max_len,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
        )

    faults = None
    if args.chaos_fetch_fail > 0 or args.chaos_step_fail > 0:
        faults = FaultPlan()
        if args.chaos_fetch_fail > 0:
            faults.fetch_fail(0, times=args.chaos_fetch_fail)
        if args.chaos_step_fail > 0:
            faults.fail_device_step(args.chaos_step_fail)

    eng = ServeEngine(
        lm,
        params,
        batch_size=args.batch_size,
        max_len=args.max_len,
        scheduler=pick_scheduler(args.scheduler, cfg),
        page_size=args.page_size,
        token_budget=args.token_budget,
        prefill_chunk=args.prefill_chunk,
        prefix_sharing=not args.no_prefix_sharing,
        llc_every=args.llc_every,
        llc_capacity_bytes=(
            args.llc_capacity_mib * 2**20 if args.llc_capacity_mib else None
        ),
        log_every_steps=args.log_every,
        adapt_order=adapt,
        adapt_epoch=args.adapt_epoch,
        adapt_hysteresis=args.adapt_hysteresis,
        adapt_confirm=args.adapt_confirm,
        autotune_cache=args.autotune_cache,
        admission=args.admission,
        max_queue=args.max_queue,
        admit_watermark=args.admit_watermark,
        max_preemptions=args.max_preemptions,
        pool_pages=args.pool_pages,
        host_pages=args.host_pages,
        spill_watermark=args.spill_watermark,
        prefetch_depth=args.prefetch_depth,
        drafter=drafter,
        draft_len=args.draft_len,
        faults=faults,
    )
    if adapt and eng.order_ctl is not None:
        src = eng.order_ctl.seeded_from
        seeded = "seeded from autotune cache" if src else "no autotune-cache hit"
        print(
            f"order adaptation on: starting order={eng.order_ctl.order.value} "
            f"({seeded}), epoch={args.adapt_epoch}, "
            f"hysteresis={args.adapt_hysteresis}, confirm={args.adapt_confirm}"
        )
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            tokens=rng.integers(2, cfg.vocab, size=rng.integers(4, 32)).astype(np.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature,
            rid=i,
            deadline_s=args.deadline_s,
        )
        for i in range(args.requests)
    ]
    t0 = time.time()
    results = eng.generate(reqs)
    dt = time.time() - t0
    ok = [r for r in results if r.status == "ok"]
    tok = sum(r.steps for r in results)
    print(f"served {len(results)} requests, {tok} tokens in {dt:.2f}s ({tok/dt:.1f} tok/s)")
    if len(ok) < len(results):
        by = {}
        for r in results:
            by[r.status] = by.get(r.status, 0) + 1
        print("  statuses: " + ", ".join(f"{k}={v}" for k, v in sorted(by.items())))
    stats = eng.last_stats
    if stats is not None:
        print(
            f"  {stats.mixed_steps} mixed steps ({stats.wide_steps} wide), "
            f"{stats.pages_adopted} prefix pages adopted "
            f"({stats.prompt_tokens_adopted} tokens), "
            f"{stats.cow_forks} CoW forks"
        )
        if stats.preemptions or stats.shed or stats.deadline_miss or stats.failed:
            print(
                f"  resilience: {stats.preemptions} preemptions "
                f"({stats.restore_tokens} tokens re-prefilled), "
                f"{stats.shed} shed, {stats.deadline_miss} deadline, "
                f"{stats.cancelled} cancelled, {stats.failed} failed"
            )
        if stats.draft_tokens:
            print(
                f"  speculative: {stats.draft_tokens} drafted, "
                f"{stats.accepted_tokens} accepted "
                f"({stats.acceptance_rate:.0%}), "
                f"{stats.rollback_tokens} rolled back"
            )
        if stats.spills or stats.tier_fetches:
            hit_rate = stats.prefetch_hits / max(stats.tier_fetches, 1)
            print(
                f"  tiering: {stats.spills} spills, {stats.tier_fetches} "
                f"fetches (hit rate {hit_rate:.0%}, "
                f"{stats.prefetch_wasted} wasted)"
            )
    for r in results[:4]:
        print(f"  rid={r.rid} -> {r.tokens.tolist()}")

    if args.metrics_out:
        from repro.obs import write_metrics_jsonl

        n = write_metrics_jsonl(
            eng.obs, args.metrics_out, extra={"arch": args.arch}
        )
        print(f"wrote {n} metric series -> {args.metrics_out}")
    if args.trace_out:
        eng.tracer.write(args.trace_out)
        print(
            f"wrote {len(eng.tracer.events())} trace events -> {args.trace_out} "
            "(open in chrome://tracing or ui.perfetto.dev)"
        )
    n_failed = sum(r.status == "failed" for r in results)
    if n_failed:
        raise SystemExit(f"{n_failed} of {len(results)} requests failed")


if __name__ == "__main__":
    main()
