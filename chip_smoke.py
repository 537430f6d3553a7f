"""Smoke check that the main path runs on a TPU: `python chip_smoke.py`.

Default (one chip): serves deepseek-7b at its published widths with random
bf16 weights seeded from ``--seed``, through the path ``repro.launch.serve``
uses — jitted init, ``ServeEngine(scheduler="continuous")``, the paged KV
pool, greedy sampling, the ragged Pallas paged-attention kernel. 4 slots,
``max_len`` 512, 128-row pages; 8 requests of 64–384 prompt tokens and 32
new tokens each. It fails unless every request ends ``ok``, the compiled
mixed step holds the Pallas kernel (``tpu_custom_call``), and the engine's
mixed step with all 4 slots live — the first wide step, then one width-1
decode step — gives logits that match an engine built with the ``xla``
attention within a bf16 tolerance.

``--four-chips``: deepseek-7b training at full width through
``run_training`` on a ``4x1`` mesh (FSDP over ``data``), factored AdamW,
global batch 4 of 2048 tokens, 3 steps with the Pallas kernels and a
timed final checkpoint outside the checkout. Then the step-0 loss and
gradients with the Pallas and the ``xla`` attention on the same mesh,
parameters and batch, cut to 20 layers so the ``xla`` step fits a chip:
loss, grad norm and each attention weight's gradient must agree within
bf16 tolerances. Parameter bytes per device are printed.

Every figure printed is a smoke figure, not a benchmark result. The last
line of standard output is ``{"ok": true, "device": {...}}`` on success;
on any failure the script exits non-zero and prints no such line. One
process drives the chip; nothing here spawns another.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

GiB = 2**30


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / GiB:.3f} GiB"


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


# Pallas vs xla logits, relative L2 per row. bf16 keeps 8 significant bits,
# and the two impls round at different points of each of the 30 layers (the
# kernel rounds its softmax weights to bf16 before the PV product, the xla
# path does not); with random weights those errors add to a few percent.
# Readings on a TPU v5e (seed 0, 4 rows of 169-379 tokens): 0.0174-0.0182
# for every row of the wide and the decode step. Planted faults in the paged
# kernel's operands: kv heads rolled by one 1.40-1.42, cache length short
# by one 0.58-0.87. The limit sits between the two.
LOGITS_TOL = 5e-2


def logits_agree(got, ref, what: str) -> list[str]:
    """bf16 comparison of two logit arrays; returns failure messages."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    rel = rel_l2(got, ref)
    say(
        f"{what}: rel L2 diff {rel:.4g} (tol {LOGITS_TOL}: bf16 roundings at "
        f"different points of every layer add up to a few percent), "
        f"max|diff| {float(np.abs(got - ref).max()):.4g} of max|ref| "
        f"{float(np.abs(ref).max()):.4g}, argmax agreement "
        f"{float(np.mean(got.argmax(-1) == ref.argmax(-1))):.4f}"
    )
    bad = []
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        bad.append(f"{what}: non-finite logits")
    if rel > LOGITS_TOL:
        bad.append(f"{what}: pallas and xla logits disagree beyond tolerance")
    return bad


# --------------------------------------------------------------------------
# one chip: serving
# --------------------------------------------------------------------------


def engine_step_logits(eng, params, prompts, next_tokens=None):
    """Logits of ``eng``'s mixed step on the engine's own paged pool: the
    first wide step, with every slot prefilling one of ``prompts``, then
    the width-1 decode step after it, each row fed ``next_tokens`` (by
    default its own greedy token). Returns (per-row wide logits at the
    valid positions, decode logits (B, V), the tokens fed)."""
    from repro.serve.kv_pool import PagedKVPool

    slots, chunk = len(prompts), eng._chunk
    cfg = eng.lm.cfg
    pool = PagedKVPool(cfg, cfg.n_layers, slots, eng._cap, prefix_sharing=False)
    # Donated pages, as in the served step: the old and new pool never
    # both live on a 16 GiB chip holding 12.9 GiB of weights.
    fn = jax.jit(eng.step_logits, donate_argnums=(2,))
    group = np.int32(eng.order_ctl.effective_group(pool.blocks_per_seq))

    def run(tokens, qlens):
        logits, pages = fn(params, tokens, pool.pages, pool.block_tables.copy(),
                           pool.lens.copy(), qlens, group)
        pool.update_pages(pages)
        for i, n in enumerate(qlens):
            pool.advance(i, int(n))
        return logits

    tokens = np.full((slots, chunk), eng.eos, np.int32)
    qlens = np.array([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        pool.admit(i, p, 1)
        pool.ensure_writable(i, len(p))
        tokens[i, : len(p)] = p
    logits = run(tokens, qlens)
    wide = [np.asarray(logits[i, :n]).astype(np.float32)
            for i, n in enumerate(qlens)]
    del logits
    if next_tokens is None:
        next_tokens = np.array([w[-1].argmax() for w in wide], np.int32)
    for i in range(slots):
        pool.ensure_writable(i, 1)
    logits = run(next_tokens[:, None], np.ones((slots,), np.int32))
    decode = np.asarray(logits[:, 0]).astype(np.float32)
    return wide, decode, next_tokens


def check_step_logits(cfg, params, prompts, *, max_len, page) -> list[str]:
    """The engine's mixed step with every slot live, Pallas vs an engine
    built with ``attn_impl="xla"``: the first wide step's logits of each
    row, and the width-1 decode step that follows."""
    from repro.models import build_model
    from repro.serve import ServeEngine

    def engine(impl):
        return ServeEngine(build_model(cfg.with_(attn_impl=impl)), params,
                           batch_size=len(prompts), max_len=max_len,
                           scheduler="continuous", page_size=page)

    wide_p, dec_p, fed = engine_step_logits(engine("pallas"), params, prompts)
    wide_x, dec_x, _ = engine_step_logits(engine("xla"), params, prompts, fed)
    fails = []
    for i, (got, ref) in enumerate(zip(wide_p, wide_x)):
        fails += logits_agree(got, ref, f"first wide step, row {i} "
                              f"({len(got)} prompt tokens x {got.shape[-1]} vocab)")
    fails += logits_agree(dec_p, dec_x, f"width-1 decode step ({len(prompts)} rows)")
    return fails


def serve_phase(cfg, *, seed: int, slots=4, max_len=512, page=128, n_req=8,
                prompt_range=(64, 384), max_new=32):
    from repro.launch.serve import init_params
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    fails: list[str] = []
    lm = build_model(cfg)
    t = time.perf_counter()
    params = jax.block_until_ready(init_params(lm, seed))
    say(f"weights {tree_bytes(params) / GiB:.3f} GiB "
        f"({sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f} B params), "
        f"jitted init {time.perf_counter() - t:.1f} s incl. compile")

    eng = ServeEngine(lm, params, batch_size=slots, max_len=max_len,
                      scheduler="continuous", page_size=page)
    rng = np.random.default_rng(seed)

    def request(rid, n_prompt, n_new):
        toks = rng.integers(2, cfg.vocab, size=n_prompt).astype(np.int32)
        return Request(tokens=toks, max_new_tokens=n_new, rid=rid)

    # Warm-up: one short request compiles both step widths (chunk, 1).
    t = time.perf_counter()
    warm = eng.generate([request(-1, prompt_range[0], 2)])
    compile_s = time.perf_counter() - t
    say(f"compile {compile_s:.1f} s (warm-up request: both mixed-step widths), "
        f"{eng.compiled_step_count()} compiled widths")
    if warm[0].status != "ok":
        fails.append(f"warm-up request ended {warm[0].status}")

    reqs = [request(i, int(rng.integers(*prompt_range, endpoint=True)), max_new)
            for i in range(n_req)]
    t = time.perf_counter()
    results = eng.generate(reqs)
    wall = time.perf_counter() - t
    n_tok = sum(r.steps for r in results)
    statuses = [r.status for r in results]
    say(f"served {len(results)} requests, {n_tok} tokens in {wall:.2f} s wall "
        f"(prompts {[len(r.tokens) for r in reqs]}), statuses {statuses}, "
        f"{eng.last_stats.mixed_steps} mixed steps "
        f"({eng.last_stats.wide_steps} wide)")
    fails += [f"request {r.rid} ended {r.status}" for r in results
              if r.status != "ok"]
    pool = eng.last_pool
    say(f"pool {tree_bytes(pool.pages) / GiB:.3f} GiB "
        f"({pool.alloc.n_pages} pages x {pool.page} rows), "
        f"peak bytes in use {peak_bytes()}")

    # The compiled wide step: the Pallas kernel must be in it.
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    chunk = eng._chunk
    lowered = eng._mixed_step_fn().lower(
        params, i32(slots, chunk), pool.pages, i32(*pool.block_tables.shape),
        i32(slots), i32(slots), i32(), jax.ShapeDtypeStruct((slots,), jnp.float32),
        i32(slots), i32(slots),
    )
    t = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    say(f"wide mixed step (width {chunk}): AOT compile {time.perf_counter() - t:.1f} s, "
        f"tpu_custom_call {has_kernel}, args {m.argument_size_in_bytes / GiB:.3f} "
        f"temp {m.temp_size_in_bytes / GiB:.3f} aliased "
        f"{m.alias_size_in_bytes / GiB:.3f} GiB")
    if not has_kernel:
        fails.append("compiled mixed step has no tpu_custom_call")
    del compiled, lowered, eng, pool
    gc.collect()

    fails += check_step_logits(cfg, params, [r.tokens for r in reqs[:slots]],
                               max_len=max_len, page=page)
    say(f"peak bytes in use after the logits check {peak_bytes()}")
    return fails


# --------------------------------------------------------------------------
# four chips: sharded training
# --------------------------------------------------------------------------

ATTN_LEAVES = ("wq", "wk", "wv", "wo")


def step0_grads(cfg, pcfg, mesh, batch, seed: int) -> dict:
    """Step-0 loss and gradients of the training step's loss for the
    Pallas and the ``xla`` attention, from the same parameters (the train
    state's ``lm.init(PRNGKey(seed))``) on ``mesh``: the loss, the global
    grad norm the optimizer reads, and the relative L2 gap of each
    attention weight's gradient (a wrong dQ, dK or dV moves those first)."""
    from repro.dist import sharding as shd
    from repro.models import build_model
    from repro.train.optimizer import global_norm

    key = jax.random.PRNGKey(seed)
    lm = build_model(cfg)
    p_sh = shd.param_shardings(jax.eval_shape(lm.init, key), pcfg, mesh)
    b_sh = shd.batch_shardings(batch, pcfg, mesh)
    with jax.set_mesh(mesh):
        params = jax.jit(lm.init, out_shardings=p_sh)(key)
        out, grads = {}, {}
        for impl in ("pallas", "xla"):
            lm_ = build_model(cfg.with_(attn_impl=impl))
            vg = jax.jit(jax.value_and_grad(lambda p, b: lm_.loss(p, b)[0]),
                         in_shardings=(p_sh, b_sh), out_shardings=(None, p_sh))
            loss, grads[impl] = vg(params, batch)
            out[impl] = (float(loss), float(global_norm(grads[impl])))
        del params

        @jax.jit
        def leaf_gaps(gp, gx):
            def gap(a, b):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                return jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)
            return {name: gap(gp["layers"]["attn"][name]["w"],
                              gx["layers"]["attn"][name]["w"])
                    for name in ATTN_LEAVES}

        gaps = {k: float(v) for k, v in leaf_gaps(grads["pallas"], grads["xla"]).items()}
    return {"loss": out, "attn_grad_gap": gaps}


# Step-0 limits, Pallas vs xla at 20 layers, relative. Readings on four TPU
# v5e chips (seed 0): loss 8.6e-6, grad norm 8.2e-6, attention gradient
# gaps 0.029-0.041. Planted faults in the Pallas backward's outputs: dK of
# the neighbouring kv head — grad norm 1.3e-3, largest gap 1.41; dV of the
# last kv tile zeroed — 1.9e-2, 0.11; dQ scaled by 1.05 — 3.5e-3, 0.065.
# GRAD_NORM_TOL and GRAD_TOL sit between the clean and the planted readings.
# A backward fault leaves the loss as it was; LOSS_TOL is ten times the
# clean reading, and no forward fault was read at this size.
LOSS_TOL = 1e-4
GRAD_NORM_TOL = 1e-4
GRAD_TOL = 5e-2


def compare_step0(cfg, pcfg, mesh, batch, seed: int) -> list[str]:
    r = step0_grads(cfg, pcfg, mesh, batch, seed)
    (loss_p, gn_p), (loss_x, gn_x) = r["loss"]["pallas"], r["loss"]["xla"]
    d_loss = abs(loss_p - loss_x) / abs(loss_x)
    d_gn = abs(gn_p - gn_x) / abs(gn_x)
    gaps = r["attn_grad_gap"]
    say(f"step-0 pallas vs xla ({cfg.n_layers} layers): loss {loss_p:.6g} vs "
        f"{loss_x:.6g} (rel {d_loss:.3g}, tol {LOSS_TOL}), grad norm {gn_p:.6g} "
        f"vs {gn_x:.6g} (rel {d_gn:.3g}, tol {GRAD_NORM_TOL}), attention "
        f"gradient rel L2 gaps "
        + ", ".join(f"{k} {v:.3g}" for k, v in gaps.items())
        + f" (tol {GRAD_TOL})")
    fails = []
    if not all(np.isfinite([loss_p, loss_x, gn_p, gn_x, *gaps.values()])):
        fails.append("non-finite step-0 loss or gradient")
    if d_loss > LOSS_TOL or d_gn > GRAD_NORM_TOL:
        fails.append("pallas and xla step-0 loss / grad norm disagree")
    if max(gaps.values()) > GRAD_TOL:
        fails.append("pallas and xla step-0 attention gradients disagree")
    return fails


def train_phase(cfg, *, seed: int, batch=4, seq=2048, steps=3, compare_layers=20):
    from repro.configs import ParallelConfig, TrainConfig
    from repro.data.pipeline import DataConfig, SyntheticPacked
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.obs import Registry, Tracer
    from repro.train.loop import run_training
    from repro.train.step import init_train_state

    fails: list[str] = []
    n_dev = len(jax.devices())
    mesh = make_local_mesh(n_dev, 1)
    pcfg = ParallelConfig(fsdp_axes=("data",), data_axes=("data",))
    data = SyntheticPacked(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                      global_batch=batch, seed=seed))
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")  # outside the checkout
    tcfg = TrainConfig(optimizer="adamw_factored", total_steps=steps,
                       warmup_steps=1, checkpoint_every=0,
                       checkpoint_dir=ckpt_dir, keep_checkpoints=1, seed=seed)
    lm = build_model(cfg.with_(attn_impl="pallas"))

    with jax.set_mesh(mesh):
        t = time.perf_counter()
        state = jax.block_until_ready(
            init_train_state(lm, tcfg, pcfg, mesh, jax.random.PRNGKey(seed)))
        init_s = time.perf_counter() - t
    per_dev: dict = {}
    for leaf in jax.tree.leaves(state["params"]):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    total = tree_bytes(state["params"])
    say(f"sharded init {init_s:.1f} s incl. compile; parameter bytes per device "
        + ", ".join(f"dev{d}: {b / GiB:.3f} GiB ({b / total:.3f})"
                    for d, b in sorted(per_dev.items()))
        + f" of {total / GiB:.3f} GiB; train state {tree_bytes(state) / GiB:.3f} GiB")
    if len(per_dev) != n_dev or max(abs(b / total - 1 / n_dev)
                                    for b in per_dev.values()) > 0.02:
        fails.append("parameters are not split about evenly across devices")
    del state
    gc.collect()

    reg, tr = Registry(), Tracer()
    t = time.perf_counter()
    try:
        res = run_training(lm, tcfg, pcfg, mesh, steps=steps,
                           make_batch=data.batch, registry=reg, tracer=tr,
                           log_every=0)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    wall = time.perf_counter() - t
    steps_s = [e.dur_ns / 1e9 for e in tr.events() if e.name == "train.step"]
    save_s = [e.dur_ns / 1e9 for e in tr.events() if e.name == "train.checkpoint"]
    say(f"pallas, {cfg.n_layers} layers: {steps} steps, losses {res.losses}, "
        f"step times (the first includes compile) {[round(s, 3) for s in steps_s]}"
        f" s, final checkpoint save {save_s[-1]:.1f} s, run wall {wall:.1f} s,"
        f" peak bytes in use (dev 0) {peak_bytes()}")
    if not np.isfinite(res.losses).all():
        fails.append("non-finite training loss")

    # The xla attention's training step needs 16.0 GiB per chip at 30
    # layers (compiled for v5e:2x2), over a v5e's 15.75 GiB; the
    # comparison runs both impls at the same width, fewer layers.
    cut = cfg.with_(n_layers=min(compare_layers, cfg.n_layers))
    fails += compare_step0(cut, pcfg, mesh, data.batch(0), seed)
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    want = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    limit = (devs[0].memory_stats() or {}).get("bytes_limit")
    say(f"device kind {devs[0].device_kind}, count {len(devs)}, memory limit "
        f"{'not reported' if limit is None else f'{limit / GiB:.3f} GiB'}, "
        f"compile cache {use_compile_cache()}; all figures are smoke "
        f"figures, not benchmark results")
    cfg = get_config("deepseek-7b")
    if args.four_chips:
        fails = train_phase(cfg, seed=args.seed)
    else:
        fails = serve_phase(cfg, seed=args.seed)
    if fails:
        for f in fails:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
