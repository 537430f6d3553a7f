"""Fault-tolerant training loop: checkpoint/resume, watchdog, injection.

The loop is deliberately plain: a production job wraps exactly this shape —
build step -> restore-or-init -> iterate(data) with watchdog ->
checkpoint cadence -> on failure: resume from latest (same or smaller mesh).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.data.pipeline import DataConfig, make_batch_iterator
from repro.models.model import LM
from repro.obs import Registry, Tracer
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import FailureInjector, StepTimeout, Watchdog
from repro.train.step import init_train_state, make_train_step, state_shardings

log = logging.getLogger(__name__)

__all__ = ["TrainResult", "run_training"]


@dataclasses.dataclass
class TrainResult:
    final_step: int
    losses: list
    resumed_from: Optional[int]
    registry: Optional[Registry] = None   # step metrics (repro.obs)
    tracer: Optional[Tracer] = None       # step/checkpoint spans


def _batch_tokens(batch) -> int:
    """Token count of one batch (throughput accounting): the ``tokens``
    leaf when present, else the largest integer leaf's element count."""
    if isinstance(batch, dict):
        if "tokens" in batch:
            return int(np.prod(np.shape(batch["tokens"])))
        sizes = [
            int(np.prod(np.shape(v)))
            for v in batch.values()
            if np.issubdtype(np.asarray(v).dtype, np.integer)
        ]
        return max(sizes, default=0)
    return 0


def run_training(
    lm: LM,
    tcfg: TrainConfig,
    pcfg: ParallelConfig,
    mesh,
    *,
    steps: Optional[int] = None,
    data_cfg: Optional[DataConfig] = None,
    injector: Optional[FailureInjector] = None,
    step_timeout_s: float = 0.0,
    log_every: int = 10,
    make_batch: Optional[Callable[[int], dict]] = None,
    registry: Optional[Registry] = None,
    tracer: Optional[Tracer] = None,
) -> TrainResult:
    steps = steps or tcfg.total_steps
    ckpt = CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)

    # Telemetry (repro.obs): per-step time/loss/grad-norm metrics and
    # step/checkpoint spans. Defaults to private instances returned on the
    # TrainResult; recording is in-process only (export is the caller's
    # sink decision, e.g. launch/train --metrics-out).
    obs = registry if registry is not None else Registry()
    tr = tracer if tracer is not None else Tracer()
    m_steps = obs.counter("train.steps")
    m_tokens = obs.counter("train.tokens")
    m_retries = obs.counter("train.steps", event="watchdog_retry")
    m_step_time = obs.histogram("train.step_time_s")
    g_loss = obs.gauge("train.loss")
    g_gnorm = obs.gauge("train.grad_norm")
    g_lr = obs.gauge("train.lr")
    g_tput = obs.gauge("train.throughput_tokens_per_s")

    with jax.set_mesh(mesh):
        state = init_train_state(
            lm, tcfg, pcfg, mesh, jax.random.PRNGKey(tcfg.seed)
        )
        resumed_from = None
        if ckpt.latest_step() is not None:
            with tr.span("train.restore"):
                state, resumed = ckpt.restore_latest(
                    state, shardings=state_shardings(state, pcfg, mesh)
                )
            resumed_from = resumed
            log.info("resumed from step %d", resumed)
        start = resumed_from + 1 if resumed_from is not None else 0

        if make_batch is None:
            assert data_cfg is not None
            src = make_batch_iterator(data_cfg, start_step=start)
            batch_fn = lambda step: next(iter(src))
        else:
            batch_fn = make_batch

        step_fn, compile_step = make_train_step(lm, tcfg, pcfg, mesh)
        batch0 = batch_fn(start)
        with tr.span("train.compile"):
            compiled = compile_step(state, batch0)

        def save_final(i: int) -> None:
            with tr.span("train.checkpoint", step=max(i - 1, 0), final=True):
                ckpt.save(state, max(i - 1, 0), blocking=True)

        losses = []
        t0 = time.time()
        i = start
        while i < steps:
            batch = batch_fn(i) if i != start else batch0
            t_step = time.perf_counter()
            try:
                if injector is not None:
                    injector.maybe_fail(i)
                # The span closes after float(loss) blocks, so it covers
                # real device step time, not the async dispatch.
                with tr.span("train.step", step=i):
                    if step_timeout_s > 0:
                        with Watchdog(step_timeout_s):
                            state, metrics = compiled(state, batch)
                            loss = float(metrics["loss"])  # blocks inside watchdog
                    else:
                        state, metrics = compiled(state, batch)
                        loss = float(metrics["loss"])
            except StepTimeout:
                log.warning("step %d hit watchdog; re-running batch", i)
                tr.instant("train.watchdog_retry", step=i)
                m_retries.inc()
                continue  # straggler mitigation: redo the step
            except RuntimeError as e:
                # Checkpoint the last completed step, then fail the run:
                # the caller (and the launcher's exit code) must see it.
                # The step donates ``state``: after an error raised at or
                # past the dispatch its buffers may be gone, so the save is
                # best effort and never replaces the step's own error.
                log.error("step %d failed: %s — checkpoint + re-raise", i, e)
                tr.instant("train.failure", step=i)
                try:
                    save_final(i)
                except Exception:
                    log.exception("checkpoint after the step %d failure failed", i)
                raise
            dt_step = time.perf_counter() - t_step
            n_tok = _batch_tokens(batch)
            m_steps.inc()
            m_tokens.inc(n_tok)
            m_step_time.observe(dt_step)
            g_loss.set(loss)
            if "grad_norm" in metrics:
                g_gnorm.set(float(metrics["grad_norm"]))
            if "lr" in metrics:
                g_lr.set(float(metrics["lr"]))
            if dt_step > 0 and n_tok:
                g_tput.set(n_tok / dt_step)
            losses.append(loss)
            if not np.isfinite(loss):
                raise FloatingPointError(f"non-finite loss at step {i}: {loss}")
            if log_every and i % log_every == 0:
                dt = time.time() - t0
                log.info("step %d loss %.4f (%.2fs elapsed)", i, loss, dt)
            if tcfg.checkpoint_every and (i + 1) % tcfg.checkpoint_every == 0:
                with tr.span("train.checkpoint", step=i):
                    ckpt.save(state, i)
            i += 1

        save_final(i)
        return TrainResult(
            final_step=i - 1,
            losses=losses,
            resumed_from=resumed_from,
            registry=obs,
            tracer=tr,
        )
