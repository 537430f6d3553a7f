"""Batched serving engine: prefill + decode over the unified LM API.

Two schedulers (``repro.serve.scheduler``):

* ``scheduler="static"`` — the original fixed-group path: requests are
  grouped into ``batch_size`` batches (left-padded into one shared prefill
  bucket), prefilled once, decoded token-by-token until every row hits its
  own EOS / ``max_new_tokens``. Works for every model family (KV caches,
  SWA ring buffers and SSM states all live behind ``lm.prefill /
  decode_step``).

* ``scheduler="continuous"`` — continuous batching over a shared paged KV
  pool (``repro.serve.kv_pool``) driven by ONE compiled **ragged mixed
  step**: each step, every decoding slot contributes a q_len=1 row and the
  remaining token budget is dealt to prompts as prefill chunks (per-row
  ``q_start``/``q_len``, causal masking inside the chunk, sampling only on
  rows that completed their prompt). Long prompts are chunk-preempted
  instead of stalling decode; the whole path compiles exactly two step
  shapes (chunk width and decode width 1) no matter how many distinct
  prompt lengths arrive. Identical prompt prefixes are deduplicated in the
  pool: full prompt pages are content-hashed, admission *adopts* matching
  pages (refcount bump, zero prefill compute) and copy-on-write forks the
  tail page when a shared page must be written. Pages are visited in the
  paper's ``KVSchedule`` order (sawtooth parity keyed per row on the
  visited length). Requires a token-only full-attention family (dense/moe).

Sampling is per-row in both paths: each request is sampled with its own
temperature and a PRNG stream folded from (engine seed, request seed —
defaulting to the submission index so identical requests decorrelate —
per-request sample index). A greedy request batched next to a sampling
request stays greedy, and a request's sampled stream does not depend on
which slot or group it landed in.

On TPU the mixed step uses the ragged Pallas paged-attention kernel with
the schedule from the paper's technique; on CPU it uses the blockwise XLA
path.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.cache_sim import slot_reuse_stats
from repro.core.schedule import future_visit_window
from repro.dist import sharding as shd
from repro.models.model import LM, build_model
from repro.obs import LLCSampler, Registry, Tracer
from repro.obs.llc import DEFAULT_CAPACITY_BYTES
from repro.serve.adapt import OrderAdaptController
from repro.serve.faults import FaultPlan, StepFault
from repro.serve.kv_pool import (
    AdmissionError,
    PagedKVPool,
    PoolExhausted,
    assemble_cache_view,
)
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.tiering import TieredPagePool, select_spill_victim

__all__ = [
    "Request",
    "GenerationResult",
    "StepStats",
    "ServeEngine",
    "CONTINUOUS_FAMILIES",
    "REQUEST_STATUSES",
    "supports_continuous",
    "select_victim",
]

EOS = 1  # legacy default, kept for callers that import it; engines use cfg.eos_id

CONTINUOUS_FAMILIES = ("dense", "moe")


def supports_continuous(cfg: ModelConfig) -> bool:
    """Whether ``cfg`` can serve under the continuous scheduler: a
    token-only full-attention family (the paged pool has no ring-buffer or
    recurrent-state layout). The single eligibility predicate — launchers
    and examples picking a scheduler automatically must use this."""
    return cfg.family in CONTINUOUS_FAMILIES and cfg.window is None


REQUEST_STATUSES = ("ok", "deadline", "cancelled", "shed", "failed")


@dataclasses.dataclass
class Request:
    tokens: np.ndarray            # prompt (1D int32)
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 = greedy
    rid: int = 0
    seed: Optional[int] = None    # sampling stream id; defaults to the
                                  # request's submission index so identical
                                  # requests sample independently
    eos_id: Optional[int] = None  # overrides ModelConfig.eos_id
    arrival: int = 0              # step arrival time (continuous only)
    deadline_s: Optional[float] = None
                                  # wall-clock budget from engine start;
                                  # checked at step boundaries — an expired
                                  # request resolves with status="deadline"
                                  # and whatever tokens it has
    priority: int = 0             # preemption shield: LOWER is preempted
                                  # first (admission order stays FIFO)
    max_preemptions: Optional[int] = None
                                  # per-request override of the engine's
                                  # preemption bound before status="failed"


@dataclasses.dataclass
class GenerationResult:
    rid: int
    tokens: np.ndarray            # generated tokens (without prompt)
    steps: int
    ttft_s: float = 0.0           # wall time, engine start -> first token
    tpot_s: float = 0.0           # mean wall time per token after the first;
                                  # NaN when <= 1 token was generated (there
                                  # is no "per token after the first" then)
    status: str = "ok"            # one of REQUEST_STATUSES; every non-"ok"
                                  # status still carries the partial tokens
                                  # generated before the request was retired
    n_preemptions: int = 0        # times this request was preempted+restored


def select_victim(candidates) -> int:
    """Preemption victim policy (DESIGN.md §12): pick from ``candidates``
    — tuples ``(slot, priority, n_generated, shared_donor)`` — the slot
    with the lowest priority, preferring non-donors (releasing a shared
    donor frees fewer pages than it holds), then the fewest generated
    tokens (cheapest chunked re-prefill on restore), slot index as the
    deterministic tiebreak."""
    return min(candidates, key=lambda c: (c[1], bool(c[3]), c[2], c[0]))[0]


def _tpot(elapsed_after_first: float, n_tok: int) -> float:
    """Mean time per output token after the first; NaN for n_tok <= 1 — a
    single-token generation has no inter-token interval, and reporting
    ``elapsed/1`` instead put a meaningless wall-clock sample into the TPOT
    percentiles. Histograms drop NaN observations by construction."""
    return (elapsed_after_first / (n_tok - 1)) if n_tok > 1 else math.nan


@dataclasses.dataclass
class StepStats:
    """Deterministic per-stream work counters for the continuous path.

    Typed replacement for the old ``ServeEngine.last_stats`` ad-hoc dict;
    every field is also published as a registry counter (``serve.steps``,
    ``pool.pages_adopted``, ...). The mapping shim below keeps
    ``stats["wide_steps"]``-style callers working (with a
    DeprecationWarning) — prefer attribute access or the registry.
    """

    mixed_steps: int = 0          # ragged mixed steps dispatched
    wide_steps: int = 0           # steps at chunk width (any prefill row)
    pages_adopted: int = 0        # prefix pages adopted instead of computed
    prompt_tokens_adopted: int = 0
    cow_forks: int = 0
    preemptions: int = 0          # victim slots evicted under pool pressure
    restore_tokens: int = 0       # tokens re-prefilled by preempt restores
    shed: int = 0                 # requests load-shed past --max-queue
    deadline_miss: int = 0        # requests retired on an expired deadline
    cancelled: int = 0            # requests retired by host-side cancel()
    failed: int = 0               # requests failed (preemption bound / step)
    spills: int = 0               # slots spilled to the host tier
    tier_fetches: int = 0         # host pages staged back toward the device
    prefetch_hits: int = 0        # fetched pages attended by the resumed row
    prefetch_wasted: int = 0      # fetched pages released before being used
    draft_tokens: int = 0         # speculative draft tokens verified
    accepted_tokens: int = 0      # drafts accepted (committed to streams)
    rollback_tokens: int = 0      # drafts rejected (len decrement + page
                                  # release); accepted + rollback == draft
                                  # by construction

    @property
    def acceptance_rate(self) -> float:
        """Fraction of verified draft tokens accepted (NaN with no drafts)."""
        return (
            self.accepted_tokens / self.draft_tokens
            if self.draft_tokens
            else math.nan
        )

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    # -- deprecation shim: dict-style access used by pre-obs benches/tests --
    def keys(self):
        return self.as_dict().keys()

    def __iter__(self):
        return iter(self.as_dict())

    def __getitem__(self, key: str):
        warnings.warn(
            "ServeEngine.last_stats is a StepStats dataclass now; use "
            f"attribute access (.{key}) or the engine's obs registry",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.as_dict()[key]

    def get(self, key: str, default=None):
        return self.as_dict().get(key, default)


@jax.jit
def _row_keys(base: jax.Array, seeds: jax.Array, counts: jax.Array) -> jax.Array:
    """One PRNG key per row: fold (request seed, sample index) into base."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.fold_in(base, s), c)
    )(seeds, counts)


@jax.jit
def _sample_rows(logits: jax.Array, temps: jax.Array, keys: jax.Array) -> jax.Array:
    """Per-row sampling: greedy where temp<=0, else categorical at that
    row's own temperature with that row's own key."""
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.vmap(
        lambda l, t, k: jax.random.categorical(k, l / jnp.maximum(t, 1e-6))
    )(logits, temps, keys)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


class ServeEngine:
    def __init__(
        self,
        lm: LM,
        params,
        *,
        batch_size: int = 8,
        max_len: int = 1024,
        seed: int = 0,
        mesh=None,
        pcfg: Optional[ParallelConfig] = None,
        scheduler: str = "static",
        page_size: Optional[int] = None,
        token_budget: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_sharing: bool = True,
        registry: Optional[Registry] = None,
        tracer: Optional[Tracer] = None,
        llc_every: int = 0,
        llc_capacity_bytes: Optional[float] = None,
        log_every_steps: int = 0,
        adapt_order: bool = False,
        adapt_epoch: int = 8,
        adapt_hysteresis: float = 0.05,
        adapt_confirm: int = 2,
        adapt_shared_threshold: float = 0.25,
        autotune_cache: Optional[str] = None,
        admission: str = "reserve",
        max_queue: Optional[int] = None,
        admit_watermark: Optional[float] = None,
        max_preemptions: int = 2,
        pool_pages: Optional[int] = None,
        faults: Optional[FaultPlan] = None,
        host_pages: Optional[int] = None,
        spill_watermark: Optional[float] = None,
        prefetch_depth: int = 2,
        drafter=None,
        draft_len: int = 4,
    ):
        """Pass ``mesh`` (+ optional ParallelConfig) for sharded serving:
        params are placed on their TP/FSDP shardings and every step runs
        under the mesh context (GSPMD propagates cache/batch shardings).

        ``scheduler="continuous"`` rebuilds the model under the paged KV
        layout (``page_size`` pages, default ``kv_block``) and serves with
        the token-budget ragged mixed step: ``token_budget`` tokens per
        step (default: one per slot plus one prefill chunk) split across
        decode rows and ``prefill_chunk``-token prompt chunks (default: 4
        pages). ``prefix_sharing=False`` disables the pool's content-hash
        page dedup (for A/B measurement). ``"static"`` keeps the
        fixed-group path.

        Telemetry (``repro.obs``, DESIGN.md §10): the engine records step
        spans into ``tracer`` and metrics (TTFT/TPOT histograms, per-kind
        token counters, pool/scheduler gauges) into ``registry`` — both
        default to fresh per-engine instances, exposed as ``.obs`` /
        ``.tracer``. Recording is in-process and sink-free; pass the
        instances to ``repro.obs.export`` to dump them. ``llc_every > 0``
        additionally samples the modeled-LLC gauges
        (``llc.modeled_miss_bytes{order=...}``) every that many mixed steps
        against the live pool footprint (continuous path only);
        ``log_every_steps > 0`` prints a one-line stats summary at that
        step cadence.

        Online order adaptation (continuous path, DESIGN.md §11):
        ``adapt_order=True`` lets an :class:`OrderAdaptController` re-pick
        the KV traversal order every ``adapt_epoch`` mixed steps from the
        live modeled-LLC gauges — a switch needs ≥ ``adapt_hysteresis``
        fractional modeled-byte improvement on ``adapt_confirm``
        consecutive samples — and ``autotune_cache`` (a hillclimb
        ``autotune_cache.jsonl`` path) seeds the initial order by
        nearest-bucket lookup before the first step. The traversal order is
        a traced operand of the mixed step (the ``order_group`` scalar), so
        switches never recompile; with adaptation off the same operand just
        stays constant at the configured order.
        ``adapt_shared_threshold`` is the live shared-page fraction above
        which the controller blends the shared-prefix LLC model into the
        decision (DESIGN.md §11 follow-up).

        Resilience (DESIGN.md §12): ``admission="optimistic"`` reserves only
        prompts and lets decode growth oversubscribe the pool — mid-flight
        ``PoolExhausted`` is answered by preempting a victim slot
        (``select_victim``) and restoring it later via chunked re-prefill,
        at most ``max_preemptions`` times per request before it resolves
        ``status="failed"``. ``max_queue`` bounds the arrived waiting queue
        (newest beyond it are load-shed with ``status="shed"``);
        ``admit_watermark`` pauses admission while pool occupancy is at or
        above it (default 0.9 under optimistic admission, 1.0 — never —
        under reserve) instead of thrashing admission against preemption.
        ``pool_pages`` overrides the pool's allocatable page count below the
        all-slots worst case — the oversubscription knob that makes real
        (non-injected) pool pressure reachable. ``faults`` attaches a
        deterministic ``serve.faults.FaultPlan`` driving the no-op injection
        hooks; one injected ``StepFault`` per step is retried once
        before the step's rows fail.

        Tiered KV memory (DESIGN.md §13): ``host_pages > 0`` backs the
        device pool with a ``serve.tiering.TieredPagePool`` host tier of
        that many pages. When device occupancy reaches ``spill_watermark``
        (default ``min(0.85, admit_watermark)``) the engine *spills* the
        coldest slot — ranked by ``cache_sim.slot_reuse_stats``, not plain
        LRU — to the host instead of (later) preempting it, and the
        pressure resolution order becomes shed → spill → preempt. Resuming
        slots stream their pages back ``prefetch_depth`` pages per step
        boundary in the next step's traversal visit order
        (``core.schedule.future_visit_window``), with the host→device
        copies issued while the current mixed step is in flight; the slot
        re-enters planning only once fully resident, so spill/resume is
        bitwise-invisible to its token stream.

        Speculative decoding (DESIGN.md §14, continuous path only):
        ``drafter`` (a ``serve.spec.Drafter``) proposes up to ``draft_len``
        draft tokens per decode row each boundary; the row rides the mixed
        step as a ``q_len = K+1`` verification chunk (the same ragged
        primitive prefill chunks use, so the compiled widths stay exactly
        two), every chunk position is sampled in the one device step, and
        the longest draft prefix matching the sampled targets is committed
        — plus the sampled token after it. Rejected drafts are undone
        host-side: ``PagedKVPool.rollback`` decrements the row's len and
        releases now-dead tail pages. Per-row PRNG keys fold the sample
        *count*, advanced only per accepted token, so greedy AND sampled
        streams are bitwise identical to non-speculative serving."""
        if scheduler not in ("static", "continuous"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if drafter is not None and scheduler != "continuous":
            raise ValueError("speculative decoding requires scheduler='continuous'")
        if draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {draft_len}")
        self.drafter = drafter
        self.draft_len = int(draft_len)
        if admission not in ("reserve", "optimistic"):
            raise AdmissionError(f"unknown admission discipline {admission!r}")
        if scheduler == "continuous":
            cfg = lm.cfg
            if not supports_continuous(cfg):
                raise ValueError(
                    "continuous scheduling needs a token-only full-attention "
                    f"family {CONTINUOUS_FAMILIES} (got family={cfg.family!r}, "
                    f"window={cfg.window}); use scheduler='static'"
                )
            page = min(page_size or cfg.page_size or cfg.kv_block, max_len)
            lm = build_model(cfg.with_(kv_layout="paged", page_size=page))
            self._page = page
            self._chunk = max(1, min(prefill_chunk or 4 * page, max_len))
            self._budget = token_budget
        self.scheduler = scheduler
        self.lm = lm
        self.mesh = mesh
        self.eos = lm.cfg.eos_id
        self.prefix_sharing = prefix_sharing
        self.admission = admission
        self.max_queue = max_queue
        self.max_preemptions = max_preemptions
        self.pool_pages = pool_pages
        self.faults = faults
        self._watermark = (
            admit_watermark
            if admit_watermark is not None
            else (0.9 if admission == "optimistic" else 1.0)
        )
        self.host_pages = host_pages
        self.prefetch_depth = max(1, int(prefetch_depth))
        if spill_watermark is not None and not 0.0 < spill_watermark <= 1.0:
            raise ValueError(
                f"spill_watermark must be in (0, 1], got {spill_watermark}"
            )
        self._spill_wm = (
            spill_watermark
            if spill_watermark is not None
            else min(0.85, self._watermark)
        )
        self._cancelled: set[int] = set()
        # Cache capacity model, shared by validation here and the budgeting
        # in _generate_batch: prefill writes bucket + prefix tokens (VLM
        # prepends prefix embeddings) and decode writes max_new - 1 more
        # (the last sampled token is never written back). Only
        # full-attention caches are max_len-bounded — SSM decode state is
        # O(1) and sliding-window archs use a ring buffer.
        self._prefix = (
            min(lm.cfg.n_prefix_embeds, 8) if lm.cfg.family == "vlm" else 0
        )
        bounded = lm.cfg.window is None and lm.cfg.family != "ssm"
        if bounded and max_len <= self._prefix:
            detail = (
                f"the {self._prefix} VLM prefix embeddings leave no room"
                if self._prefix
                else "it must be positive"
            )
            raise ValueError(
                f"max_len={max_len} gives a zero-capacity KV cache ({detail}); "
                f"use max_len > {self._prefix}"
            )
        self._cap = max_len - self._prefix if bounded else None
        if mesh is not None:
            pcfg = pcfg or ParallelConfig(fsdp_axes=("data",), data_axes=("data",))
            params = jax.device_put(params, shd.param_shardings(params, pcfg, mesh))
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(lambda p, b: lm.prefill(p, b, max_len))
        self._decode = jax.jit(lm.decode_step)
        self._mixed_step = None       # single jitted ragged step (continuous)

        # ---- telemetry (repro.obs) ----
        self.obs = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else Tracer()
        self._log_every = log_every_steps
        self.last_stats: Optional[StepStats] = None
        r = self.obs  # hot-loop handles resolved once (recording = attr add)
        self._m_tok_decode = r.counter("serve.step.tokens", kind="decode")
        self._m_tok_prefill = r.counter("serve.step.tokens", kind="prefill")
        self._m_kv_tok = r.counter("serve.step.kv_tokens")
        self._m_qk_pairs = r.counter("serve.step.qk_pairs")
        self._m_generated = r.counter("serve.tokens.generated")
        self._m_steps_wide = r.counter("serve.steps", width="wide")
        self._m_steps_narrow = r.counter("serve.steps", width="narrow")
        self._m_req_admitted = r.counter("serve.requests", event="admitted")
        self._m_req_finished = r.counter("serve.requests", event="finished")
        self._m_req_requeued = r.counter("serve.requests", event="requeued")
        self._m_compiles = r.counter("serve.compiles")
        self._m_ttft = r.histogram("serve.ttft_s")
        self._m_tpot = r.histogram("serve.tpot_s")
        self._m_step_time = r.histogram("serve.step_time_s")
        self._m_queue = r.gauge("serve.queue_depth")
        self._m_active = r.gauge("serve.active_slots")
        self._m_budget = r.gauge("serve.budget_utilization")
        # Resilience series (DESIGN.md §12) — created here, not lazily, so
        # every engine exposes the full schema from step 0 (check_metrics.py
        # requires them even on fault-free runs).
        self._m_preempt = r.counter("serve.preemptions")
        self._m_restore_tok = r.counter("serve.restore_tokens")
        self._m_shed = r.counter("serve.shed")
        self._m_deadline = r.counter("serve.deadline_miss")
        self._m_cancel = r.counter("serve.cancelled")
        self._m_failed = r.counter("serve.failed")
        self._m_retries = r.counter("serve.step_retries")
        self._m_admit_paused = r.gauge("serve.admission_paused")
        # Speculative-decoding series (DESIGN.md §14) — pre-created at zero
        # on every engine so check_metrics.py can require the schema (and
        # its accepted + rolled_back == drafted conservation) even on
        # non-speculative runs.
        self._m_draft_tok = r.counter("serve.spec.draft_tokens")
        self._m_accept_tok = r.counter("serve.spec.accepted_tokens")
        self._m_rollback_tok = r.counter("serve.spec.rollback_tokens")
        # Tiering series (DESIGN.md §13) — likewise pre-created at zero on
        # every engine (tiered or not), so check_metrics.py can require the
        # full tier.* schema unconditionally. The TieredPagePool increments
        # them; on an untiered engine they stay flat at zero.
        for name in (
            "tier.spills",
            "tier.fetches",
            "tier.prefetch_hits",
            "tier.prefetch_wasted",
            "tier.fetch_failures",
            "tier.spill_bytes",
            "tier.fetch_bytes",
        ):
            r.counter(name)
        for name in (
            "tier.host_pages",
            "tier.device_pages",
            "tier.suspended_slots",
            "tier.overlap_frac",
        ):
            r.gauge(name)
        self.llc: Optional[LLCSampler] = None
        self.order_ctl: Optional[OrderAdaptController] = None
        if scheduler == "continuous":
            cfg = self.lm.cfg
            elem_bytes = (
                1
                if cfg.kv_cache_dtype == "int8"
                else np.dtype(cfg.activation_dtype()).itemsize
            )
            capacity = llc_capacity_bytes or DEFAULT_CAPACITY_BYTES
            # The controller owns the live (order, snake_group) pair — also
            # when adaptation is off, so serve.current_order /
            # serve.order_switches exist on every continuous engine and the
            # step operand has a single source.
            self.order_ctl = OrderAdaptController(
                self.obs,
                order=cfg.attn_order,
                snake_group=cfg.snake_group,
                epoch=adapt_epoch,
                hysteresis=adapt_hysteresis,
                confirm=adapt_confirm,
                shared_threshold=adapt_shared_threshold,
                enabled=adapt_order,
            )
            if adapt_order and autotune_cache:
                self.order_ctl.seed_from_cache(
                    autotune_cache,
                    arch=cfg.name,
                    seq_bucket=max_len,
                    capacity_mib=capacity / 2**20,
                    backend=jax.default_backend(),
                )
            self.llc = LLCSampler(
                self.obs,
                page=self._page,
                n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd,
                elem_bytes=elem_bytes,
                current_order=self.order_ctl.order.value,
                snake_group=self.order_ctl.snake_group,
                every=llc_every,
                capacity_bytes=capacity,
                **(
                    {"orders": self.order_ctl.candidate_orders}
                    if adapt_order
                    else {}
                ),
            )

    def _mesh_ctx(self):
        return (
            jax.set_mesh(self.mesh) if self.mesh is not None else contextlib.nullcontext()
        )

    def cancel(self, rid: int) -> None:
        """Host-side cancellation of request ``rid``: at the next step
        boundary (continuous) / decode iteration (static) the request is
        retired, its pages released, and it resolves with
        ``status="cancelled"`` carrying whatever tokens it had produced.
        Unknown rids are remembered — a request submitted later under a
        pre-cancelled rid resolves immediately."""
        self._cancelled.add(int(rid))

    def _eos_for(self, r: Request) -> int:
        return self.eos if r.eos_id is None else r.eos_id

    def _seed_for(self, r: Request, idx: int) -> int:
        """Effective sampling-stream id: explicit seed, else the request's
        submission index (distinct by construction, so N identical
        temperature>0 requests in one call return N independent samples)."""
        return idx if r.seed is None else r.seed

    def _pad_batch(
        self,
        prompts: Sequence[np.ndarray],
        max_bucket: Optional[int] = None,
        batch: Optional[int] = None,
        bucket: Optional[int] = None,
    ) -> jnp.ndarray:
        # Shared prefill bucket. Bounded (full-attention) caches cap it at
        # the cache capacity: an overlong prompt keeps only its most recent
        # tokens (causal LM — the tail conditions generation) instead of
        # silently overflowing the prefill bucket and then clamp-overwriting
        # the cache's last slot every decode step. max_bucket=None (SSM
        # state, SWA ring buffers) leaves prompts untouched.
        length = bucket or max(1, max(len(p) for p in prompts))  # all-empty -> 1 pad
        if max_bucket is not None:
            length = min(length, max_bucket)
        out = np.full((batch or self.batch_size, length), self.eos, np.int32)
        for i, p in enumerate(prompts):
            p = p[-length:]
            out[i, length - len(p) :] = p  # left-pad into a shared bucket
        return jnp.asarray(out)

    def generate(self, requests: Sequence[Request]) -> list[GenerationResult]:
        if self.scheduler == "continuous":
            return self._generate_continuous(requests)
        results: list[GenerationResult] = []
        t0 = time.perf_counter()  # TTFT includes queueing behind earlier groups
        for i in range(0, len(requests), self.batch_size):
            group = list(requests[i : i + self.batch_size])
            results.extend(self._generate_batch(group, base_idx=i, t0=t0))
        return results

    # ---- static path ---------------------------------------------------------

    def _generate_batch(
        self, group: Sequence[Request], base_idx: int = 0, t0: Optional[float] = None
    ) -> list[GenerationResult]:
        # Prompts get priority for the bounded capacity (see __init__ for
        # the capacity model); a request whose max_new_tokens exceeds what
        # remains after the shared bucket is clamped (visible via .steps),
        # not failed — one greedy request must not abort or context-starve
        # the rest of the batch.
        prefix, cap = self._prefix, self._cap
        tokens = self._pad_batch([r.tokens for r in group], max_bucket=cap)
        bucket = tokens.shape[1]
        new_limits = [
            r.max_new_tokens
            if cap is None
            else max(0, min(r.max_new_tokens, cap - bucket + 1))
            for r in group
        ]
        max_new = max(new_limits)
        if self.lm.cfg.family == "encdec":
            b, s = tokens.shape
            batch = {
                "src_embeds": jnp.zeros((b, s, self.lm.cfg.d_model), self.lm.cfg.activation_dtype()),
                "tgt_tokens": tokens,
            }
        elif self.lm.cfg.family == "vlm":
            b, s = tokens.shape
            batch = {
                "tokens": tokens,
                "prefix_embeds": jnp.zeros((b, prefix, self.lm.cfg.d_model), self.lm.cfg.activation_dtype()),
            }
        else:
            batch = {"tokens": tokens}

        t0 = time.perf_counter() if t0 is None else t0
        with self.tracer.span("serve.prefill", rows=len(group), bucket=bucket):
            with self._mesh_ctx():
                logits, caches = self._prefill(self.params, batch)
        self._m_tok_prefill.inc(len(group) * bucket)
        generated = np.zeros((len(group), max_new), np.int32)
        done = np.asarray([lim == 0 for lim in new_limits])  # 0-limit rows emit nothing
        steps = np.zeros(len(group), np.int32)
        status = ["ok"] * len(group)
        eos_for = [self._eos_for(r) for r in group]
        # logits carry batch_size rows (padding rows included) — size the
        # per-row sampling params to match.
        temps_np = np.zeros((tokens.shape[0],), np.float32)
        seeds_np = np.zeros((tokens.shape[0],), np.int32)
        for j, r in enumerate(group):
            temps_np[j] = r.temperature
            seeds_np[j] = self._seed_for(r, base_idx + j)
        temps = jnp.asarray(temps_np)
        seeds = jnp.asarray(seeds_np)

        cur = jax.block_until_ready(self._sample(logits[:, -1], temps, seeds, 0))
        # Group-shared TTFT (one fused prefill+sample), measured from engine
        # start so queueing behind earlier groups counts; blocked first —
        # dispatch is async, the unforced timestamp would exclude device time.
        ttft = time.perf_counter() - t0
        for t in range(max_new):
            # Boundary checks BEFORE recording: a request cancelled (or past
            # its deadline) before this iteration keeps only what it already
            # has — a deadline_s=0 request resolves with zero tokens.
            now_s = time.perf_counter() - t0
            for j, r in enumerate(group):
                if done[j]:
                    continue
                if r.rid in self._cancelled:
                    done[j] = True
                    status[j] = "cancelled"
                    self._cancelled.discard(r.rid)
                elif r.deadline_s is not None and now_s > r.deadline_s:
                    done[j] = True
                    status[j] = "deadline"
            for j in range(len(group)):
                if not done[j]:
                    generated[j, t] = int(cur[j, 0])
                    steps[j] = t + 1
                    if int(cur[j, 0]) == eos_for[j] or t + 1 >= new_limits[j]:
                        done[j] = True
            if done.all():
                break
            with self.tracer.span("serve.decode_step", t=t):
                with self._mesh_ctx():
                    logits, caches = self._decode(self.params, cur, caches)
                cur = self._sample(logits[:, -1], temps, seeds, t + 1)
            self._m_tok_decode.inc(int((~done).sum()))
        total = time.perf_counter() - t0

        results = [
            GenerationResult(
                rid=r.rid,
                tokens=generated[j, : steps[j]],
                steps=int(steps[j]),
                ttft_s=ttft,
                tpot_s=_tpot(total - ttft, int(steps[j])),
                status=status[j],
            )
            for j, r in enumerate(group)
        ]
        for res in results:
            self._record_result(res)
        return results

    def _record_result(self, res: GenerationResult) -> None:
        """Publish one finished request into the registry (NaN TPOT — a
        single-token generation — is dropped by the histogram). Latency
        histograms only see ``status="ok"`` requests — a shed or expired
        request's wall time is a policy artifact, not a latency sample —
        while each non-ok terminal status counts into its own series."""
        self._m_req_finished.inc()
        self._m_generated.inc(res.steps)
        if res.status == "ok":
            self._m_ttft.observe(res.ttft_s)
            self._m_tpot.observe(res.tpot_s)
        elif res.status == "deadline":
            self._m_deadline.inc()
        elif res.status == "cancelled":
            self._m_cancel.inc()
        elif res.status == "shed":
            self._m_shed.inc()
        elif res.status == "failed":
            self._m_failed.inc()

    def _sample(self, logits: jax.Array, temps, seeds, count: int) -> jnp.ndarray:
        counts = jnp.full(seeds.shape, count, jnp.int32)
        keys = _row_keys(self.key, seeds, counts)
        return _sample_rows(logits, temps, keys)[:, None]

    # ---- continuous path -----------------------------------------------------
    #
    # One fused jitted RAGGED MIXED STEP per iteration: assemble the cache
    # view (pages + block tables + per-row q_start/q_len), run the ragged
    # chunk through the model, sample the last valid position of every row
    # — a single dispatch, so the scheduler's fewer-steps win is not eaten
    # by per-step host overhead. The step compiles at exactly two widths
    # (1 for decode-only steps, prefill_chunk otherwise) regardless of how
    # many distinct prompt lengths the stream carries — the per-bucket
    # prefill jit cache of the previous design (unbounded compilation
    # growth) is gone, as is the separate decode-only step.

    def step_logits(self, params, tokens, pages, bt, lens, qlens, order_group):
        """The mixed step's model half: ``(logits (B, C, V), new pages)``
        for a ragged chunk ``tokens`` written at each row's ``lens`` offset
        (``qlens`` valid positions per row). The compiled step samples
        from exactly these logits.

        ``order_group`` is the traced effective reversal-group scalar
        (adapt.OrderAdaptController.effective_group): the traversal order
        is step *data*, so the adaptation can switch it between steps
        inside one compiled step.
        """
        caches = assemble_cache_view(
            pages, bt, lens, self.lm.cfg.n_layers, qlens, order_group
        )
        logits, caches = self.lm.decode_step(params, tokens, caches)
        return logits, {name: caches[name] for name in pages}

    def _mixed_step_fn(self):
        if self._mixed_step is None:
            base = self.key

            def step(
                params, tokens, pages, bt, lens, qlens, order_group,
                temps, seeds, bases,
            ):
                logits, new_pages = self.step_logits(
                    params, tokens, pages, bt, lens, qlens, order_group
                )
                # EVERY chunk position is sampled — position p of row b uses
                # the PRNG key for sample index ``bases[b] + p``, the exact
                # key a sequence of q_len=1 steps would have used one by
                # one. The host picks what it needs: the last valid position
                # for prefill/decode rows, the whole K+1 target ladder for a
                # speculative verification row (position i conditions on
                # chunk[0..i], i.e. on the first i draft tokens). Per-row
                # sampling math is unchanged (greedy at temp<=0, categorical
                # at the row's own temperature), so each position is bitwise
                # what the old single-position step sampled.
                greedy = jnp.argmax(logits, axis=-1)

                def _sampled(_):
                    pos = jnp.arange(logits.shape[1], dtype=jnp.int32)
                    keys = jax.vmap(
                        lambda s, b: jax.vmap(
                            lambda c: jax.random.fold_in(
                                jax.random.fold_in(base, s), c
                            )
                        )(b + pos)
                    )(seeds, bases)
                    return jax.vmap(
                        jax.vmap(
                            lambda l, t, k: jax.random.categorical(
                                k, l / jnp.maximum(t, 1e-6)
                            ),
                            in_axes=(0, None, 0),
                        )
                    )(logits, temps, keys)

                # An all-greedy batch (the decode-heavy common case) skips
                # the key ladder + categorical entirely; with any sampling
                # row present the full per-position math runs, bitwise
                # identical to the ungated form.
                sampled = jax.lax.cond(
                    jnp.any(temps > 0.0), _sampled, lambda _: greedy, None
                )
                toks = jnp.where(
                    temps[:, None] > 0.0, sampled, greedy
                ).astype(jnp.int32)
                return toks, new_pages

            # The pool pages are donated: the step writes them in place, so
            # the old and new pool are never both live (at deepseek-7b
            # widths that is 1 GiB of a 16 GiB chip). The pool adopts the
            # returned pages right after every dispatch.
            self._mixed_step = jax.jit(step, donate_argnums=(2,))
        return self._mixed_step

    def compiled_step_count(self) -> int:
        """Number of compiled variants of the continuous mixed step (the
        compile-counter regression surface: O(1) — at most two widths — for
        any stream of prompt lengths), read from the jit cache itself."""
        if self._mixed_step is None:
            return 0
        return int(self._mixed_step._cache_size())

    def _generate_continuous(
        self, requests: Sequence[Request]
    ) -> list[GenerationResult]:
        cfg = self.lm.cfg
        n_slots = self.batch_size
        cap = self._cap
        sched = ContinuousScheduler(
            n_slots, token_budget=self._budget, prefill_chunk=self._chunk
        )
        sched.submit(list(requests))
        idx_of = {id(r): i for i, r in enumerate(requests)}  # default seeds
        tiered = self.host_pages is not None and self.host_pages > 0
        pool_kw = dict(
            prefix_sharing=self.prefix_sharing,
            registry=self.obs,
            admission=self.admission,
            n_pages=self.pool_pages,
            faults=self.faults,
        )
        if tiered:
            pool = TieredPagePool(
                cfg, cfg.n_layers, n_slots, cap,
                host_pages=self.host_pages, **pool_kw,
            )
        else:
            pool = PagedKVPool(cfg, cfg.n_layers, n_slots, cap, **pool_kw)
        self.last_pool = pool  # exposed for benches/tests (sharing counters)

        drafter = self.drafter
        if drafter is not None:
            drafter.reset()
        results: dict[int, GenerationResult] = {}
        resume: dict[int, list[int]] = {}   # preempted: id(req) -> generated
        n_preempts: dict[int, int] = {}     # id(req) -> times preempted
        tally = {
            "preempt": 0, "restore": 0, "spill": 0,
            "draft": 0, "accept": 0, "roll": 0,
        }
        cur = np.full((n_slots,), self.eos, np.int32)  # last sampled token
        temps = np.zeros((n_slots,), np.float32)
        seeds = np.zeros((n_slots,), np.int32)
        counts = np.zeros((n_slots,), np.int32)
        t0_ns = time.perf_counter_ns()
        t0 = t0_ns / 1e9
        first_t: dict[int, float] = {}
        # A request's queue wait (``serve.queued``) starts at t0, or at the
        # boundary that first reaches its arrival step (``ready_ns``).
        arrivals = collections.deque(
            sorted({r.arrival for r in requests if r.arrival > 0})
        )
        ready_ns: dict[int, int] = {}

        def resolve(r, tokens: list, status: str) -> None:
            # Terminal for ANY lifecycle outcome — every submitted request
            # funnels through here exactly once, with a typed status and
            # whatever (possibly partial) tokens it produced.
            now = time.perf_counter()
            n_tok = len(tokens)
            ttft = first_t.pop(id(r), now) - t0
            res = GenerationResult(
                rid=r.rid,
                tokens=np.asarray(tokens, np.int32),
                steps=n_tok,
                ttft_s=ttft,
                tpot_s=_tpot((now - t0) - ttft, n_tok),
                status=status,
                n_preemptions=n_preempts.get(id(r), 0),
            )
            results[id(r)] = res
            self._cancelled.discard(r.rid)
            self._record_result(res)

        def finish(slot: int, status: str = "ok") -> None:
            st = sched.retire(slot)
            pool.release(slot)
            if drafter is not None:
                drafter.release(slot)
            cur[slot] = self.eos
            temps[slot] = 0.0
            resolve(st.request, list(st.generated), status)

        def preempt(slot: int) -> None:
            # Evict a live slot under pool pressure: release its pages and
            # requeue it at the queue head (restore = chunked re-prefill of
            # prompt + generated-so-far through the same mixed step), or
            # fail it cleanly once past its preemption bound.
            st = sched.retire(slot)
            pool.release(slot)
            if drafter is not None:
                drafter.release(slot)
            cur[slot] = self.eos
            temps[slot] = 0.0
            r = st.request
            n_pre = n_preempts.get(id(r), 0) + 1
            n_preempts[id(r)] = n_pre
            limit = (
                self.max_preemptions
                if getattr(r, "max_preemptions", None) is None
                else r.max_preemptions
            )
            if n_pre > limit:
                resolve(r, list(st.generated), "failed")
                return
            resume[id(r)] = list(st.generated)
            sched.requeue(r)
            tally["preempt"] += 1
            self._m_preempt.inc()
            self._m_req_requeued.inc()
            tr.instant(
                "serve.preempt", rid=r.rid, slot=slot,
                generated=len(st.generated),
            )

        def preempt_victim() -> bool:
            # Suspended slots are not candidates: they hold no device pages,
            # so preempting one frees nothing (and throws away the spilled
            # KV the tier just paid to preserve).
            cands = [
                (
                    i,
                    getattr(sched.slots[i].request, "priority", 0),
                    len(sched.slots[i].generated),
                    pool.shared_donor(i),
                )
                for i in sched.runnable_slots()
                if not sched.slots[i].done
            ]
            if not cands:
                return False
            preempt(select_victim(cands))
            return True

        def spill_one(keep: int) -> bool:
            # Spill the coldest runnable slot to the host tier, keeping at
            # least ``keep`` runnable (the watermark pass keeps one so the
            # stream always advances; the pressure path may go to zero —
            # the freed pages are exactly what lets a resume complete).
            # Shielded slots (resumed, not yet stepped) are excluded: they
            # would waste their just-fetched pages and invite ping-pong.
            run = [i for i in sched.runnable_slots() if not sched.slots[i].done]
            cands = [
                i for i in run if pool.can_spill(i) and not pool.shielded(i)
            ]
            if not cands or len(run) <= keep:
                return False
            stats = slot_reuse_stats(
                self.order_ctl.order.value,
                [int(l) for l in pool.lens],
                pool.page,
                snake_group=self.order_ctl.snake_group,
            )
            victim = select_spill_victim(
                [
                    (
                        i,
                        getattr(sched.slots[i].request, "priority", 0),
                        pool.shared_donor(i),
                        stats[i]["mean"],
                    )
                    for i in cands
                ]
            )
            if victim is None or not pool.spill_slot(victim):
                return False  # host full / injected tier.spill stall
            sched.suspend(victim)
            tally["spill"] += 1
            tr.instant(
                "serve.spill", slot=victim,
                pages=pool._offslot_pages(victim),
            )
            return True

        def tier_boundary() -> None:
            # Per-boundary tier work, in resolution order (DESIGN.md §13):
            # splice finished resumes back in, spill down to the watermark,
            # then open the fetch queue of (at most) one suspended slot —
            # pages stream back in the next step's traversal visit order.
            for i in pool.suspended_slots():
                if pool.resume_ready(i) and pool.complete_resume(i):
                    sched.resume(i)
                    tr.instant("serve.tier_resume", slot=i)
            while pool.occupancy() >= self._spill_wm and spill_one(keep=1):
                pass
            suspended = pool.suspended_slots()
            if not suspended or any(
                pool._suspended[i].started for i in suspended
            ):
                return
            runnable = [
                i for i in sched.runnable_slots() if not sched.slots[i].done
            ]
            n_alloc = pool.alloc.n_pages - 1
            held = n_alloc - pool.alloc.free_count
            for i in suspended:  # oldest slot index: deterministic FIFO-ish
                n_pgs = pool._offslot_pages(i)
                # Resume only into calm (a resume that immediately pushes
                # occupancy back over the spill watermark just rotates the
                # pressure onto a different victim — park instead, and let
                # running work finish at full width) — unless nothing is
                # runnable, where a resume is the only way to make progress.
                calm = (held + n_pgs) / max(n_alloc, 1) < self._spill_wm
                if pool.alloc.available >= pool.resume_need(i) and (
                    calm or not runnable
                ):
                    group = self.order_ctl.effective_group(max(n_pgs, 1))
                    pool.start_resume(
                        i,
                        order=future_visit_window(
                            int(pool.lens[i]) // pool.page, n_pgs,
                            n_pgs, group,
                        ),
                    )
                    break

        tr = self.tracer
        step_fn = self._mixed_step_fn()
        step = 0
        n_steps = n_wide = 0  # deterministic per-stream work counters
        last_cc = self.compiled_step_count()
        while sched.has_work():
            t_iter = time.perf_counter()
            with tr.span("serve.step", step=step):
                with tr.span("serve.admit"):
                    # Arrival steps reached at this boundary: the queue
                    # wait of a request arriving there starts now.
                    now_ns = time.perf_counter_ns()
                    while arrivals and arrivals[0] <= step:
                        ready_ns[arrivals.popleft()] = now_ns

                    # ---- step-boundary lifecycle checks (DESIGN.md §12) ----
                    if self.faults is not None:
                        self.faults.begin_step(step)
                        for rid in self.faults.take_cancels():
                            self._cancelled.add(int(rid))
                    if self._cancelled:
                        hit = sched.drain_waiting(
                            lambda r: r.rid in self._cancelled
                        )
                        for r in hit:
                            resolve(r, resume.pop(id(r), []), "cancelled")
                        for i in list(sched.active_slots()):
                            if sched.slots[i].request.rid in self._cancelled:
                                finish(i, "cancelled")
                    now_s = time.perf_counter() - t0
                    for r in sched.drain_waiting(
                        lambda r: r.deadline_s is not None and now_s > r.deadline_s
                    ):
                        resolve(r, resume.pop(id(r), []), "deadline")
                    for i in list(sched.active_slots()):
                        r = sched.slots[i].request
                        if r.deadline_s is not None and now_s > r.deadline_s:
                            finish(i, "deadline")

                    # Tiered KV boundary work BEFORE admission: spilling down to
                    # the spill watermark is what un-pauses admission under the
                    # (higher) admit watermark — park cold work, keep admitting.
                    if tiered:
                        tier_boundary()

                    # Admission: fill free slots with arrived requests while the
                    # pool can reserve their (sharing-reduced) worst case. The
                    # high watermark pauses admission under pool pressure so new
                    # work does not immediately thrash running work back out via
                    # preemption; with no active slots it never pauses (only
                    # retirements can lower occupancy — registered prefix pages
                    # legitimately outlive their donors).
                    paused = (
                        pool.occupancy() >= self._watermark
                        and bool(sched.active_slots())
                    )
                    self._m_admit_paused.set(float(paused))
                    while not paused and (slot := sched.free_slot()) is not None:
                        req = sched.pop_admissible(step)
                        if req is None:
                            break
                        restored = id(req) in resume
                        ctx = (
                            tr.span("serve.preempt_restore", rid=req.rid)
                            if restored
                            else contextlib.nullcontext()
                        )
                        with ctx:
                            st = self._admit(
                                req, slot, sched, pool, temps, seeds, counts,
                                idx_of.get(id(req), 0), prior=resume.get(id(req)),
                            )
                        if st is None:
                            sched.requeue(req)  # no pages yet; retry after retirements
                            self._m_req_requeued.inc()
                            break
                        resume.pop(id(req), None)
                        self._m_req_admitted.inc()
                        if not restored:  # a restore is not a new wait
                            start = ready_ns.get(req.arrival, t0_ns)
                            tr.record(
                                "serve.queued", start,
                                time.perf_counter_ns() - start, rid=req.rid,
                            )
                        if restored and st.prompt is not None:
                            n_re = int(len(st.prompt) - st.prompt_pos)
                            tally["restore"] += n_re
                            self._m_restore_tok.inc(n_re)
                        if st.done:  # zero-limit request: emits nothing
                            finish(slot)

                    # Load shed AFTER admission drained what it could: the
                    # queue bound applies to arrived requests this boundary
                    # could not place, newest rejected first.
                    if self.max_queue is not None:
                        for r in sched.shed_over(step, self.max_queue):
                            resolve(r, resume.pop(id(r), []), "shed")

                # Speculative drafting (DESIGN.md §14) — ONCE per boundary,
                # before the plan/pressure retry loop: a model drafter runs
                # device steps of its own, so it must not be re-invoked when
                # a PoolExhausted retry below re-plans. K is clamped per row
                # so the verification chunk can neither outgrow the row's
                # new_limit / cache capacity (speculative writes stay inside
                # the admission reservation) nor exceed the wide compiled
                # width (q_len = K+1 <= prefill_chunk).
                drafts: dict[int, list[int]] = {}
                if drafter is not None:
                    want = []
                    for i in sched.runnable_slots():
                        st = sched.slots[i]
                        if st.done or st.prefilling:
                            continue
                        kmax = min(
                            self.draft_len,
                            st.new_limit - len(st.generated) - 1,
                            cap - int(pool.lens[i]) - 1,
                            self._chunk - 1,
                        )
                        if kmax < 1:
                            continue
                        ctx = np.concatenate(
                            [
                                st.prompt,
                                np.asarray(
                                    st.generated[st.n_prior :], np.int32
                                ),
                            ]
                        )
                        want.append((i, ctx, kmax))
                    if want:
                        with tr.span("serve.draft", rows=len(want)):
                            out = drafter.draft_batch(want)
                        for (i, _, kmax) in want:
                            d = [int(t) for t in out.get(i, [])][:kmax]
                            if d:
                                drafts[i] = d

                # Plan under pressure: make every planned row writable; a
                # mid-step PoolExhausted (optimistic oversubscription or an
                # injected fault) resolves shed → spill → preempt: spilling
                # a victim to the host tier preserves its KV (resume is a
                # memcpy), preemption is the fallback that throws work away.
                # Each retry removes one runnable slot — the victim may be
                # the very slot that failed — so this terminates.
                # ensure_writable is idempotent; re-ensured rows are no-ops
                # on retry. (Draft q_lens are part of the plan; a retried
                # plan re-derives them from the surviving slots.)
                draft_lens = {i: len(d) for i, d in drafts.items()} or None
                while True:
                    with tr.span("serve.plan_step"):
                        plan = sched.plan_step(draft_lens)
                    if not plan:
                        break
                    try:
                        for it in plan:
                            pool.ensure_writable(it.slot, it.q_len)
                    except PoolExhausted:
                        if tiered and spill_one(keep=0):
                            continue
                        if not preempt_victim():
                            raise
                        continue
                    break
                self._m_queue.set(len(sched.waiting))
                self._m_active.set(len(sched.active_slots()))
                if not plan:
                    if tiered and pool.suspended_slots():
                        # Nothing runnable, but suspended work exists: spend
                        # the boundary streaming pages back (nothing to
                        # overlap with — the fetches count as un-overlapped)
                        # and come back; complete_resume at the next
                        # boundary returns the slot to planning.
                        with tr.span("serve.prefetch", overlapped=False):
                            for i in pool.suspended_slots():
                                pool.issue_fetches(
                                    i, self.prefetch_depth, overlapped=False
                                )
                        step += 1
                        continue
                    if sched.waiting:
                        nxt = sched.next_arrival()
                        step = max(step + 1, nxt if nxt is not None else step + 1)
                        continue
                    break
                planned = sum(it.q_len for it in plan)
                self._m_budget.set(planned / sched.token_budget)

                width = 1 if all(it.q_len == 1 for it in plan) else self._chunk
                tokens = np.full((n_slots, width), self.eos, np.int32)
                qlens = np.zeros((n_slots,), np.int32)
                # Per-row first sample index for the step's key ladder
                # (position p of row b folds ``bases[b] + p``): decode and
                # verification rows start at the row's live count; a prefill
                # row's only consumed position is its last (q_len-1), which
                # must land exactly on the row's count — the same key the
                # old single-position step folded.
                bases = counts.copy()
                n_decode = n_prefill = 0
                n_kv = 0  # keys the step's queries attend, over its rows
                n_qk = 0  # query-key pairs they attend: each query sees the
                #           row's cached keys and the chunk's up to its own
                for it in plan:
                    st = sched.slots[it.slot]
                    n_kv += int(pool.lens[it.slot]) + it.q_len
                    n_qk += int(pool.lens[it.slot]) * it.q_len + it.q_len * (it.q_len + 1) // 2
                    if it.is_prefill:
                        seg = st.prompt[st.prompt_pos : st.prompt_pos + it.q_len]
                        tokens[it.slot, : len(seg)] = seg
                        bases[it.slot] = counts[it.slot] - (it.q_len - 1)
                        n_prefill += it.q_len
                    else:
                        row = [int(cur[it.slot])] + drafts.get(it.slot, [])[
                            : it.n_draft
                        ]
                        tokens[it.slot, : len(row)] = row
                        n_decode += it.q_len
                    qlens[it.slot] = it.q_len

                # The device span closes only after the sampled tokens are
                # host-materialized, so it brackets real device time (the
                # dispatch itself is async). The step is functional (pages
                # come back as fresh arrays; the pool adopts them only on
                # success), so a failed dispatch leaves no partial state and
                # a retry re-runs the identical computation: one injected
                # ``StepFault`` is retried once, a second fails the step's
                # rows cleanly and the engine moves on. Any other error
                # (a compile refusal, an out-of-memory) propagates.
                # Suspended rows keep their logical length host-side for the
                # resume, but the step operand sees 0: their block-table row
                # is dummied out, and a len>0 row over dummy pages is a
                # shape the kernels never needed to define.
                # Every host array goes in as a snapshot: the CPU backend may
                # alias numpy arguments zero-copy, and the step's page writes
                # can still be reading them after ``toks`` is ready — while
                # the host already advances lens/tables/temps in place.
                lens_op = pool.lens.copy()
                if tiered and pool.suspended_slots():
                    lens_op[pool.suspended_slots()] = 0

                def dispatch():
                    # The injected fault fires before the step is called,
                    # so a retry never re-sends already donated pages.
                    if self.faults is not None:
                        self.faults.raise_if("device.step")
                    with self._mesh_ctx(), tr.span("serve.dispatch"):
                        toks_dev, pages = step_fn(
                            self.params,
                            jnp.asarray(tokens),
                            pool.pages,
                            pool.block_tables.copy(),
                            lens_op,
                            qlens,
                            np.int32(
                                self.order_ctl.effective_group(
                                    pool.blocks_per_seq
                                )
                            ),
                            temps.copy(),
                            seeds.copy(),
                            bases,
                        )
                    if tiered and pool.fetch_backlog():
                        # Overlap the prefetch with the in-flight step: the
                        # async device_put H2D copies queue up behind the
                        # dispatched step, and the np.asarray force below
                        # only blocks on the step's own outputs. Staged rows
                        # are spliced at a later boundary — never into the
                        # pages this step is reading.
                        with tr.span("serve.prefetch", overlapped=True):
                            for i in pool.suspended_slots():
                                pool.issue_fetches(
                                    i, self.prefetch_depth, overlapped=True
                                )
                    with tr.span("serve.wait_tokens"):
                        toks = np.asarray(toks_dev)
                    return toks, pages

                with tr.span(
                    "serve.device_step", width=width, rows=len(plan),
                    tokens=planned,
                ):
                    try:
                        toks, pages = dispatch()
                    except StepFault:
                        self._m_retries.inc()
                        tr.instant("serve.step_retry", step=step)
                        try:
                            toks, pages = dispatch()
                        except StepFault:
                            for it in plan:
                                if sched.slots[it.slot] is not None:
                                    finish(it.slot, "failed")
                            step += 1
                            continue
                with tr.span("serve.commit"):
                    pool.update_pages(pages)
                    cc = self.compiled_step_count()
                    if cc > last_cc:
                        tr.instant("serve.compile", width=width, variants=cc)
                        self._m_compiles.inc(cc - last_cc)
                        last_cc = cc
                    step += 1
                    n_steps += 1
                    n_wide += width > 1
                    self._m_tok_decode.inc(n_decode)
                    self._m_tok_prefill.inc(n_prefill)
                    self._m_kv_tok.inc(n_kv)
                    self._m_qk_pairs.inc(n_qk)
                    (self._m_steps_wide if width > 1 else self._m_steps_narrow).inc()
                    for it in plan:
                        st = sched.slots[it.slot]
                        pool.advance(it.slot, it.q_len)
                        if it.is_prefill:
                            st.prompt_pos += it.q_len
                            if not it.finishes_prompt:
                                continue
                            # Prompt complete: publish its frozen pages for future
                            # admissions to adopt, then take the first sample.
                            pool.register_prompt(it.slot, st.prompt)
                        if it.n_draft == 0:
                            tok = int(toks[it.slot, it.q_len - 1])
                            if id(st.request) not in first_t:
                                first_t[id(st.request)] = time.perf_counter()
                            counts[it.slot] += 1
                            cur[it.slot] = tok
                            if st.record(tok):
                                finish(it.slot)
                            continue
                        # Speculative verification row: the chunk was [cur,
                        # d_1..d_K]; target t_i = toks[slot, i] is the token the
                        # sequential stream would sample after absorbing the
                        # first i drafts. Accept the longest prefix d_1..d_a
                        # with d_{i+1} == t_i, emit t_0..t_a (the bonus token t_a
                        # rides for free), stopping early at EOS / new_limit as
                        # a sequential stream would; then roll the uncommitted
                        # chunk tail back out of the cache. The row's sample
                        # count advances by exactly the tokens emitted — the
                        # PRNG-stream guarantee that keeps sampled runs bitwise
                        # identical to non-speculative serving.
                        d = drafts.get(it.slot, [])[: it.n_draft]
                        k = len(d)
                        a = 0
                        while a < k and d[a] == int(toks[it.slot, a]):
                            a += 1
                        emitted = 0
                        finished = False
                        for p in range(a + 1):
                            tok = int(toks[it.slot, p])
                            if id(st.request) not in first_t:
                                first_t[id(st.request)] = time.perf_counter()
                            emitted += 1
                            cur[it.slot] = tok
                            if st.record(tok):
                                finished = True
                                break
                        counts[it.slot] += emitted
                        n_roll = it.q_len - emitted
                        if n_roll and not finished:
                            pool.rollback(it.slot, n_roll)
                        accepted = emitted - 1
                        tally["draft"] += k
                        tally["accept"] += accepted
                        tally["roll"] += k - accepted
                        self._m_draft_tok.inc(k)
                        self._m_accept_tok.inc(accepted)
                        self._m_rollback_tok.inc(k - accepted)
                        if finished:
                            finish(it.slot)
                    if self.faults is not None and self.faults.fired_this_step:
                        # Every injected fault is followed by a full pool
                        # consistency audit at the very step that absorbed it.
                        pool.check_invariants()
                    pool.emit_gauges()
                    # Widest decode/verify chunk of this step (K+1 under
                    # speculative decoding, 1 otherwise): the LLC models must
                    # see the query width each KV sweep is amortized over.
                    step_q = max(
                        (it.q_len for it in plan if not it.is_prefill), default=1
                    )
                    if self.order_ctl is not None and self.order_ctl.enabled:
                        # Adaptation drives its own sampling cadence (the
                        # decision needs a fresh reading, not a stale gauge).
                        if self.order_ctl.maybe_adapt(
                            n_steps, pool, self.llc, step_q=step_q
                        ):
                            tr.instant(
                                "serve.order_switch",
                                order=self.order_ctl.order.value,
                                step=n_steps,
                            )
                    elif self.llc is not None:
                        self.llc.maybe_sample(n_steps, pool, step_q=step_q)
            self._m_step_time.observe(time.perf_counter() - t_iter)
            if self._log_every and n_steps and n_steps % self._log_every == 0:
                self._log_stats_line(n_steps, pool, sched)

        # A drained stream is definitionally un-paused: the loop can exit
        # right after the final retirement, before any boundary recomputes
        # the watermark, and the gauge must not stay latched at 1.
        self._m_admit_paused.set(0.0)
        # Deterministic work counters for benches / CI trend lines (wall
        # clock on a shared CI box is noisy; step counts are not). Typed
        # snapshot of this stream; cumulative totals live in the registry.
        by_status: dict[str, int] = {}
        for res in results.values():
            by_status[res.status] = by_status.get(res.status, 0) + 1
        self.last_stats = StepStats(
            mixed_steps=n_steps,
            wide_steps=n_wide,
            pages_adopted=pool.shared_hits,
            prompt_tokens_adopted=pool.shared_tokens,
            cow_forks=pool.cow_forks,
            preemptions=tally["preempt"],
            restore_tokens=tally["restore"],
            shed=by_status.get("shed", 0),
            deadline_miss=by_status.get("deadline", 0),
            cancelled=by_status.get("cancelled", 0),
            failed=by_status.get("failed", 0),
            spills=getattr(pool, "spills", 0),
            tier_fetches=getattr(pool, "fetches", 0),
            prefetch_hits=getattr(pool, "prefetch_hits", 0),
            prefetch_wasted=getattr(pool, "prefetch_wasted", 0),
            draft_tokens=tally["draft"],
            accepted_tokens=tally["accept"],
            rollback_tokens=tally["roll"],
        )
        return [results[id(r)] for r in requests]

    def _log_stats_line(self, n_steps: int, pool, sched) -> None:
        """Periodic one-line operational summary (launchers enable it)."""
        v = self.obs.value
        spec = ""
        if self.drafter is not None:
            drafted = v("serve.spec.draft_tokens")
            acc = v("serve.spec.accepted_tokens")
            spec = (
                f" draft={drafted:.0f} accept={acc:.0f}"
                f" ({acc / drafted:.0%})" if drafted else " draft=0"
            )
        print(
            f"[serve] step {n_steps}: "
            f"queue={len(sched.waiting)} active={len(sched.active_slots())} "
            f"tokens dec/pre={v('serve.step.tokens', kind='decode'):.0f}"
            f"/{v('serve.step.tokens', kind='prefill'):.0f} "
            f"gen={v('serve.tokens.generated'):.0f} "
            f"pool free={pool.alloc.free_count} "
            f"occ={v('pool.occupancy_frac'):.0%} "
            f"adopted={pool.shared_hits} cow={pool.cow_forks}"
            f"{spec}"
        )

    def _admit(
        self,
        req: Request,
        slot: int,
        sched,
        pool,
        temps,
        seeds,
        counts,
        idx: int,
        prior: Optional[list] = None,
    ):
        """Admit ``req`` into ``slot``; returns the placed ``Slot`` or None
        if the pool lacks pages.

        No prefill happens here — the prompt's non-shared tokens run
        through the mixed step as chunks. The pool adopts any registered
        shared prefix (its KV is already resident), so ``prompt_pos``
        starts past the adopted tokens.

        ``prior`` (a preempted request's generated-so-far) turns admission
        into a *restore*: the effective prompt becomes prompt + prior —
        re-prefilled chunk-wise through the same compiled mixed step, no
        restore kernel — the slot's generated list is pre-seeded with the
        prior tokens (so ``new_limit`` and EOS accounting continue, not
        restart), and the sampling count resumes at ``len(prior)``. Row
        PRNG keys depend only on (engine seed, request seed, count), never
        on the slot or the step, so the restored stream is bitwise the
        uninterrupted one — for greedy and sampled rows alike.
        """
        cap = self._cap
        prompt = np.asarray(req.tokens, np.int32)[-cap:]
        if len(prompt) == 0:
            prompt = np.full((1,), self.eos, np.int32)  # empty prompt -> 1 pad
        new_limit = max(0, min(req.max_new_tokens, cap - len(prompt) + 1))
        if new_limit == 0:
            # Nothing to emit — resolve without consuming pages.
            st = sched.place(slot, req, eos_id=self._eos_for(req), new_limit=0)
            st.done = True
            return st
        prior = list(prior) if prior else []
        if prior:
            # len(prompt+prior) <= len(prompt) + new_limit - 1 <= cap by the
            # new_limit clamp above, so the restore prompt always fits.
            full = np.concatenate([prompt, np.asarray(prior, np.int32)])
        else:
            full = prompt
        shared = pool.admit(slot, full, new_limit - len(prior))
        if shared is None:
            return None
        st = sched.place(
            slot,
            req,
            eos_id=self._eos_for(req),
            new_limit=new_limit,
            prompt=full,
            prompt_pos=shared,
        )
        st.generated = prior
        st.n_prior = len(prior)  # prompt already carries the prior tokens —
                                 # the committed stream for drafters is
                                 # prompt + generated[n_prior:]
        temps[slot] = req.temperature
        seeds[slot] = self._seed_for(req, idx)
        counts[slot] = len(prior)
        return st
