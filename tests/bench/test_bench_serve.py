"""Each serve cell's path at the program's reduced size on the CPU, through
the harness's own run (the look for a chip skipped): a sound run is
correct, and the same run with the timed path broken underneath is not.
Each cell's plain reference, loaded by name and used only through its
contract (``bench/reference/__init__.py``), makes the program's weights from
the seed and agrees with its forward pass; the control (fp8) fails the
cell's limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, serve, traffic

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
SEED = 2**31 + 11
# Equal to the last f32 bit or one ulp off: the program draws the layers
# under vmap, and XLA may fuse the scaling of the normal draw differently
# there. The served weights are bf16, which absorbs that. A reference
# states another tolerance as WEIGHT_RTOL, with its reason.
WEIGHT_RTOL = 3e-7
# The program's forward at HIGHEST precision against the reference's
# float32 one. A reference states another tolerance as LOGITS_TOL, with its
# reason.
LOGITS_TOL = 2e-4


def reduced(cell):
    return serve.model_config(harness.load_config(cell["config"])["model"]).reduced()


def reference(cell):
    return harness.load_reference(harness.load_config(cell["config"])["reference"])


def run_cell(name, *, traced=False):
    import time

    cell = harness.find_cell(SPEC, name)
    return serve.run(cell=cell, spec=SPEC, seed=SEED, seconds=0.0, traced=traced,
                     t_start=time.perf_counter(), devices=jax.devices(), cfg=reduced(cell),
                     bursts=1)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_at_reduced_size(name):
    line = run_cell(name)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in harness.metrics_for(SPEC, name, "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and list(line)[-1] == "checks"
    assert line["checks"]["tokens_compared"]["value"] >= line["checks"]["tokens_compared"]["limit"]


def test_traced_run_reports_per_layer_metrics():
    line = run_cell("ds7b-chat", traced=True)
    assert line["correct"]
    got = set(line["metrics"])
    # Spans and counters exist on any backend; the device trace only on a chip.
    assert {"host_ms_per_step", "narrow_step_ms", "wide_step_ms", "padded_slot_frac"} <= got
    assert 0 < line["metrics"]["padded_slot_frac"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line


def _a_gap_fails(line):
    return any(c["value"] > c["limit"] for name, c in line["checks"].items() if "gap" in name)


def _state_unchanged(orig):
    def step_logits(self, params, tokens, pages, *rest):
        logits, _ = orig(self, params, tokens, pages, *rest)
        return logits, pages  # the KV the step computed is thrown away
    return step_logits


def _rows_left_out(orig):
    def step_logits(self, params, tokens, pages, *rest):
        logits, new = orig(self, params, tokens, pages, *rest)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), new  # the batch's second half is not computed
    return step_logits


@pytest.mark.parametrize("fault", [_state_unchanged, _rows_left_out])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    from repro.serve import ServeEngine

    monkeypatch.setattr(ServeEngine, "step_logits", fault(ServeEngine.step_logits))
    line = run_cell("ds7b-chat")
    assert not line["correct"]
    assert _a_gap_fails(line)


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serve import ServeEngine

    orig = ServeEngine._mixed_step_fn

    def mixed_step_fn(self):
        step = orig(self)
        vocab = self.lm.cfg.vocab

        def altered(*args):
            toks, pages = step(*args)
            return (toks + 1) % vocab, pages  # every sampled token moved by one id
        return altered

    monkeypatch.setattr(ServeEngine, "_mixed_step_fn", mixed_step_fn)
    line = run_cell("olmoe-code")
    assert not line["correct"]
    assert _a_gap_fails(line)


@pytest.mark.parametrize("name", CELLS)
def test_reference_makes_the_programs_weights(name):
    from repro.models import build_model

    cell = harness.find_cell(SPEC, name)
    ref, cfg = reference(cell), reduced(cell)
    params = build_model(cfg).init(jax.random.PRNGKey(SEED))
    rtol = getattr(ref, "WEIGHT_RTOL", WEIGHT_RTOL)
    names = []
    for what, ours, theirs in ref.weight_pairs(serve.model_dict(cfg), SEED, params):
        np.testing.assert_allclose(ours, theirs, rtol=rtol, atol=0, err_msg=what)
        names.append(what)
    assert names and len(set(names)) == len(names)


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_the_programs_forward(name):
    from repro.models import build_model

    cell = harness.find_cell(SPEC, name)
    ref, cfg = reference(cell), reduced(cell).with_(attn_impl="reference")
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(0)
    s = 24
    tokens = rng.integers(traffic.FIRST_ID, cfg.vocab, size=(2, s)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm.prefill(params, {"tokens": jnp.asarray(tokens)}, s)
    got = ref.logits(serve.model_dict(cfg), SEED, tokens)
    assert got.shape == (2, s, cfg.vocab) and got.dtype == np.float32
    tol = getattr(ref, "LOGITS_TOL", LOGITS_TOL)
    np.testing.assert_allclose(got[:, -1], want[:, 0], rtol=tol, atol=tol)


def _program_configs():
    from repro.configs.registry import get_config

    bench = [serve.model_config(harness.load_config(c["name"])["model"]) for c in SPEC["configs"]]
    return bench + [get_config("mamba2-130m"), get_config("zamba2-2_7b")]


@pytest.mark.parametrize("cfg", _program_configs(), ids=lambda c: c.name)
def test_model_config_turns_every_sub_config_back_into_its_dataclass(cfg):
    back = serve.model_config(serve.model_dict(cfg))
    assert back == cfg
    assert type(back.moe) is type(cfg.moe) and type(back.ssm) is type(cfg.ssm)


@pytest.mark.parametrize("name", CELLS)
def test_the_fp8_control_fails_the_cells_limit(name):
    """The control: the reference in float8 e4m3 in the program's place. On
    the prompts and tokens that the program, run in bf16 as the
    configuration states, served for one burst, the token the control puts
    first lies further below the float32 reference's best than the cell's
    limit allows, and at least three times as far as the program's."""
    cell = harness.find_cell(SPEC, name)
    cfg = reduced(cell).with_(dtype="bfloat16", param_dtype="bfloat16")
    mix = traffic.load_mix(cell["traffic"])
    eng = serve.build_engine(cfg, mix, SEED)
    limits = harness.load_limits(name)
    w = serve.run_window(eng, mix, cfg.vocab, SEED, 0.0,
                         bursts=traffic.bursts_serving(mix, limits["sample_tokens"]))
    del eng
    sample = serve.draw_sample(w, SEED, limits["sample_tokens"])
    gaps = serve.reference_gaps(harness.load_config(cell["config"]), serve.model_dict(cfg),
                                SEED, sample, mix["chunk"], modes=("f32", "fp8"))
    assert serve.judge(gaps, limits)[0]
    assert not serve.judge(gaps, limits, prefix="fp8.")[0]
    for n in limits["checks"]:
        assert gaps["fp8." + n] >= 3 * gaps[n]
