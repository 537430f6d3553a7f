"""Work counts and peaks: parameter counts against the program's own
initialisation shapes, causal FLOPs against a brute-force count, and the
two shares that read a configuration's counts from its reference."""

import jax
import numpy as np
import pytest

from bench import harness, peaks, serve, work
from bench import trace as T

CONFIGS = ["deepseek-7b", "olmoe-1b-7b"]


def _leaves(cfg, only=None):
    from repro.models import build_model

    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return sum(int(np.prod(x.shape)) for path, x in flat
               if only is None or only(jax.tree_util.keystr(path)))


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_models_leaves(name):
    model = harness.load_config(name)["model"]
    cfg = serve.model_config(model)
    total, active = work.param_counts(model)
    assert total == _leaves(cfg)
    matmul_total, _ = work.matmul_params(model)
    assert matmul_total == _leaves(cfg, only=lambda p: "embed" not in p and "scale" not in p)
    if cfg.moe is None:
        assert active == total
    else:
        per_expert = 3 * cfg.d_model * cfg.moe.d_ff_expert
        assert total - active == cfg.n_layers * (cfg.moe.num_experts - cfg.moe.top_k) * per_expert


def test_deepseek_7b_has_6_910_billion_parameters():
    total, _ = work.param_counts(harness.load_config("deepseek-7b")["model"])
    assert round(total / 1e9, 3) == 6.910


@pytest.mark.parametrize("start,n", [(0, 1), (0, 7), (5, 1), (3, 9), (17, 4)])
def test_causal_pairs_match_a_brute_force_count(start, n):
    brute = sum(1 for q in range(start, start + n) for k in range(start + n) if k <= q)
    assert work.causal_pairs(start, n) == brute


def test_attention_flops_match_a_brute_force_count():
    m = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2, "d_ff": 64, "vocab": 16}
    s, hd = 6, 8
    # QK^T and PV: 2 FLOPs per multiply-accumulate, hd MACs per pair each.
    brute = sum(2 * hd + 2 * hd for q in range(s) for k in range(s) if k <= q) * 4 * 2
    assert work.attn_flops_per_pair(m) * work.causal_pairs(0, s) == brute
    flops, byts = work.flash_fwd_cost(1, 4, 2, s, hd)
    assert flops == brute / 2  # one layer
    bflops, bbytes = work.flash_bwd_cost(1, 4, 2, s, hd)
    assert bflops == 2.5 * flops and bbytes > byts > 0
    assert work.train_flops(m, 1, s) == 3 * (2 * work.matmul_params(m)[1] * s + brute)


def test_unknown_device_kind_raises():
    assert peaks.peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


def _readings(model, config):
    """Readings of a hand-made window: 51 s on one v5e, 4,000 decode and
    2,500 prefill tokens, and one step of the paged kernel, 7.331 ms over
    550 keys."""
    counts = {("serve.step.tokens", "decode"): 4000.0, ("serve.step.tokens", "prefill"): 2500.0,
              ("serve.step.kv_tokens", None): 550.0}
    ops = [T.Op("paged_flash_decode_fwd.7", "bf16[128,8,128]", 1000, 7_332_000)]
    return harness.Readings(model, {}, 1, peaks.peak_for("TPU v5 lite"), 51.0, [],
                            lambda name, **lb: counts[(name, lb.get("kind"))],
                            [(117, 181), (338, 20), (4064, 2), (9, 0)],
                            T.Trace({"/device:TPU:0": ops}, [], (0, 51_000_000_000)), config)


@pytest.mark.parametrize("name", CONFIGS)
def test_shares_read_the_counts_they_read_before_references_gave_them(name):
    config = harness.load_config(name)
    m = config["model"]
    r = _readings(m, config)
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    # mfu.serve: 2 per active matmul parameter per token, causal QK^T and PV once.
    pairs = sum(work.causal_pairs(0, p + n - 1) for p, n in r.requests if n)
    flops = 2.0 * work.matmul_params(m)[1] * 6500.0 + 4 * m["n_layers"] * m["n_heads"] * hd * pairs
    assert harness.load_reader("mfu.serve")(r) == 100.0 * flops / (51.0 * 1 * 197e12)
    # paged_attn_roofline: K and V of every key attended, over all layers, in bf16.
    byts = 550.0 * m["n_layers"] * 2 * m["n_kv_heads"] * hd * 2
    want = 100.0 * byts / 1 / (T.op_seconds(r.trace, "paged_flash_decode_fwd") * 819e9)
    assert harness.load_reader("paged_attn_roofline")(r) == want


def test_readings_without_a_configuration_have_no_reference():
    r = _readings(harness.load_config("deepseek-7b")["model"], None)
    with pytest.raises(ValueError, match="no configuration"):
        harness.load_reader("mfu.serve")(r)
