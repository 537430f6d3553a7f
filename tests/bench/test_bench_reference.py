"""The llama-style reference (``bench/reference/decoder.py``) keeps the
contract every reference keeps: it pairs every matrix it draws with the
program's, and its sizing probes compile."""

import jax
import pytest

from bench import harness, serve

DECODER = harness.load_reference("decoder")
CONFIGS = ["deepseek-7b", "olmoe-1b-7b"]


def _reduced(name):
    return serve.model_config(harness.load_config(name)["model"]).reduced()


@pytest.mark.parametrize("name", CONFIGS)
def test_decoder_pairs_every_matrix_it_draws(name):
    from repro.models import build_model

    cfg = _reduced(name)
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    got = [what for what, _, _ in DECODER.weight_pairs(serve.model_dict(cfg), 7, params)]
    per_layer = ["wq", "wk", "wv", "wo", "wg", "wu", "wd"] + (["router"] if cfg.moe else [])
    assert got == ["embed", "lm_head"] + [f"layer {i} {k}" for i in range(cfg.n_layers)
                                          for k in per_layer]


@pytest.mark.parametrize("name", CONFIGS)
def test_decoder_compile_probes_compile(name):
    model = serve.model_dict(_reduced(name))
    probes = list(DECODER.compile_probes(model, 32))
    assert [p[0] for p in probes] == ["reference layer weights", "reference layer, f32",
                                      "reference layer, fp8", "reference logits"]
    for _, fn, *shapes in probes:
        jax.jit(fn).lower(*shapes).compile()
