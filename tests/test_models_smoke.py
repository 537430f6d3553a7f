"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates a REDUCED same-family config and runs one forward/train
step on CPU, asserting output shapes + no NaNs. Full configs are exercised
only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, all_configs, get_config
from repro.models import build_model
from repro.train.optimizer import make_optimizer
from repro.configs.base import TrainConfig

ARCHS = sorted(all_configs().keys())


def _batch_for(cfg, lm, seed=0):
    spec = lm.input_specs(SHAPES["train_4k"], reduced=True)
    key = jax.random.PRNGKey(seed)
    batch = {}
    for k, v in spec.items():
        if v.dtype == jnp.int32:
            batch[k] = jax.random.randint(key, v.shape, 0, cfg.vocab)
        else:
            batch[k] = jax.random.normal(key, v.shape, v.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_loss_shapes_no_nan(arch):
    cfg = all_configs()[arch].reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, lm)
    loss, metrics = jax.jit(lm.loss)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), (arch, float(loss))
    assert float(metrics["tokens"]) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step_updates_params(arch):
    cfg = all_configs()[arch].reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, lm)
    init, update = make_optimizer(TrainConfig(lr=1e-3, warmup_steps=0))
    opt = init(params)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm.loss(p, batch), has_aux=True
        )(params)
        new_params, new_opt, stats = update(grads, opt, params)
        return new_params, new_opt, loss, stats

    new_params, _, loss, stats = step(params, opt, batch)
    assert np.isfinite(float(loss))
    assert float(stats["grad_norm"]) > 0
    # at least one leaf moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
    )
    assert moved, arch


@pytest.mark.parametrize(
    "arch",
    [
        "deepseek-7b",
        "deepseek-v2-lite",
        "olmoe-1b-7b",
        "mixtral-8x7b",
        "qwen2-72b",
        "codeqwen1_5-7b",
        "mamba2-130m",
        "zamba2-2_7b",
        "seamless-m4t-medium",
        "phi-3-vision-4_2b",
        "llama3-405b",
    ],
)
def test_prefill_decode_consistency(arch):
    cfg = all_configs()[arch].reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    if cfg.family == "encdec":
        src = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
        batch = {"src_embeds": src, "tgt_tokens": toks}
        extend = lambda t: {"src_embeds": src, "tgt_tokens": t}
    elif cfg.family == "vlm":
        pe = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
        batch = {"tokens": toks, "prefix_embeds": pe}
        extend = lambda t: {"tokens": t, "prefix_embeds": pe}
    else:
        batch = {"tokens": toks}
        extend = lambda t: {"tokens": t}
    logits, caches = jax.jit(lambda p, b: lm.prefill(p, b, 48))(params, batch)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None]
    lg, caches = jax.jit(lm.decode_step)(params, nxt, caches)
    ext = jnp.concatenate([toks, nxt], 1)
    logits2, _ = jax.jit(lambda p, b: lm.prefill(p, b, 48))(params, extend(ext))
    np.testing.assert_allclose(
        np.asarray(lg[:, -1]), np.asarray(logits2[:, -1]), atol=1e-3, rtol=1e-3
    )


def test_sawtooth_vs_cyclic_configs_agree():
    """The paper's schedule is output-preserving at the model level too."""
    base = get_config("deepseek-7b").reduced()
    lm_s = build_model(base.with_(attn_order="sawtooth"))
    lm_c = build_model(base.with_(attn_order="cyclic"))
    params = lm_s.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, base.vocab)}
    l1, _ = jax.jit(lm_s.loss)(params, batch)
    l2, _ = jax.jit(lm_c.loss)(params, batch)
    assert abs(float(l1) - float(l2)) < 1e-5
