"""Pallas SSD kernel (interpret mode) vs sequential oracle: shape sweep,
state chaining, dtype, model-level parity, grads through custom_vjp."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.ref import ssd_ref
from repro.kernels.ssd import ssd_fwd
from repro.models import build_model


def _inputs(seed, B, S, H, P, N, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    b = jax.random.normal(ks[3], (B, S, N), dtype)
    c = jax.random.normal(ks[4], (B, S, N), dtype)
    return x, dt, a, b, c


SWEEP = [
    # B, S, H, P, N, chunk
    (2, 96, 3, 8, 16, 32),
    (1, 128, 2, 64, 128, 64),
    (2, 100, 4, 16, 32, 32),   # S not a chunk multiple
    (1, 64, 1, 8, 8, 64),      # single chunk
]


@pytest.mark.parametrize("case", SWEEP)
def test_kernel_matches_oracle(case):
    B, S, H, P, N, chunk = case
    x, dt, a, b, c = _inputs(sum(case), B, S, H, P, N)
    y_ref, s_ref = ssd_ref(x, dt, a, b, c)
    y, s = ssd_fwd(x, dt, a, b, c, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=3e-4, rtol=3e-4)


def test_state_chaining():
    x, dt, a, b, c = _inputs(0, 2, 128, 4, 16, 32)
    y_ref, s_ref = ssd_ref(x, dt, a, b, c)
    y1, s1 = ssd_fwd(x[:, :64], dt[:, :64], a, b[:, :64], c[:, :64], chunk=32, interpret=True)
    y2, s2 = ssd_fwd(
        x[:, 64:], dt[:, 64:], a, b[:, 64:], c[:, 64:], chunk=32, interpret=True,
        init_state=s1,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_ref), atol=3e-4, rtol=3e-4
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_ref), atol=3e-4, rtol=3e-4)


def test_bf16_inputs():
    x, dt, a, b, c = _inputs(1, 1, 64, 2, 16, 16, jnp.bfloat16)
    y_ref, _ = ssd_ref(x, dt, a, b, c)
    y, _ = ssd_fwd(x, dt, a, b, c, chunk=32, interpret=True)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), atol=5e-2, rtol=5e-2
    )


def test_ops_dispatch_and_grads():
    x, dt, a, b, c = _inputs(2, 1, 64, 2, 8, 16)

    def loss(impl):
        def f(x, b, c):
            y, s = ops.ssd(x, dt, a, b, c, chunk=32, impl=impl)
            return (y**2).sum() + (s**2).sum()
        return f

    g_pallas = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(x, b, c)
    g_xla = jax.grad(loss("xla"), argnums=(0, 1, 2))(x, b, c)
    for gp, gx in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gx), atol=2e-3, rtol=2e-3)


def test_model_level_parity():
    cfg = get_config("mamba2-130m").reduced()
    lm_x = build_model(cfg.with_(ssd_impl="xla"))
    lm_p = build_model(cfg.with_(ssd_impl="pallas_interpret"))
    params = lm_x.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)}
    lx, _ = jax.jit(lm_x.loss)(params, batch)
    lp, _ = jax.jit(lm_p.loss)(params, batch)
    assert abs(float(lx) - float(lp)) < 1e-4


def test_kernel_is_named():
    """The kernel carries a stable name for profiler traces. (The TPU
    lowering has no ``cumsum`` yet, so unlike the attention kernels it
    cannot be compiled for a described chip.)"""
    x, dt, a, b, c = _inputs(0, 1, 64, 2, 8, 16)
    jaxpr = jax.make_jaxpr(lambda *z: ssd_fwd(*z, chunk=32, interpret=True))(x, dt, a, b, c)

    def names(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                yield str(e.params["name"])
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    yield from names(v.jaxpr)

    assert list(names(jaxpr.jaxpr)) == ["ssd_fwd"]
