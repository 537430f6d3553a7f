"""MoE: routing invariants, capacity behavior, dropless == capacity@no-drop."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import moe


def _setup(cap_factor=1.25, seed=0):
    cfg = get_config("olmoe-1b-7b").reduced()
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=cap_factor))
    p = moe.moe_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, cfg.d_model))
    return cfg, p, x


def test_output_shape_and_finite():
    cfg, p, x = _setup()
    y, aux = moe.moe_apply(p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.0


def test_dropless_matches_high_capacity():
    """With capacity high enough that nothing drops, the two paths agree."""
    cfg, p, x = _setup(cap_factor=100.0)
    y_cap, _ = moe.moe_apply(p, cfg, x, dropless=False)
    y_free, _ = moe.moe_apply(p, cfg, x, dropless=True)
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_free), atol=2e-4, rtol=2e-4)


def test_low_capacity_drops_but_stays_finite():
    cfg, p, x = _setup(cap_factor=0.25)
    y, aux = moe.moe_apply(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()


def test_capacity_respected():
    cfg, p, x = _setup()
    t = x.shape[0] * x.shape[1]
    cap = moe.expert_capacity(t, cfg)
    assert cap >= t * cfg.moe.top_k // cfg.moe.num_experts
    assert cap % 8 == 0


def test_token_permutation_equivariance_dropless():
    """Dropless MoE is a per-token map: permuting tokens permutes outputs."""
    cfg, p, x = _setup()
    xf = x.reshape(1, -1, x.shape[-1])
    perm = jax.random.permutation(jax.random.PRNGKey(9), xf.shape[1])
    y1, _ = moe.moe_apply(p, cfg, xf, dropless=True)
    y2, _ = moe.moe_apply(p, cfg, xf[:, perm], dropless=True)
    np.testing.assert_allclose(
        np.asarray(y1[:, perm]), np.asarray(y2), atol=2e-4, rtol=2e-4
    )


def test_grad_flows_through_router_and_experts():
    cfg, p, x = _setup()

    def loss(p):
        y, aux = moe.moe_apply(p, cfg, x)
        return (y**2).sum() + aux

    g = jax.grad(loss)(p)
    for name in ("router", "w_gate", "w_up", "w_down"):
        leaf = g[name]["w"] if isinstance(g[name], dict) else g[name]
        assert float(jnp.abs(leaf).sum()) > 0.0, name


# --- in-place expert reads on the serve layer scan --------------------------

_N_LAYERS = 4
_SAME_8 = (0, 1, 17, 30, 31, 45, 62, 63)  # both ends of the layer's groups


def _stacked_setup(routing):
    """Reduced olmoe-1b-7b widths with its published routing (64 experts,
    top-8) in bf16, the serving dtype, over a stack of ``_N_LAYERS`` layers.
    ``routing`` biases each layer's router: ``some_empty`` shuts out every
    odd expert, ``same_8`` sends every token to the experts of ``_SAME_8``."""
    cfg = get_config("olmoe-1b-7b").reduced()
    cfg = cfg.with_(
        moe=dataclasses.replace(cfg.moe, num_experts=64, top_k=8),
        dtype="bfloat16",
        param_dtype="bfloat16",
    )
    keys = jax.random.split(jax.random.PRNGKey(3), _N_LAYERS)
    stack = jax.vmap(lambda k: moe.moe_init(k, cfg))(keys)
    e = cfg.moe.num_experts
    if routing == "some_empty":
        bias = jnp.where(jnp.arange(e) % 2 == 1, -1e4, 0.0)
    else:
        bias = jnp.full((e,), -1e4).at[jnp.asarray(_SAME_8)].set(
            1e3 + jnp.arange(len(_SAME_8), dtype=jnp.float32)
        )
    stack["router"]["b"] = jnp.broadcast_to(bias, (_N_LAYERS, e))
    return cfg, stack


def _ulp_distance(a, b):
    """Distance in bf16 units in the last place, element by element."""
    def ordered(x):
        bits = np.asarray(x).view(np.uint16).astype(np.int32)
        return np.where(bits >= 0x8000, 0x8000 - bits, bits)

    return np.abs(ordered(a) - ordered(b))


@pytest.mark.parametrize("routing", ["some_empty", "same_8"])
@pytest.mark.parametrize("n_tokens", [1, 2, 16, 37])
@pytest.mark.parametrize("layer", [0, 1, _N_LAYERS - 1], ids=["first", "middle", "last"])
def test_dropless_in_place_matches_layer_slice(layer, n_tokens, routing):
    """Reading layer ``layer``'s experts in place from the whole [L, E, ...]
    stacks equals the dropless path on the per-layer slice ``w[layer]``."""
    cfg, stack = _stacked_setup(routing)
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, cfg.d_model))
    x = x.astype(jnp.bfloat16)
    sliced = jax.tree.map(lambda a: a[layer], stack)
    whole = {**sliced, **{k: stack[k] for k in moe.EXPERT_STACKS}}

    logits = x.reshape(n_tokens, -1).astype(jnp.float32) @ sliced["router"]["w"]
    _, sel = jax.lax.top_k(logits + sliced["router"]["b"], cfg.moe.top_k)
    used = set(np.asarray(sel).ravel().tolist())
    if routing == "same_8":
        assert used == set(_SAME_8)
    else:
        assert used and all(i % 2 == 0 for i in used)

    want, _ = _dropless_on_slice(sliced, cfg, x)
    got, _ = _dropless_in_place(whole, cfg, x, jnp.int32(layer))
    assert got.dtype == want.dtype == jnp.bfloat16
    assert _ulp_distance(got, want).max() <= 1


@functools.partial(jax.jit, static_argnums=1)
def _dropless_on_slice(p, cfg, x):
    return moe.moe_apply(p, cfg, x, dropless=True)


@functools.partial(jax.jit, static_argnums=1)
def _dropless_in_place(p, cfg, x, layer):
    return moe.moe_apply(p, cfg, x, dropless=True, layer=layer)


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-7b"])
def test_serve_scan_slices_no_expert_stack(arch):
    """The serve decode scan takes dropless expert stacks whole (closed
    over, not sliced per layer) and scans a dense model's params as before."""
    from repro.models import build_model
    from repro.models import transformer as T

    cfg = get_config(arch).reduced()
    lm = build_model(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: T.init_cache(cfg, 2, 16))
    caches = jax.tree.map(
        lambda c: jax.ShapeDtypeStruct((cfg.n_layers, *c.shape), c.dtype), caches
    )
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(lm.decode_step)(params, tokens, caches).jaxpr
    (scan,) = [q for q in jaxpr.eqns if q.primitive.name == "scan"]
    n_consts = scan.params["num_consts"] + scan.params["num_carry"]
    scanned = {tuple(v.aval.shape) for v in scan.invars[n_consts:]}
    ffn = params["layers"]["ffn"]
    if cfg.moe is None:
        assert {tuple(a.shape) for a in jax.tree.leaves(ffn)} <= scanned
    else:
        stacks = {tuple(ffn[k].shape) for k in moe.EXPERT_STACKS}
        assert not stacks & scanned
        closed = {tuple(v.aval.shape) for v in scan.invars[: scan.params["num_consts"]]}
        assert stacks <= closed
        assert (cfg.n_layers,) in scanned  # the layer index rides the scan


# ---- DeepSeek-V2's expert layer: shared experts, unnormalised top-k, a chip's share


def _frozen_dropless(p, cfg, x):
    """The dropless path as it was before shares, shared experts and
    unnormalised weights were added: the oracle that ``experts_held=None``
    must match bit for bit."""
    m = cfg.moe
    dt = cfg.activation_dtype()
    b, s, d = x.shape
    t = b * s
    e, k = m.num_experts, m.top_k
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]["w"]
    top_logits, sel = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    e_flat = sel.reshape(-1)
    w_flat = weights.reshape(-1)
    order = jnp.argsort(e_flat)
    inv = jnp.argsort(order)
    x_sorted = jnp.repeat(xf, k, axis=0)[order].astype(dt)
    group_sizes = jnp.bincount(e_flat, length=e).astype(jnp.int32)
    g = jax.lax.ragged_dot(x_sorted, p["w_gate"].astype(dt), group_sizes)
    u = jax.lax.ragged_dot(x_sorted, p["w_up"].astype(dt), group_sizes)
    y_sorted = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"].astype(dt), group_sizes)
    y_flat = y_sorted[inv] * w_flat[:, None].astype(dt)
    return y_flat.reshape(t, k, d).sum(axis=1).reshape(b, s, d).astype(x.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dropless_without_a_share_is_bit_equal_to_before(dtype):
    cfg = get_config("olmoe-1b-7b").reduced().with_(dtype=dtype, param_dtype=dtype)
    assert cfg.moe.experts_held is None and cfg.moe.norm_topk_prob
    p = moe.moe_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, cfg.d_model)).astype(dtype)
    got, _ = jax.jit(lambda p, x: moe.moe_apply(p, cfg, x, dropless=True))(p, x)
    want = jax.jit(lambda p, x: _frozen_dropless(p, cfg, x))(p, x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _v2_cfg(held=None):
    """DeepSeek-V2's expert layer at small width: 8 routed experts, top-2,
    2 shared experts, weights not renormalised."""
    cfg = get_config("deepseek-v2-lite").reduced()
    return cfg.with_(moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=2, experts_held=held))


@pytest.mark.parametrize("dropless", [True, False], ids=["dropless", "capacity"])
def test_four_shares_and_the_shared_experts_add_up_to_the_whole_layer(dropless):
    """Four chips of an expert-parallel deployment, each holding 2 of the 8
    routed experts: every chip routes over all 8 and computes its own
    experts' part, and every chip computes the shared experts alike. The
    routed parts of the four shares, plus the shared experts once, equal
    the uncut layer. Chip i holds experts [2i, 2i + 2); the program holds
    ids [0, held), so chip i is the program with the router's columns and
    the expert stacks rolled by 2i."""
    whole_cfg = _v2_cfg()
    whole_cfg = whole_cfg.with_(moe=dataclasses.replace(whole_cfg.moe, capacity_factor=100.0))
    share_cfg = whole_cfg.with_(moe=dataclasses.replace(whole_cfg.moe, experts_held=2))
    p = moe.moe_init(jax.random.PRNGKey(7), whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 11, whole_cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.moe_apply(p, whole_cfg, x, dropless=dropless)
        shared = L.swiglu(p["shared"], x, dtype=whole_cfg.activation_dtype())
        parts = []
        for i in range(4):
            ids = (jnp.arange(8) + 2 * i) % 8  # chip i's experts first
            chip = {"router": {"w": p["router"]["w"][:, ids]},
                    **{n: p[n][ids[:2]] for n in moe.EXPERT_STACKS}}
            parts.append(moe.moe_apply(chip, share_cfg, x, dropless=dropless)[0])
    # float32 at HIGHEST precision: the same terms, summed in another order
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0  # each share computes a part


def test_unnormalised_weights_are_the_full_softmax_probabilities():
    cfg = _v2_cfg()
    p = moe.moe_init(jax.random.PRNGKey(9), cfg)
    x = jax.random.normal(jax.random.PRNGKey(10), (5, cfg.d_model))
    logits, sel, w = moe._route(p, cfg, x)
    probs = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(w), np.asarray(jnp.take_along_axis(probs, sel, -1)))
    assert (np.asarray(w.sum(-1)) < 1).all()
