"""Top-k routed MoE FFN (GShard/Mixtral-style), in two forms.

Training (``moe_apply``, ``dropless=False``) uses the capacity path: a
scatter into an (E, C, d) expert buffer and a gather back, fully static
shapes so it lowers cleanly under pjit; with experts sharded on the 'model'
axis GSPMD materializes the dispatch/combine as all-to-all-class
collectives (the dominant collective term for the MoE archs, see
EXPERIMENTS.md §Roofline). Aux losses: load-balance (Switch-style over full
softmax probs × dispatch fractions) + router z-loss; returned as a scalar
the caller folds into the training loss.

Serving (``dropless=True``, ``_moe_dropless``) drops no token: the routed
copies are sorted by expert and run through ``lax.ragged_dot`` grouped
GEMMs. On the serve layer scan the expert weights stay whole ([L, E, ...])
and each layer's experts are read in place from them (see
``_moe_dropless``); the aux loss is zero.

Both forms take the DeepSeek-V2 variants of ``MoEConfig``: shared experts
every token passes through, top-k weights left as probabilities over all
experts (``norm_topk_prob`` False), and one chip's share of the routed
experts (``experts_held``): the router scores every expert, and the
assignments to experts held elsewhere contribute nothing here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.context import constrain
from repro.models import layers as L

__all__ = ["moe_init", "moe_apply", "expert_capacity", "EXPERT_STACKS"]

# The expert weight leaves of a MoE FFN: [E, ...] per layer, [L, E, ...]
# stacked over a layer scan.
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = int(math.ceil(n_tokens * m.top_k / m.num_experts * m.capacity_factor))
    return max(8, -(-cap // 8) * 8)  # pad to a multiple of 8


def moe_init(key, cfg: ModelConfig) -> dict:
    """Router over all ``num_experts``, the held experts' stacks
    ([held, ...]) and, with shared experts, one dense SwiGLU ``shared`` of
    ``n_shared_experts * d_ff_expert``."""
    m = cfg.moe
    d, ff, e, held = cfg.d_model, m.d_ff_expert, m.num_experts, m.held
    kr, kg, ku, kd, *ks = L.split_keys(key, 5 if m.n_shared_experts else 4)
    pd = cfg.parameter_dtype()
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff)
    p = {
        "router": L.dense_init(kr, d, e, dtype=jnp.float32),
        "w_gate": (jax.random.normal(kg, (held, d, ff)) * s_in).astype(pd),
        "w_up": (jax.random.normal(ku, (held, d, ff)) * s_in).astype(pd),
        "w_down": (jax.random.normal(kd, (held, ff, d)) * s_out).astype(pd),
    }
    if m.n_shared_experts:
        fs = m.n_shared_experts * ff
        kg, ku, kd = L.split_keys(ks[0], 3)
        p["shared"] = {
            "w_gate": L.dense_init(kg, d, fs, dtype=pd),
            "w_up": L.dense_init(ku, d, fs, dtype=pd),
            "w_down": L.dense_init(kd, fs, d, dtype=pd),
        }
    return p


def _route(p: dict, cfg: ModelConfig, xf: jax.Array):
    """(router logits (T, E) f32, selected experts (T, k), their weights)."""
    m = cfg.moe
    logits = L.dense(p["router"], xf.astype(jnp.float32))
    top_logits, sel = jax.lax.top_k(logits, m.top_k)
    if m.norm_topk_prob:
        weights = jax.nn.softmax(top_logits, axis=-1)
    else:
        weights = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), sel, axis=-1)
    return logits, sel, weights


def _with_shared(p: dict, cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    """The routed experts' output ``y`` plus the shared experts' on ``x``."""
    if "shared" not in p:
        return y
    return y + L.swiglu(p["shared"], x, dtype=cfg.activation_dtype()).astype(y.dtype)


def moe_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    dropless: bool = False,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    dropless=True uses the sort + ``lax.ragged_dot`` grouped-GEMM path (no
    capacity, no token dropping) — the serving configuration; ``layer``
    selects its in-place read of whole expert stacks (``_moe_dropless``).
    Training uses the capacity path (GShard-style) whose static buffer shapes
    shard predictably under pjit.
    """
    if dropless:
        return _moe_dropless(p, cfg, x, layer=layer)
    m = cfg.moe
    dt = cfg.activation_dtype()
    b, s, d = x.shape
    t = b * s
    e, k, held = m.num_experts, m.top_k, m.held
    cap = expert_capacity(t, cfg)

    xf = x.reshape(t, d)
    logits, sel, weights = _route(p, cfg, xf)  # (T, E) f32 router; (T, k)
    probs = jax.nn.softmax(logits, axis=-1)
    weights = weights.astype(jnp.float32)

    # --- flat assignment stream (token-major priority) ---------------------
    e_flat = sel.reshape(-1)  # (T*k,)
    w_flat = weights.reshape(-1)
    oh = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)  # (T*k, E)
    pos_all = jnp.cumsum(oh, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, e_flat[:, None], axis=1)[:, 0]  # (T*k,)
    keep = (pos < cap).astype(jnp.float32)
    pos_c = jnp.minimum(pos, cap - 1)
    e_buf = e_flat
    if held < e:  # assignments to experts held elsewhere are not computed here
        keep = keep * (e_flat < held)
        e_buf = jnp.where(e_flat < held, e_flat, 0)

    # --- dispatch: scatter tokens into (held, C, d) buffers -----------------
    x_rep = jnp.repeat(xf, k, axis=0).astype(dt)  # (T*k, d)
    buf = jnp.zeros((held, cap, d), dt)
    buf = buf.at[e_buf, pos_c].add(x_rep * keep[:, None].astype(dt))
    buf = constrain(buf, "moe_buffer")

    # --- expert SwiGLU -------------------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))

    # --- combine: gather back, weight, reduce over k -------------------------
    y_flat = y_buf[e_buf, pos_c] * (w_flat * keep)[:, None].astype(dt)
    y = _with_shared(p, cfg, x, y_flat.reshape(t, k, d).sum(axis=1).reshape(b, s, d))

    # --- aux losses -----------------------------------------------------------
    me = probs.mean(axis=0)                                   # (E,) mean router prob
    ce = oh.astype(jnp.float32).mean(axis=0) * (1.0 / k) * e  # dispatch fraction
    load_balance = e * jnp.sum(me * ce) / e                   # Switch aux (≈1 when uniform)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = m.aux_loss_coef * load_balance + m.router_z_coef * z
    return y.astype(x.dtype), aux


def _moe_dropless(p: dict, cfg: ModelConfig, x: jax.Array, *, layer=None):
    """Dropless grouped-GEMM MoE (vLLM/MegaBlocks-style) via lax.ragged_dot.

    With ``layer`` None, ``p`` is one layer's FFN: ``w_gate``/``w_up``
    [E, d, ff] and ``w_down`` [E, ff, d]. With ``layer`` a traced int32
    layer index, those three are the whole stacks of every layer ([L, E, ...])
    and ``p["router"]`` is still layer ``layer``'s own. Each grouped GEMM then
    runs on the [L*E, ...] view of its stack (a reshape that is a bitcast),
    with the routed expert ids offset by ``layer * E``, so the group sizes are
    zero outside that layer's E groups. The TPU ``ragged_dot`` kernel visits
    only tiles of groups that have rows; taking a per-layer slice instead
    would copy all E experts of the layer, since the custom call cannot fuse
    a dynamic-slice operand.

    With a share of the experts held (``experts_held`` < E), the stacks hold
    ``held`` experts a layer and the groups are ``held`` a layer: the
    assignments to experts held elsewhere sort after this chip's and are
    counted in no group, so the grouped GEMMs never visit them.
    """
    m = cfg.moe
    dt = cfg.activation_dtype()
    b, s, d = x.shape
    t = b * s
    e, k, held = m.num_experts, m.top_k, m.held

    xf = x.reshape(t, d)
    _, sel, weights = _route(p, cfg, xf)

    e_flat = sel.reshape(-1)
    w_flat = weights.reshape(-1)
    if held < e:
        here = e_flat < held
        e_flat = jnp.where(here, e_flat, held)  # sorted after the held experts
    order = jnp.argsort(e_flat)  # stable in jnp
    inv = jnp.argsort(order)
    x_sorted = constrain(jnp.repeat(xf, k, axis=0)[order].astype(dt), "moe_tokens")
    w_gate, w_up, w_down = (p[name] for name in EXPERT_STACKS)
    if layer is None:  # one layer's experts: a stack of one
        w_gate, w_up, w_down, layer = w_gate[None], w_up[None], w_down[None], 0
    n = w_gate.shape[0] * held
    ids = e_flat + layer * held
    if held < e:
        ids = jnp.where(here, ids, n)  # in no group: bincount drops ids >= n
    group_sizes = jnp.bincount(ids, length=n).astype(jnp.int32)
    w_gate, w_up, w_down = (w.reshape(n, *w.shape[2:]) for w in (w_gate, w_up, w_down))

    g = jax.lax.ragged_dot(x_sorted, w_gate.astype(dt), group_sizes)
    u = jax.lax.ragged_dot(x_sorted, w_up.astype(dt), group_sizes)
    h = jax.nn.silu(g) * u
    y_sorted = jax.lax.ragged_dot(h, w_down.astype(dt), group_sizes)

    y_flat = y_sorted[inv] * w_flat[:, None].astype(dt)
    if held < e:  # rows in no group: whatever the grouped GEMM left there
        y_flat = jnp.where(here[:, None], y_flat, 0)
    y = _with_shared(p, cfg, x, y_flat.reshape(t, k, d).sum(axis=1).reshape(b, s, d))
    return y.astype(x.dtype), jnp.zeros((), jnp.float32)
