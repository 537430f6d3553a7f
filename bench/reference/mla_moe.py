"""Plain float32 reference of DeepSeek-V2's block: latent attention and an
expert layer with shared experts, a chip's share of the routed experts.

Written from the layer equations, with no kernel, cache or batching of the
program under test and nothing imported from it. Weights are made here
from the seed with the same draws as the model's initialisation (one
normal per matrix, scaled by 1/sqrt(fan-in), rounded to the stated
parameter dtype), layer by layer, so the reference never holds more than
one layer's weights next to the embedding rows it needs. Everything runs
under ``jax.default_matmul_precision("highest")``.

Layer equations (pre-norm; n(x) = x / sqrt(mean(x^2) + eps), scales are
ones at initialisation):
    q = Wq·n1(x) -> H x (nope + rope);  [c; k_pe] = Wkva·n1(x);  c_kv = n(c)
    [k_nope; v] = Wkvb·c_kv -> H x (nope + v)   (K and V decompressed per head)
    q_pe, k_pe: YaRN rotary (k_pe one head, shared by all H)
    h  = x + Wo · softmax(s · q·[k_nope; k_pe], causal) · v
         with s = qk_head_dim^-1/2 · (0.1 · mscale_all_dim · ln(factor) + 1)^2
    x' = h + ffn(n2(h))
    ffn(y) = Wd · (silu(Wg·y) * (Wu·y))                          dense layers
    ffn(y) = shared(y) + sum_{e in top-k, e held} p_e(y) · ffn_e(y)   MoE layers
with p the softmax over all routed experts' router logits (renormalised
over the top-k only where ``norm_topk_prob``), the held experts ids
[0, experts_held), and rotary embeddings in half-split pair order.
``logits = Whead · nf(x_L)``.

This is the unabsorbed form: the program serves in the absorbed one (the
query folded through Wkvb's key half, scores taken against the cached
latents), so the two compute the same attention by different paths.

``mode="fp8"`` is the control: every weight matrix and every activation
that enters a weight matmul goes through float8 e4m3 with a per-channel
scale (per output column for weights, per token for activations).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows of one attention block: scores (H, 512, S) in float32


class Dims(NamedTuple):
    n_layers: int
    d: int
    h: int
    vocab: int
    eps: float
    theta: float
    param_dtype: str
    rank: int
    nope: int
    rope: int
    vd: int
    rope_factor: float
    rope_original_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    n_dense: int
    dense_ff: int
    experts: int
    held: int
    top_k: int
    ff: int
    n_shared: int
    norm_topk: bool

    @classmethod
    def of(cls, m: dict) -> "Dims":
        a, moe = m["mla"], m["moe"]
        return cls(m["n_layers"], m["d_model"], m["n_heads"], m["vocab"],
                   float(m.get("norm_eps", 1e-5)), float(m.get("rope_theta", 1e4)),
                   m.get("param_dtype", "float32"), a["kv_lora_rank"], a["qk_nope_head_dim"],
                   a["qk_rope_head_dim"], a["v_head_dim"], float(a.get("rope_factor", 1.0)),
                   a.get("rope_original_max", 4096), float(a.get("beta_fast", 32.0)),
                   float(a.get("beta_slow", 1.0)), float(a.get("mscale", 1.0)),
                   float(a.get("mscale_all_dim", 1.0)), m.get("first_dense_layers", 0), m["d_ff"],
                   moe["num_experts"], moe.get("experts_held") or moe["num_experts"],
                   moe["top_k"], moe["d_ff_expert"], moe.get("n_shared_experts", 0),
                   moe.get("norm_topk_prob", True))

    @property
    def latent(self) -> int:
        return self.rank + self.rope


# ---------------------------------------------------------------- weights


def _normal(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _embed_table(key, dm: Dims):
    ke, _ = jax.random.split(key, 2)
    return (jax.random.normal(ke, (dm.vocab, dm.d)) * 0.02).astype(dm.param_dtype)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _head(key, dm: Dims):
    _, kh = jax.random.split(key, 2)
    return _normal(kh, (dm.d, dm.vocab), dm.d, dm.param_dtype)


def _swiglu_weights(key, d, ff, pd):
    kg, ku, kd = jax.random.split(key, 3)
    return {"wg": _normal(kg, (d, ff), d, pd), "wu": _normal(ku, (d, ff), d, pd),
            "wd": _normal(kd, (ff, d), ff, pd)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(key, dm: Dims, dense: bool) -> dict:
    ka, kf = jax.random.split(key, 2)
    kq, kkva, kkvb, ko = jax.random.split(ka, 4)
    pd, h = dm.param_dtype, dm.h
    w = {
        "wq": _normal(kq, (dm.d, h * (dm.nope + dm.rope)), dm.d, pd),
        "wkva": _normal(kkva, (dm.d, dm.latent), dm.d, pd),
        "wkvb": _normal(kkvb, (dm.rank, h * (dm.nope + dm.vd)), dm.rank, pd),
        "wo": _normal(ko, (h * dm.vd, dm.d), h * dm.vd, pd),
    }
    if dense:
        w.update(_swiglu_weights(kf, dm.d, dm.dense_ff, pd))
        return w
    kr, kg, ku, kd, *ks = jax.random.split(kf, 5 if dm.n_shared else 4)
    e = dm.held
    w["router"] = _normal(kr, (dm.d, dm.experts), dm.d, jnp.float32)
    w["wg"] = _normal(kg, (e, dm.d, dm.ff), dm.d, pd)
    w["wu"] = _normal(ku, (e, dm.d, dm.ff), dm.d, pd)
    w["wd"] = _normal(kd, (e, dm.ff, dm.d), dm.ff, pd)
    if dm.n_shared:
        w["shared"] = _swiglu_weights(ks[0], dm.d, dm.n_shared * dm.ff, pd)
    return w


def model_keys(seed: int, n_layers: int):
    """(head key, per-layer keys) as the model's initialisation splits them."""
    kh, ks, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return kh, jax.random.split(ks, n_layers)


# ---------------------------------------------------------------- forward


def _fp8(x, axis):
    """Round through float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    """x (..., k) @ w (k, n) in float32, through fp8 in the control."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dm: Dims) -> np.ndarray:
    """YaRN's rotary frequencies (rope // 2): inv_i = (1 - r_i) · theta^(-2i/rope) /
    factor + r_i · theta^(-2i/rope), r_i = 1 - clip((i - lo) / (hi - lo), 0, 1),
    lo and hi the floor and ceil of the dims that turn beta_fast and beta_slow
    times over the original context."""
    base = dm.theta ** (-np.arange(0, dm.rope, 2, dtype=np.float64) / dm.rope)
    if dm.rope_factor <= 1:
        return base.astype(np.float32)
    turns = lambda b: (dm.rope * math.log(dm.rope_original_max / (b * 2 * math.pi))
                       / (2 * math.log(dm.theta)))
    lo, hi = max(math.floor(turns(dm.beta_fast)), 0), min(math.ceil(turns(dm.beta_slow)),
                                                         dm.rope - 1)
    r = 1.0 - np.clip((np.arange(dm.rope // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - r) * base / dm.rope_factor + r * base).astype(np.float32)


def scale(dm: Dims) -> float:
    return (dm.nope + dm.rope) ** -0.5 * _yarn_mscale(dm.rope_factor, dm.mscale_all_dim) ** 2


def _rope(x, pos, dm: Dims):
    """Half-split rotary embedding of x (S, H, rope), YaRN-scaled."""
    half = dm.rope // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(dm))   # (S, half)
    m = _yarn_mscale(dm.rope_factor, dm.mscale) / _yarn_mscale(dm.rope_factor, dm.mscale_all_dim)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, dm: Dims):
    """Causal softmax attention of one sequence, a block of Q_BLOCK query
    rows at a time: q, k (S, H, nope + rope), v (S, H, vd) -> (S, H, vd)."""
    s = q.shape[0]
    qb = min(Q_BLOCK, s)
    pad = -s % qb
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, *q.shape[1:])

    def block(args):
        i, qi = args
        scores = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * scale(dm)
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(qs.shape[0]), qs))
    return out.reshape(-1, *out.shape[2:])[:s]


def _swiglu(y, w, mode):
    return _mm(jax.nn.silu(_mm(y, w["wg"], mode)) * _mm(y, w["wu"], mode), w["wd"], mode)


def gates(y, w, dm: Dims):
    """(S, experts) weight of each routed expert for each token: the top-k
    experts' probabilities under a softmax over all routed experts (or a
    softmax over the top-k logits, where ``norm_topk_prob``), 0 elsewhere."""
    logits = jnp.matmul(y, w["router"], precision=HIGHEST)     # (S, E)
    top, sel = jax.lax.top_k(logits, dm.top_k)
    if dm.norm_topk:
        gate = jax.nn.softmax(top, axis=-1)
    else:
        gate = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), sel, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(y.shape[0])[:, None], sel].set(gate)


def routed(y, w, dm: Dims, mode, first=0):
    """The part of the routed experts' output that the experts held in
    ``w`` give: they are ids [first, first + len(w["wg"]))."""
    weight = gates(y, w, dm)

    def expert(acc, j):
        we = {n: w[n][j] for n in ("wg", "wu", "wd")}
        return acc + weight[:, first + j, None] * _swiglu(y, we, mode), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(y), jnp.arange(w["wg"].shape[0]))
    return acc


def ffn(y, w, dm: Dims, mode):
    if "router" not in w:
        return _swiglu(y, w, mode)
    out = routed(y, w, dm, mode)
    return out + _swiglu(y, w["shared"], mode) if "shared" in w else out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, dm: Dims, mode):
    """One layer over one sequence x (S, d) that starts at position 0."""
    pos = jnp.arange(x.shape[0])
    n = _norm(x, dm.eps)
    q = _mm(n, w["wq"], mode).reshape(-1, dm.h, dm.nope + dm.rope)
    q = jnp.concatenate([q[..., : dm.nope], _rope(q[..., dm.nope:], pos, dm)], axis=-1)
    kva = _mm(n, w["wkva"], mode)
    c = _norm(kva[:, : dm.rank], dm.eps)
    k_pe = _rope(kva[:, None, dm.rank:], pos, dm)                          # (S, 1, rope)
    kv = _mm(c, w["wkvb"], mode).reshape(-1, dm.h, dm.nope + dm.vd)
    k = jnp.concatenate([kv[..., : dm.nope], jnp.broadcast_to(k_pe, (*kv.shape[:2], dm.rope))],
                        axis=-1)
    o = _attention(q, k, kv[..., dm.nope:], dm)
    h = x + _mm(o.reshape(-1, dm.h * dm.vd), w["wo"], mode)
    return h + ffn(_norm(h, dm.eps), w, dm, mode)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(x, head, dm: Dims, mode):
    return _mm(_norm(x, dm.eps), head, mode)


@jax.jit
def _gap_stats(ref, targets):
    """Gaps by which the logit of ``targets`` lies below the best logit of
    ``ref`` (n, V): (widest, sum)."""
    got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    gap = ref.max(axis=-1) - got
    return gap.max(), gap.sum()


def forward(m: dict, seed: int, tokens: np.ndarray, modes=("f32",)):
    """(dims, output head, {mode: [final hidden states (S, d) per row]})
    for tokens (B, S) int32, each row a sequence from position 0."""
    dm = Dims.of(m)
    with jax.default_matmul_precision("highest"):
        kh, layer_keys = model_keys(seed, dm.n_layers)
        table = _embed_table(kh, dm)
        xs = {mode: [_embed(table, jnp.asarray(t)) for t in tokens] for mode in modes}
        del table
        for i in range(dm.n_layers):
            w = _layer_weights(layer_keys[i], dm, i < dm.n_dense)
            for mode in modes:
                xs[mode] = [_layer(x, w, dm, mode) for x in xs[mode]]
            del w
        return dm, _head(kh, dm), xs


def logit_gaps(m: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
               modes=("f32",)) -> dict:
    """Gaps below the float32 reference's best logit (the contract of
    ``bench/reference/__init__.py``; as ``decoder.logit_gaps``). Logits are
    taken only at the compared positions: a row of 16k positions would
    hold 6.7 GB of float32 logits over the 102,400-word vocabulary."""
    dm, head, xs = forward(m, seed, tokens, modes)
    acc = {mode: [0.0, 0.0] for mode in modes}  # widest, sum
    n = 0
    with jax.default_matmul_precision("highest"):
        for b in range(len(tokens)):
            at = np.nonzero(np.asarray(targets[b]) >= 0)[0]
            if not len(at):
                continue
            ref = _logits(xs["f32"][b][at], head, dm, "f32")
            tg = jnp.asarray(np.asarray(targets[b])[at])
            for mode in modes:
                pick = tg if mode == "f32" else jnp.argmax(
                    _logits(xs[mode][b][at], head, dm, mode), axis=-1)
                widest, total = _gap_stats(ref, pick)
                acc[mode][0] = max(acc[mode][0], float(widest))
                acc[mode][1] += float(total)
            n += len(at)
    out = {"tokens": n}
    for mode, (widest, total) in acc.items():
        prefix = "" if mode == "f32" else f"{mode}."
        out[prefix + "logit_gap"] = widest
        out[prefix + "mean_logit_gap"] = total / max(n, 1)
    return out


def logits(m: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """float32 logits (B, S, vocab) of tokens (B, S), each row a sequence
    from position 0."""
    dm, head, xs = forward(m, seed, tokens)
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(_logits(x, head, dm, "f32")) for x in xs["f32"]])


def weight_pairs(m: dict, seed: int, params):
    """(name, reference matrix, program matrix) for every matrix drawn from
    the seed: the embedding table, the output head, and each layer's
    attention projections, dense or shared FFN, router and held expert
    stacks. The program's ``params`` hold the leading dense layers and the
    MoE layers as two stacks, each on a leading layer axis."""
    dm = Dims.of(m)
    kh, layer_keys = model_keys(seed, dm.n_layers)
    yield "embed", _embed_table(kh, dm).astype(jnp.float32), params["embed"]["table"]
    yield "lm_head", _head(kh, dm), params["lm_head"]["w"]
    swiglu = (("wg", "w_gate"), ("wu", "w_up"), ("wd", "w_down"))

    def theirs(stack, dense):
        attn, ffn = stack["attn"], stack["ffn"]
        out = {ours: attn[name]["w"] for ours, name in
               (("wq", "wq"), ("wkva", "wkv_a"), ("wkvb", "wkv_b"), ("wo", "wo"))}
        if dense:
            out.update({ours: ffn[name]["w"] for ours, name in swiglu})
            return out
        out.update({ours: ffn[name] for ours, name in swiglu})
        out["router"] = ffn["router"]["w"]
        if dm.n_shared:
            out.update({f"shared {ours}": ffn["shared"][name]["w"] for ours, name in swiglu})
        return out

    stacks = [(params["dense_layers"], True)] if dm.n_dense else []
    stacks.append((params["layers"], False))
    i = 0
    for stack, dense in stacks:
        mats = theirs(stack, dense)
        for j in range(next(iter(mats.values())).shape[0]):
            w = _layer_weights(layer_keys[i], dm, dense)
            for k, stacked in mats.items():
                ours = w["shared"][k.split()[1]] if k.startswith("shared") else w[k]
                yield f"layer {i} {k}", ours, stacked[j]
            i += 1


def matmul_params(m: dict) -> float:
    """Matmul parameters a served token is multiplied by on this chip, on
    average: each layer's four attention projections, the dense FFN or
    the shared experts and the router, the held share of the top-k routed
    experts (top_k x held / experts of them), and the output head."""
    dm = Dims.of(m)
    attn = (dm.d * dm.h * (dm.nope + dm.rope) + dm.d * dm.latent
            + dm.rank * dm.h * (dm.nope + dm.vd) + dm.h * dm.vd * dm.d)
    dense = 3 * dm.d * dm.dense_ff
    moe = (3 * dm.d * dm.n_shared * dm.ff + dm.d * dm.experts
           + dm.top_k * dm.held / dm.experts * 3 * dm.d * dm.ff)
    n_moe = dm.n_layers - dm.n_dense
    return dm.n_layers * attn + dm.n_dense * dense + n_moe * moe + dm.d * dm.vocab


def attention_flops(m: dict, pairs: float) -> float:
    """FLOPs of attention over ``pairs`` query-key pairs in the absorbed
    form the paged kernel runs: per layer and head, a score over the
    latent_dim-wide cached latent and an output over its kv_lora_rank
    columns, 2 FLOPs a multiply-add."""
    dm = Dims.of(m)
    return pairs * dm.n_layers * dm.h * 2 * (dm.latent + dm.rank)


def serve_flops(m: dict, tokens: float, pairs: int) -> float:
    """Model FLOPs of a served forward: 2 per matmul parameter a token
    meets here (``matmul_params``) plus the absorbed attention of each
    query-key pair (``attention_flops``)."""
    return 2 * matmul_params(m) * tokens + attention_flops(m, pairs)


def cache_bytes(m: dict, kv_tokens: float) -> float:
    """Latent bytes of ``kv_tokens`` keys over all layers: one latent_dim
    vector a key a layer (31,104 B at DeepSeek-V2-Lite's widths in bf16)."""
    dm = Dims.of(m)
    return kv_tokens * dm.n_layers * dm.latent * jnp.dtype(m["kv_cache_dtype"]).itemsize


def compile_probes(m: dict, rows: int):
    """(program name, function, *argument shapes) of what a comparison over
    rows of ``rows`` positions compiles: one MoE layer's weights, one MoE
    layer in float32 and in the fp8 control, and the output logits."""
    dm = Dims.of(m)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    weights = lambda k: _layer_weights(k, dm, False)
    x = jax.ShapeDtypeStruct((rows, dm.d), jnp.float32)
    yield "reference layer weights", weights, key
    for mode in ("f32", "fp8"):
        yield (f"reference layer, {mode}", lambda x, w, mode=mode: _layer(x, w, dm, mode), x,
               jax.eval_shape(weights, key))
    yield ("reference logits", lambda x, h: _logits(x, h, dm, "f32"), x,
           jax.ShapeDtypeStruct((dm.d, dm.vocab), jnp.float32))
