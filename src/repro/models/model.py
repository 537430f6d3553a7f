"""Family dispatch: one ``LM`` object per architecture config.

API (used by train/serve/launch):

  lm = build_model(cfg)
  params                    = lm.init(key)
  loss, metrics             = lm.loss(params, batch)
  logits, caches            = lm.prefill(params, batch, max_len)
  logits, caches            = lm.decode_step(params, tokens, caches)
  batch                     = lm.input_specs(shape_cfg)   # ShapeDtypeStructs

Batch dict contents per family (all synthesizable by data.pipeline and by
``input_specs`` for the dry-run):
  dense/moe/ssm/hybrid: {"tokens": (B, S) i32}
  vlm:    {"tokens": (B, S - P) i32, "prefix_embeds": (B, P, d)}
  encdec: {"src_embeds": (B, S, d), "tgt_tokens": (B, S) i32}
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import transformer as T

__all__ = ["LM", "build_model"]

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    input_specs: Callable


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------


def _embed_tokens(params, cfg, tokens):
    return params["embed"]["table"].astype(cfg.activation_dtype())[tokens]


def _logits(params, cfg, h):
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(cfg.activation_dtype()).T
        out = h @ w
    else:
        out = L.dense(params["lm_head"], h, dtype=cfg.activation_dtype())
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        out = jnp.tanh(out / c) * c
    return out


def _head_init(key, cfg):
    ke, kh = L.split_keys(key, 2)
    pd = cfg.parameter_dtype()
    p = {"embed": L.embed_init(ke, cfg.vocab, cfg.d_model, pd)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(kh, cfg.d_model, cfg.vocab, dtype=pd)
    return p


def _lm_loss(params, cfg, tokens, h, *, mask=None, aux=0.0, z_loss=1e-4):
    """Next-token CE over h (B,S,d) vs tokens (B,S)."""
    logits = _logits(params, cfg, h[:, :-1])
    labels = tokens[:, 1:]
    m = None if mask is None else mask[:, 1:]
    loss, metrics = L.cross_entropy(logits, labels, m, z_loss=z_loss)
    loss = loss + aux
    metrics["aux_loss"] = jnp.asarray(aux, jnp.float32)
    metrics["total_loss"] = loss
    return loss, metrics


def _positions(b, s):
    return jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))


# --------------------------------------------------------------------------
# decoder-only families (dense / moe / vlm)
# --------------------------------------------------------------------------


def _ffn_fn_for(cfg: ModelConfig, *, serve: bool = False):
    if cfg.family == "moe" or (cfg.moe is not None):
        dropless = serve and cfg.moe_serve_dropless
        return lambda p, c, h, **kw: MOE.moe_apply(p, c, h, dropless=dropless, **kw)
    return None


def _ffn_init_for(cfg: ModelConfig):
    if cfg.moe is not None:
        return lambda k: MOE.moe_init(k, cfg)
    return None


def _split_caches(cfg: ModelConfig, caches: dict) -> tuple[dict, dict]:
    """The leading dense stack's cache pytree and the main stack's, out of
    the model's: each stack's own leaves (the dense stack's named with
    ``T.DENSE_PREFIX``) and its layers' slice of the per-row metadata."""
    k = cfg.first_dense_layers
    meta = {n: caches[n] for n in T.CACHE_META if n in caches}
    p = T.DENSE_PREFIX
    dense = {n[len(p):]: v for n, v in caches.items() if n.startswith(p)}
    main = {n: v for n, v in caches.items() if n not in meta and not n.startswith(p)}
    dense.update({n: v[:k] for n, v in meta.items()})
    main.update({n: v[k:] for n, v in meta.items()})
    return dense, main


def _join_caches(dense: dict, main: dict) -> dict:
    """Inverse of :func:`_split_caches`."""
    out = {T.DENSE_PREFIX + n: v for n, v in dense.items() if n not in T.CACHE_META}
    out.update({n: v for n, v in main.items() if n not in T.CACHE_META})
    for n in T.CACHE_META:
        if n in main:
            out[n] = jnp.concatenate([dense[n], main[n]])
    return out


def _build_decoder_only(cfg: ModelConfig) -> LM:
    ffn_fn = _ffn_fn_for(cfg)
    ffn_fn_serve = _ffn_fn_for(cfg, serve=True)
    ffn_init = _ffn_init_for(cfg)
    is_vlm = cfg.family == "vlm"
    # Leading dense layers (``first_dense_layers``) run as a stack of their
    # own ahead of the main one: a layer scan takes layers of one kind.
    n_dense = cfg.first_dense_layers

    def init(key):
        kh, ks, kp = L.split_keys(key, 3)
        p = _head_init(kh, cfg)
        if n_dense:
            keys = jnp.stack(L.split_keys(ks, cfg.n_layers))
            p["dense_layers"] = jax.vmap(lambda k: T.layer_init(k, cfg))(keys[:n_dense])
            p["layers"] = jax.vmap(
                lambda k: T.layer_init(k, cfg, ffn_init_fn=ffn_init)
            )(keys[n_dense:])
        else:
            p["layers"] = T.stack_init(ks, cfg, cfg.n_layers, ffn_init_fn=ffn_init)
        p["ln_f"] = L.rmsnorm_init(cfg.d_model, cfg.parameter_dtype())
        if is_vlm:
            p["vision_proj"] = L.dense_init(kp, cfg.d_model, cfg.d_model, dtype=cfg.parameter_dtype())
        return p

    def _embed_batch(params, batch):
        tokens = batch["tokens"]
        x = _embed_tokens(params, cfg, tokens)
        mask = jnp.ones(tokens.shape, jnp.float32)
        if is_vlm:
            pe = L.dense(params["vision_proj"], batch["prefix_embeds"], dtype=cfg.activation_dtype())
            x = jnp.concatenate([pe, x], axis=1)
            pad = jnp.zeros((tokens.shape[0], pe.shape[1]), tokens.dtype)
            tokens = jnp.concatenate([pad, tokens], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(pe.shape[:2], jnp.float32), mask], axis=1
            )
        return x, tokens, mask

    def loss(params, batch):
        x, tokens, mask = _embed_batch(params, batch)
        b, s, _ = x.shape
        if n_dense:
            x, _ = T.stack_apply(params["dense_layers"], cfg, x, _positions(b, s), causal=True)
        h, aux = T.stack_apply(
            params["layers"], cfg, x, _positions(b, s), causal=True, ffn_apply_fn=ffn_fn
        )
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _lm_loss(params, cfg, tokens, h, mask=mask, aux=aux)

    def prefill(params, batch, max_len):
        x, tokens, _ = _embed_batch(params, batch)
        b, s, _ = x.shape
        if n_dense:
            x, dense = T.stack_prefill(
                params["dense_layers"], cfg, x, _positions(b, s), max_len
            )
        h, caches = T.stack_prefill(
            params["layers"], cfg, x, _positions(b, s), max_len, ffn_apply_fn=ffn_fn_serve
        )
        if n_dense:
            caches = _join_caches(dense, caches)
        h = L.rmsnorm(params["ln_f"], h[:, -1:], cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def decode_step(params, tokens, caches):
        x = _embed_tokens(params, cfg, tokens)  # (B, 1)
        if n_dense:
            dense, caches = _split_caches(cfg, caches)
            x, dense = T.stack_decode(params["dense_layers"], cfg, x, dense)
        h, caches = T.stack_decode(
            params["layers"], cfg, x, caches, ffn_apply_fn=ffn_fn_serve
        )
        if n_dense:
            caches = _join_caches(dense, caches)
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def input_specs(shape: ShapeConfig, reduced: bool = False):
        c = cfg.reduced() if reduced else cfg
        sh = shape.reduced() if reduced else shape
        b, s = sh.global_batch, sh.seq_len
        dt = c.activation_dtype()
        if is_vlm:
            p = min(c.n_prefix_embeds, max(s // 4, 1))
            return {
                "tokens": jax.ShapeDtypeStruct((b, s - p), jnp.int32),
                "prefix_embeds": jax.ShapeDtypeStruct((b, p, c.d_model), dt),
            }
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}

    return LM(cfg, init, loss, prefill, decode_step, input_specs)


# --------------------------------------------------------------------------
# SSM / hybrid families
# --------------------------------------------------------------------------


def _build_ssm(cfg: ModelConfig) -> LM:
    hybrid = cfg.family == "hybrid"

    def init(key):
        kh, ks = L.split_keys(key, 2)
        p = _head_init(kh, cfg)
        if hybrid:
            p["layers"] = HY.hybrid_init(ks, cfg)
        else:
            keys = jnp.stack(L.split_keys(ks, cfg.n_layers))
            p["layers"] = jax.vmap(
                lambda k: {
                    "ln": L.rmsnorm_init(cfg.d_model, cfg.parameter_dtype()),
                    "mamba": SSM.mamba_init(k, cfg),
                }
            )(keys)
        p["ln_f"] = L.rmsnorm_init(cfg.d_model, cfg.parameter_dtype())
        return p

    def _backbone(params, cfg_, x, positions):
        if hybrid:
            return HY.hybrid_apply(params["layers"], cfg_, x, positions)

        def body(h, lp):
            out = SSM.mamba_apply(lp["mamba"], cfg_, L.rmsnorm(lp["ln"], h, cfg_.norm_eps))
            return h + out, None

        body = T.remat_wrap(body, cfg_)
        h, _ = T.layer_scan(cfg_, body, x, params["layers"])
        return h, jnp.zeros(())

    def loss(params, batch):
        tokens = batch["tokens"]
        x = _embed_tokens(params, cfg, tokens)
        b, s, _ = x.shape
        h, aux = _backbone(params, cfg, x, _positions(b, s))
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _lm_loss(params, cfg, tokens, h, aux=aux)

    def prefill(params, batch, max_len):
        tokens = batch["tokens"]
        x = _embed_tokens(params, cfg, tokens)
        b, s, _ = x.shape
        if hybrid:
            h, caches = HY.hybrid_prefill(params["layers"], cfg, x, _positions(b, s), max_len)
        else:

            def body(h, lp):
                out, st = SSM.mamba_prefill(lp["mamba"], cfg, L.rmsnorm(lp["ln"], h, cfg.norm_eps))
                return h + out, st

            h, states = T.layer_scan(cfg, body, x, params["layers"])
            caches = {"mamba": states, "len": jnp.asarray(s, jnp.int32)}
        h = L.rmsnorm(params["ln_f"], h[:, -1:], cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def decode_step(params, tokens, caches):
        x = _embed_tokens(params, cfg, tokens)
        if hybrid:
            h, caches = HY.hybrid_decode(params["layers"], cfg, x, caches)
        else:

            def body(h, sc):
                lp, st = sc
                out, st = SSM.mamba_decode(lp["mamba"], cfg, L.rmsnorm(lp["ln"], h, cfg.norm_eps), st)
                return h + out, st

            h, states = T.layer_scan(cfg, body, x, (params["layers"], caches["mamba"]))
            caches = {"mamba": states, "len": caches["len"] + 1}
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def input_specs(shape: ShapeConfig, reduced: bool = False):
        sh = shape.reduced() if reduced else shape
        return {"tokens": jax.ShapeDtypeStruct((sh.global_batch, sh.seq_len), jnp.int32)}

    return LM(cfg, init, loss, prefill, decode_step, input_specs)


# --------------------------------------------------------------------------
# encoder-decoder family
# --------------------------------------------------------------------------


def _build_encdec(cfg: ModelConfig) -> LM:
    def init(key):
        kh, ks = L.split_keys(key, 2)
        p = _head_init(kh, cfg)
        p.update(ED.encdec_init(ks, cfg))
        p["ln_f"] = L.rmsnorm_init(cfg.d_model, cfg.parameter_dtype())
        return p

    def loss(params, batch):
        enc_out = ED.encode(params, cfg, batch["src_embeds"].astype(cfg.activation_dtype()))
        tgt = batch["tgt_tokens"]
        x = _embed_tokens(params, cfg, tgt)
        h = ED.decode_train(params, cfg, x, enc_out)
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _lm_loss(params, cfg, tgt, h)

    def prefill(params, batch, max_len):
        enc_out = ED.encode(params, cfg, batch["src_embeds"].astype(cfg.activation_dtype()))
        tgt = batch["tgt_tokens"]
        x = _embed_tokens(params, cfg, tgt)
        h, caches = ED.encdec_prefill(params, cfg, x, enc_out, max_len)
        h = L.rmsnorm(params["ln_f"], h[:, -1:], cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def decode_step(params, tokens, caches):
        x = _embed_tokens(params, cfg, tokens)
        h, caches = ED.encdec_decode(params, cfg, x, caches)
        h = L.rmsnorm(params["ln_f"], h, cfg.norm_eps)
        return _logits(params, cfg, h), caches

    def input_specs(shape: ShapeConfig, reduced: bool = False):
        c = cfg.reduced() if reduced else cfg
        sh = shape.reduced() if reduced else shape
        b, s = sh.global_batch, sh.seq_len
        return {
            "src_embeds": jax.ShapeDtypeStruct((b, s, c.d_model), c.activation_dtype()),
            "tgt_tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }

    return LM(cfg, init, loss, prefill, decode_step, input_specs)


# --------------------------------------------------------------------------


def build_model(cfg: ModelConfig) -> LM:
    if cfg.family in ("dense", "moe", "vlm"):
        return _build_decoder_only(cfg)
    if cfg.family in ("ssm", "hybrid"):
        return _build_ssm(cfg)
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    raise ValueError(f"unknown family {cfg.family!r}; expected one of {FAMILIES}")
