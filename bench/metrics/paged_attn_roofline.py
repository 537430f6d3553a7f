"""Share of the HBM bandwidth bound that the paged attention kernel
(``paged_flash_decode_fwd``, all layers) reaches, %.

Bytes: the engine's ``serve.step.kv_tokens`` counter (per mixed step, the
sum over its rows of the row's KV length before the step plus its query
length: the keys its queries attend) times each token's K and V over all
layers. That is a lower bound on what the kernel reads: it leaves out the
queries, the block tables and the scales of a quantized cache. Time: the
kernel's device seconds in the trace, per chip."""

import jax.numpy as jnp

from bench import trace


def kv_bytes(m: dict, kv_tokens: float) -> float:
    """K and V bytes of ``kv_tokens`` keys, over all layers."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    elem = jnp.dtype(m["kv_cache_dtype"]).itemsize
    return kv_tokens * m["n_layers"] * 2 * m["n_kv_heads"] * hd * elem


def read(r):
    kv = r.counter("serve.step.kv_tokens")
    if r.trace is None or r.peak is None or not kv:
        return None
    s = trace.op_seconds(r.trace, "paged_flash_decode_fwd")
    if not s:
        return None
    return 100.0 * kv_bytes(r.model, kv) / r.chips / (s * r.peak["hbm_bytes_per_s"])
