"""Work counts from shapes: parameters, model FLOPs, flash-attention cost.

Everything here takes a configuration's ``model`` dict (the file under
``bench/configs/``) or plain shapes, and imports nothing of the program.
A FLOP is one multiply or one add, so a multiply-accumulate counts 2.
"""

from __future__ import annotations


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def _layer_params(m: dict, experts: int) -> int:
    d, hd = m["d_model"], _hd(m)
    attn = d * hd * (m["n_heads"] + 2 * m["n_kv_heads"]) + m["n_heads"] * hd * d
    if m.get("qkv_bias"):
        attn += hd * (m["n_heads"] + 2 * m["n_kv_heads"])
    moe = m.get("moe")
    if moe:
        ffn = experts * 3 * d * moe["d_ff_expert"] + d * moe["num_experts"]
    else:
        ffn = 3 * d * m["d_ff"]
    return attn + ffn + 2 * d  # two RMSNorm scales


def param_counts(m: dict) -> tuple[int, int]:
    """(total, active per token) parameters, embedding table and head included."""
    moe = m.get("moe")
    n_e = moe["num_experts"] if moe else 0
    k = moe["top_k"] if moe else 0
    d, v = m["d_model"], m["vocab"]
    head = v * d * (1 if m.get("tie_embeddings") else 2) + d  # + final norm
    total = m["n_layers"] * _layer_params(m, n_e) + head
    active = m["n_layers"] * _layer_params(m, k) + head
    return total, active


def matmul_params(m: dict) -> tuple[int, int]:
    """(total, active) parameters that a token multiplies by: every weight
    matrix, the output head among them, and not the embedding table (a
    lookup) or the norm scales."""
    total, active = param_counts(m)
    d = m["d_model"]
    skip = (0 if m.get("tie_embeddings") else m["vocab"] * d) + (2 * m["n_layers"] + 1) * d
    return total - skip, active - skip


def causal_pairs(start: int, n: int) -> int:
    """Query-key pairs that ``n`` queries at positions ``start .. start+n-1``
    attend under a causal mask: position p attends p + 1 keys."""
    return n * start + n * (n + 1) // 2


def attn_flops_per_pair(m: dict) -> int:
    """Forward FLOPs of one query-key pair in every layer: QK^T and PV."""
    return 4 * m["n_layers"] * m["n_heads"] * _hd(m)


def serve_flops(m: dict, tokens: int, pairs: int) -> float:
    """Model FLOPs of a served forward over ``tokens`` positions that attend
    ``pairs`` query-key pairs in all: 2 per active matmul parameter per
    token, plus causal attention counted once."""
    return 2.0 * matmul_params(m)[1] * tokens + attn_flops_per_pair(m) * pairs


# The training counts below wait for the training cell (PERF.md, Open
# questions, row 0): no cell reads them yet; tests check them on small shapes.


def train_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step over ``batch`` rows of ``seq``
    tokens: forward and backward (3x the forward), recomputation not
    counted, causal attention counted once."""
    fwd = 2.0 * matmul_params(m)[1] * batch * seq
    fwd += attn_flops_per_pair(m) * batch * causal_pairs(0, seq)
    return 3.0 * fwd


def flash_fwd_cost(b: int, hq: int, hkv: int, s: int, d: int, *, causal: bool = True,
                   itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one flash-attention forward call: causal work
    counted once, not the tiles visited; bytes are Q, K, V read once, O
    written once, plus the f32 log-sum-exp per query row."""
    pairs = b * (causal_pairs(0, s) if causal else s * s)
    flops = 4.0 * hq * d * pairs
    byts = itemsize * b * s * d * (2 * hq + 2 * hkv) + 4 * b * hq * s
    return flops, float(byts)


def flash_bwd_cost(b: int, hq: int, hkv: int, s: int, d: int, *, causal: bool = True,
                   itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the flash backward (delta, dQ, dK/dV kernels
    together): per pair the recomputed scores and dP (2 matmuls) and dQ,
    dK, dV (3 matmuls), 10 FLOPs per head dim; bytes are Q, K, V, O, dO
    read and dQ, dK, dV written once, plus log-sum-exp and delta."""
    pairs = b * (causal_pairs(0, s) if causal else s * s)
    flops = 10.0 * hq * d * pairs
    byts = itemsize * b * s * d * (4 * hq + 4 * hkv) + 2 * 4 * b * hq * s
    return flops, float(byts)
