"""Share of the bf16 compute bound that the paged attention kernel
(``paged_flash_decode_fwd``, all layers) reaches, %.

FLOPs: the engine's ``serve.step.qk_pairs`` counter (per mixed step, the
sum over its rows of the query-key pairs its queries attend: each query
the row's cached keys and the chunk's keys up to its own) times the
attention FLOPs of one pair over all layers, as the configuration's
reference counts them (``attention_flops``: in the form the kernel runs).
Time: the kernel's device seconds in the trace, per chip. Beside
``paged_attn_roofline``, the share of the bandwidth bound, it places the
kernel against both roofs. A reference that counts no attention FLOPs
gives no reading."""

from bench import trace


def read(r):
    pairs = r.counter("serve.step.qk_pairs")
    if r.trace is None or r.peak is None or not pairs:
        return None
    flops = getattr(r.reference(), "attention_flops", None)
    s = trace.op_seconds(r.trace, "paged_flash_decode_fwd")
    if flops is None or not s:
        return None
    return 100.0 * flops(r.model, pairs) / r.chips / (s * r.peak["bf16_flops"])
