"""Share of the HBM bandwidth bound that the paged attention kernel
(``paged_flash_decode_fwd``, all layers) reaches, %.

Bytes: the engine's ``serve.step.kv_tokens`` counter (per mixed step, the
sum over its rows of the row's KV length before the step plus its query
length: the keys its queries attend) times the cached bytes of one key over
all layers, as the configuration's reference counts them (``cache_bytes``;
K and V for a llama-style decoder). That is a lower bound on what the
kernel reads: it leaves out the queries, the block tables and the scales of
a quantized cache. Time: the kernel's device seconds in the trace, per
chip."""

from bench import trace


def read(r):
    kv = r.counter("serve.step.kv_tokens")
    if r.trace is None or r.peak is None or not kv:
        return None
    s = trace.op_seconds(r.trace, "paged_flash_decode_fwd")
    if not s:
        return None
    return 100.0 * r.reference().cache_bytes(r.model, kv) / r.chips / (s * r.peak["hbm_bytes_per_s"])
