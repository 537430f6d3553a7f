"""Model FLOPs of the window over the chips' bf16 peak, %.

FLOPs: 2 per active matmul parameter per token the steps carried (decode
and prefill tokens, the engine's counters) plus causal attention counted
once per query-key pair (each request's prompt and served tokens, from
position 0). Time: the window on the host clock."""

from bench import work


def read(r):
    tokens = r.counter("serve.step.tokens", kind="decode") + r.counter("serve.step.tokens", kind="prefill")
    if not tokens or r.peak is None:
        return None
    pairs = sum(work.causal_pairs(0, p + n - 1) for p, n in r.requests if n)
    flops = work.serve_flops(r.model, tokens, pairs)
    return 100.0 * flops / (r.window_s * r.chips * r.peak["bf16_flops"])
