"""Model FLOPs of the window over the chips' bf16 peak, %.

FLOPs: the configuration's reference counts them (``serve_flops``) from
the tokens the steps carried (decode and prefill tokens, the engine's
counters) and the query-key pairs of causal attention counted once (each
request's prompt and served tokens, from position 0). Time: the window on
the host clock."""

from bench import work


def read(r):
    tokens = r.counter("serve.step.tokens", kind="decode") + r.counter("serve.step.tokens", kind="prefill")
    if not tokens or r.peak is None:
        return None
    pairs = sum(work.causal_pairs(0, p + n - 1) for p, n in r.requests if n)
    flops = r.reference().serve_flops(r.model, tokens, pairs)
    return 100.0 * flops / (r.window_s * r.chips * r.peak["bf16_flops"])
