"""Device time of the paged attention kernel (``paged_flash_decode_fwd``,
all layers) per mixed step, ms, from the profiler trace."""

from bench import trace


def read(r):
    steps = sum(e.name == "serve.device_step" for e in r.spans)
    if r.trace is None or not steps:
        return None
    s = trace.op_seconds(r.trace, "paged_flash_decode_fwd")
    return s / steps * 1e3 if s else None
