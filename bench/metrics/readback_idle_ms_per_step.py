"""Device idle time from the end of the last device op inside each
``serve.wait_tokens`` (the host's copy of the sampled tokens) to that
span's end, ms per device step. Read from the spans the profiler recorded
natively, never from their shifted copies: the method is in
``bench/boundary.py``."""

from bench import boundary


def read(r):
    s = boundary.split(r)
    return None if s is None else s["readback"] / s["steps"] / 1e6
