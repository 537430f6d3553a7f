"""Device idle time from the start of each ``serve.dispatch`` (operand
upload and launch of the mixed step) to the first device op after it, ms
per device step. Read from the spans the profiler recorded natively, never
from their shifted copies: the method is in ``bench/boundary.py``."""

from bench import boundary


def read(r):
    s = boundary.split(r)
    return None if s is None else s["launch"] / s["steps"] / 1e6
