"""Device idle time inside the ``serve.step`` spans that neither the launch
nor the readback part covers (commit, admission, drafting and planning), ms
per device step. Read from the spans the profiler recorded natively, never
from their shifted copies: the method is in ``bench/boundary.py``."""

from bench import boundary


def read(r):
    s = boundary.split(r)
    return None if s is None else s["host"] / s["steps"] / 1e6
