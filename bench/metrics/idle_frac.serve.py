"""Share of the window in which no op ran on the device, %: 1 - the union
of the device's op intervals over the traced window."""

from bench import trace


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s(r.trace) / trace.window_s(r.trace))
