"""Median queue wait of the requests admitted in the window, ms: the
engine's ``serve.queued`` span, from the moment a request could be admitted
(its ``generate()`` call's start, or the step boundary that reached its
arrival step) to its first admission to a slot."""

from bench import harness


def read(r):
    waits = [e.dur_ns for e in r.spans if e.name == "serve.queued"]
    return harness.percentile(waits, 50) / 1e6 if waits else None
