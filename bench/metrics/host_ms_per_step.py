"""Host time per mixed step, ms: the engine's ``serve.step`` span less the
``serve.device_step`` span inside it (planning, admission, bookkeeping),
summed over the window and divided by the steps dispatched."""


def read(r):
    steps = [e.dur_ns for e in r.spans if e.name == "serve.step"]
    device = [e.dur_ns for e in r.spans if e.name == "serve.device_step"]
    if not device:
        return None
    return (sum(steps) - sum(device)) / len(device) / 1e6
