"""Mean ``serve.device_step`` span of wide mixed steps (any prefill row, so
the step runs at the prefill chunk's width), ms."""


def read(r):
    d = [e.dur_ns for e in r.spans if e.name == "serve.device_step" and e.args["width"] > 1]
    return sum(d) / len(d) / 1e6 if d else None
