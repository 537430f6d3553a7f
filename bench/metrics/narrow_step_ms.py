"""Mean ``serve.device_step`` span of width-1 (decode-only) mixed steps, ms:
dispatch, device time and the copy of the sampled tokens to the host."""


def read(r):
    d = [e.dur_ns for e in r.spans if e.name == "serve.device_step" and e.args["width"] == 1]
    return sum(d) / len(d) / 1e6 if d else None
