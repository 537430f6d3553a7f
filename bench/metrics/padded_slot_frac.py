"""Share of the token slots of all mixed steps that carried no token, %:
1 - (decode + prefill tokens) / sum over steps of (slots x step width).
Read from the engine's registry counters over the window."""


def read(r):
    narrow = r.counter("serve.steps", width="narrow")
    wide = r.counter("serve.steps", width="wide")
    slots = r.mix["slots"] * (narrow + wide * r.mix["chunk"])
    if not slots:
        return None
    used = r.counter("serve.step.tokens", kind="decode") + r.counter("serve.step.tokens", kind="prefill")
    return 100.0 * (1.0 - used / slots)
