"""Device time of one decode step: the mean duration of the decode
program's runs in the traced window.

``ServeSession`` jits ``decode_step`` through a lambda, so the trace names
the program after it."""

DECODE_PROGRAM = "jit__lambda"


def read(rec):
    t = rec.get("trace")
    if t is None:
        return None
    runs, seconds = t.module_seconds(DECODE_PROGRAM)
    return seconds / runs * 1e3 if runs else None
