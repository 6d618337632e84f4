"""Share of the traced window in which no operation ran on the device
(profiler trace: 1 - busy union / window)."""


def read(rec):
    t = rec.get("trace")
    return None if t is None else 100.0 * t.idle_share
