"""Device time of the prefill per request: in each ``generate`` call of the
traced window (a ``perfbench.generate`` annotation), the device time of
the programs that ran before the call's first decode step (``jit__lambda``),
summed over the calls and divided by the requests.

Each call's readback waits for its last program, so a call's programs run
inside its annotation. Counted with the prefill: the sampling of the first
token from the prefill's logits, a few programs of microseconds."""

DECODE_PROGRAM = "jit__lambda"
CALL = "perfbench.generate"


def read(rec):
    t = rec.get("trace")
    if t is None or not rec.get("requests"):
        return None
    calls = [(s, e) for n, s, e in t.annotations if n == CALL]
    if not calls:
        return None
    ns = 0.0
    for lo, hi in calls:
        mods = [(n, s, e) for n, s, e in t.modules if lo <= s <= hi]
        first = min((s for n, s, _ in mods if n == DECODE_PROGRAM),
                    default=hi)
        ns += sum(e - s for _, s, e in mods if s < first)
    return ns * 1e-9 / rec["requests"] * 1e3
