"""Host time of the evaluator (its ``evaluate`` spans, ``repro.obs``:
prune and forward on the device, then the DSE on the host), per trial in
the window."""


def read(rec):
    s = rec.get("span_s") or {}
    if "evaluate" not in s or not rec.get("trials"):
        return None
    return s["evaluate"] / rec["trials"] * 1e3
