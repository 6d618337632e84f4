"""Host time of the evaluator's prune+forward (its ``evaluate.forward``
spans, ``repro.obs``: dispatch of the vmapped device program until its
outputs are host arrays), per trial in the window."""


def read(rec):
    s = rec.get("span_s") or {}
    if "evaluate.forward" not in s or not rec.get("trials"):
        return None
    return s["evaluate.forward"] / rec["trials"] * 1e3
