"""Host time of the evaluator's DSE and frontier scoring (its
``evaluate.dse`` spans, ``repro.obs``), per trial in the window."""


def read(rec):
    s = rec.get("span_s") or {}
    if "evaluate.dse" not in s or not rec.get("trials"):
        return None
    return s["evaluate.dse"] / rec["trials"] * 1e3
