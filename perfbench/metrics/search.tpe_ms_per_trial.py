"""Host time the search loop spends in the TPE (its ``propose`` and
``tell`` spans, ``repro.obs``), per trial in the window."""


def read(rec):
    s = rec.get("span_s") or {}
    if "propose" not in s or not rec.get("trials"):
        return None
    return (s["propose"] + s.get("tell", 0.0)) / rec["trials"] * 1e3
