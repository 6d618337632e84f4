"""Set-up time: process start to the first timed unit, compiles included
(host clock)."""


def read(rec):
    return rec.get("setup_s")
