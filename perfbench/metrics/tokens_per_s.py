"""Serving throughput: output tokens of the requests completed in the
window, over the whole window (host clock)."""


def read(rec):
    if "output_tokens" not in rec:
        return None
    return rec["output_tokens"] / rec["window_s"]
