"""Search throughput: trials told to the TPE over the whole window (host
clock)."""


def read(rec):
    if "trials" not in rec:
        return None
    return rec["trials"] / rec["window_s"]
