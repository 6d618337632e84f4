"""The decode step's share of its roofline: the least time the chip could
take for one step (the larger of its FLOPs over the bf16 peak and its
least bytes over the HBM bandwidth), over the step's mean device time.

Least bytes: every weight once in bf16 plus the live KV cache of the whole
batch, averaged over the decode steps of a request (the step that feeds
output token j sees prompt_len + j positions)."""
from perfbench import costs

DECODE_PROGRAM = "jit__lambda"


def read(rec):
    t = rec.get("trace")
    if t is None or "max_new" not in rec:
        return None
    runs, seconds = t.module_seconds(DECODE_PROGRAM)
    if not runs:
        return None
    cfg, peaks = rec["config"], costs.load_peaks(rec["device_kind"])
    B, P, N = rec["batch_slots"], rec["prompt_len"], rec["max_new"]
    steps = range(1, N)
    live = B * sum(P + j for j in steps) / len(steps)
    flops = B * sum(costs.decode_flops(cfg, P + j) for j in steps) \
        / len(steps)
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  costs.decode_step_min_bytes(cfg, live)
                  / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (seconds / runs)
