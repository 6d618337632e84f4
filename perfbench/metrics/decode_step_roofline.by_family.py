"""The decode step's share of its roofline, counted by the configuration's
family: the least time the chip could take for one step (the larger of its
FLOPs over the bf16 peak and its least bytes over the HBM bandwidth), over
the step's mean device time.

``families/<family>.py`` counts both where it defines
``decode_flops(cfg, context)`` and ``decode_step_min_bytes(cfg,
live_tokens, batch)``; a family without them (Qwen3) is counted by
``costs.py``'s dense decoder, so there this reads what
``decode_step_roofline`` reads. Averaged over the decode steps of a
request: the step that feeds output token j sees prompt_len + j
positions. The family file is read from the tree this reader lies in,
the cell's own."""
import os

from perfbench import costs, harness

DECODE_PROGRAM = "jit__lambda"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(rec):
    t = rec.get("trace")
    if t is None or "max_new" not in rec:
        return None
    runs, seconds = t.module_seconds(DECODE_PROGRAM)
    if not runs:
        return None
    cfg, peaks = rec["config"], costs.load_peaks(rec["device_kind"])
    fam = harness.load_module("families", cfg["family"], ROOT)
    flops_of = getattr(fam, "decode_flops", costs.decode_flops)
    bytes_of = getattr(fam, "decode_step_min_bytes",
                       lambda c, live, batch: costs.decode_step_min_bytes(
                           c, live))
    B, P, N = rec["batch_slots"], rec["prompt_len"], rec["max_new"]
    steps = range(1, N)
    live = B * sum(P + j for j in steps) / len(steps)
    flops = B * sum(flops_of(cfg, P + j) for j in steps) / len(steps)
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  bytes_of(cfg, live, B) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (seconds / runs)
