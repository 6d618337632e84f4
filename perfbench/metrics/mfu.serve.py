"""Serving's share of the chip's bf16 peak: forward FLOPs of every token
the window's requests put through the model (prompt and fed-back output
tokens, causal attention over the live context, the LM head only where
logits are produced), over the window times the peak."""

from perfbench import costs


def read(rec):
    if "flops" not in rec:
        return None
    peak = costs.load_peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rec["flops"] / (rec["window_s"] * peak)
