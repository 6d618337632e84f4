"""The search's share of the chip's bf16 peak: FLOPs of the weight tiles
each proposal keeps (each pruned layer's dense FLOPs times one minus its
requested weight sparsity, over the calibration images), summed over the
window's trials, over the window times the peak."""

from perfbench import costs


def read(rec):
    if "kept_flops" not in rec:
        return None
    peak = costs.load_peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rec["kept_flops"] / (rec["window_s"] * peak)
