"""Small cells for driving the benchmark's jobs on the CPU."""
from __future__ import annotations

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def _json(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def small_resnet(res: int = 32, classes: int = 11) -> dict:
    """The ResNet-18 table at ``res`` x ``res`` and ``classes`` classes:
    the same layers, spatial sizes recomputed through the strides."""
    cfg = copy.deepcopy(_json("perfbench/configs/resnet18.json"))
    cfg["image_size"], cfg["num_classes"] = res, classes
    hw, last = {"input": res}, "input"
    for l in cfg["layers"]:
        src = l.get("input", last)
        if "in_hw" in l:
            l["in_hw"] = hw[src]
            l["out_hw"] = hw[src] // l.get("stride", 1)
        if l["kind"] == "gap":
            l["out_hw"] = 1
        if l["name"] == "fc":
            l["cout"] = classes
        hw[l["name"]] = l.get("out_hw", hw[src])
        last = l["name"]
    return cfg


def small_qwen3() -> dict:
    cfg = dict(_json("perfbench/configs/qwen3-0.6b.json"))
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=503)
    return cfg


SEARCH_TRAFFIC = {"images": 4, "batch_size": 4, "liar": "min", "s_max": 0.9,
                  "dse_iters": 60, "iters": 8, "check_trials": 4}
SERVE_TRAFFIC = {"batch_slots": 2, "prompt_len": 16, "max_new": 6,
                 "check_requests": 2}


def cell(name, config, traffic, checks, bench=None) -> harness.Cell:
    bench = bench or harness.load_benchmark()
    e2e = harness._metrics_for(bench["end_to_end"], name)
    per = harness._metrics_for(bench["per_layer"], name,
                               {m["name"] for m in e2e})
    return harness.Cell(name, 1, config, traffic, checks, e2e, per)


def run(cell_, seed=7, seconds=0.5, trace=False):
    import jax
    job = harness.load_module("jobs", cell_.config["job"])
    r = harness.Run(cell_, seed, seconds, trace, harness.now(),
                    jax.devices())
    return job.run(r)


def committed_checks(workload: str) -> dict:
    return _json(f"perfbench/checks/{workload}.json")


def mid_qwen3() -> dict:
    """Qwen3-0.6B's widths with two layers and a 4096-token vocabulary:
    logits on the scale of the full model's, small enough for the CPU."""
    cfg = dict(_json("perfbench/configs/qwen3-0.6b.json"))
    cfg.update(num_hidden_layers=2, vocab_size=4096)
    return cfg


# the committed decode mix with short requests: its slots and its sample
# of compared requests as they are timed
MID_SERVE_TRAFFIC = dict(_json("perfbench/traffic/decode.json"),
                         prompt_len=24, max_new=8)
