"""The serve cells' per-layer readers on records made by hand: the prefill's
device time per request, the serving cache per request, and the decode
roofline counted by the configuration's family."""
import cells
from perfbench import harness, trace_reduce


def _read(metric, rec):
    return harness.load_module("metrics", metric).read(rec)


def _summary(modules, annotations):
    return trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, window_ns=(0.0, 1e9), modules=modules,
        op_seconds={}, gap_seconds={}, annotations=annotations)


def test_prefill_time_is_what_runs_before_each_calls_first_decode_step():
    ms = 1e6                                         # ns
    calls = [("perfbench.generate", 0.0, 100 * ms),
             ("perfbench.generate", 100 * ms, 200 * ms)]
    modules = [
        # call 1: an eager prefill of two programs, one sampling, decode
        ("jit_scan", 1 * ms, 9 * ms), ("jit_gather", 9 * ms, 10 * ms),
        ("jit__argmax", 10 * ms, 10.5 * ms),
        ("jit__lambda", 11 * ms, 20 * ms), ("jit__argmax", 20 * ms, 21 * ms),
        ("jit__lambda", 21 * ms, 30 * ms),
        # call 2: one jitted prefill program, then decode
        ("jit_prefill", 101 * ms, 105 * ms),
        ("jit__lambda", 106 * ms, 115 * ms), ("jit__argmax", 115 * ms,
                                              116 * ms),
    ]
    rec = {"trace": _summary(modules, calls), "requests": 4}
    # (8 + 1 + 0.5) ms and 4 ms over 4 requests
    assert abs(_read("serve.prefill_ms_per_request", rec) - 13.5 / 4) < 1e-9
    assert _read("serve.prefill_ms_per_request", {"requests": 4}) is None
    untimed = dict(rec, trace=_summary(modules, []))
    assert _read("serve.prefill_ms_per_request", untimed) is None


def test_cache_memory_per_request_sums_kv_and_recurrent_bytes():
    rec = {"requests": 64, "counters": {"serve.cache_bytes:kv": 6.4e9,
                                        "serve.cache_bytes:recurrent": 3.2e9}}
    assert abs(_read("serve.cache_mb_per_request", rec) - 150.0) < 1e-9
    # a program without the counters (the parent of this reader) reads
    # nothing rather than a wrong number
    assert _read("serve.cache_mb_per_request",
                 {"requests": 64, "counters": {}}) is None


def _decode_rec(cfg, traffic, step_s):
    class Trace:
        def module_seconds(self, name):
            return (100, 100 * step_s) if name == "jit__lambda" else (0, 0.0)
    return {"trace": Trace(), "config": cfg, "device_kind": "TPU v5 lite",
            "batch_slots": traffic["batch_slots"],
            "prompt_len": traffic["prompt_len"],
            "max_new": traffic["max_new"]}


def test_the_family_roofline_of_qwen3_is_the_dense_roofline():
    cfg = cells._json("perfbench/configs/qwen3-0.6b.json")
    rec = _decode_rec(cfg, cells._json("perfbench/traffic/decode.json"),
                      0.0129)
    want = _read("decode_step_roofline", rec)
    assert 30 < want < 40
    assert _read("decode_step_roofline.by_family", rec) == want


def test_the_family_roofline_of_zamba2_counts_its_state_and_sites():
    cfg = cells._json("perfbench/configs/zamba2-7b.json")
    t = cells._json("perfbench/traffic/decode.json")
    fam = harness.load_module("families", "zamba2")
    live = t["batch_slots"] * sum(t["prompt_len"] + j
                                  for j in range(1, t["max_new"])) \
        / (t["max_new"] - 1)
    least_s = fam.decode_step_min_bytes(cfg, live, t["batch_slots"]) / 819e9
    # the bytes bound it: 10.70 GB at 819 GB/s, 13.07 ms
    assert abs(least_s - 13.07e-3) < 0.01e-3
    got = _read("decode_step_roofline.by_family", _decode_rec(cfg, t, 0.026))
    assert abs(got - 100 * least_s / 0.026) < 1e-9


def test_the_family_roofline_reads_the_family_of_its_own_tree(tmp_path):
    """A family that only a copied tree holds is found by the reader loaded
    from that tree, as ``harness.metric_values(..., root)`` loads it."""
    import shutil
    name = "decode_step_roofline.by_family"
    for kind in ("metrics", "families"):
        (tmp_path / "perfbench" / kind).mkdir(parents=True)
    shutil.copy(f"{harness.ROOT}/perfbench/metrics/{name}.py",
                tmp_path / "perfbench" / "metrics")
    (tmp_path / "perfbench" / "families" / "only_here.py").write_text(
        "def decode_flops(cfg, ctx):\n    return 0.0\n\n\n"
        "def decode_step_min_bytes(cfg, live, batch):\n    return 819e7\n")
    cfg = {"family": "only_here"}
    rec = _decode_rec(cfg, cells._json("perfbench/traffic/decode.json"),
                      0.02)
    got = harness.load_module("metrics", name, str(tmp_path)).read(rec)
    # 819e7 bytes at 819 GB/s: 10 ms of a 20 ms step
    assert abs(got - 50.0) < 1e-9
