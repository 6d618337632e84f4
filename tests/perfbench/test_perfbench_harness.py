"""Discovery of a cell's files by name, the metric readers, the device
gate and the result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import cells
from perfbench import harness

ROOT = cells.ROOT
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_workload_resolves_to_its_files(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    harness.load_module("jobs", cell.config["job"]).run
    assert cell.checks and all("limit" in c for c in cell.checks.values())
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for m in cell.per_layer:
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_metric_and_config_has_its_file():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        harness.load_module("metrics", m["name"])
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["job"] in ("search", "serve")


def _copy_tree(tmp_path):
    """A checkout of the benchmark alone: BENCHMARK.json and its paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_a_new_traffic_file_is_found_with_no_code_edit(tmp_path):
    root = _copy_tree(tmp_path)
    with open(root / "perfbench" / "traffic" / "decode.json") as f:
        mix = json.load(f)
    mix["prompt_len"] = 1024
    with open(root / "perfbench" / "traffic" / "decode_long.json", "w") as f:
        json.dump(mix, f)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "serve.qwen3-0.6b.decode_long", "config": "qwen3-0.6b",
        "traffic": "decode_long", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / "perfbench" / "checks" / "serve.qwen3-0.6b.decode.json",
                root / "perfbench" / "checks"
                / "serve.qwen3-0.6b.decode_long.json")
    cell = harness.load_cell("serve.qwen3-0.6b.decode_long", root=str(root))
    assert cell.traffic["prompt_len"] == 1024
    assert cell.config["job"] == "serve"
    # metrics without a workloads list reach the new cell through `moves`
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]


def test_a_metric_without_a_cell_list_follows_its_end_to_end_metric():
    entries = [{"name": "a", "moves": "x"}, {"name": "b", "moves": "y"},
               {"name": "c", "moves": "y", "workloads": ["other"]}]
    assert [m["name"] for m in
            harness._metrics_for(entries, "cell", {"y"})] == ["b"]


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "serve.qwen3-0.6b.decode", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0")


def test_run_refuses_to_run_without_a_tpu():
    p = _run_py(ROOT, *ARGS)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_fails_in_a_tree_of_the_benchmark_alone(tmp_path):
    p = _run_py(str(_copy_tree(tmp_path)), *ARGS)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_result_line_has_the_contract_keys_and_checks_last():
    cell = cells.cell("serve.qwen3-0.6b.decode", cells.small_qwen3(),
                      dict(cells.SERVE_TRAFFIC),
                      {"served_logit_gap": {"limit": 0.5}})

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 123}

    run = harness.Run(cell, 1, 1.0, False, 0.0, [Dev()])
    rec = {"setup_s": 12.5, "window_s": 2.0, "output_tokens": 100}
    out = harness.Outcome(attempted=20, failed=0, record=rec,
                          checks=[harness.Check("served_logit_gap", 0.2,
                                                0.5)],
                          memory_peak_bytes=123)
    import jax
    line = harness.result_line(cell, run, out)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["metrics"] == {
        "tokens_per_s": {"value": 50.0, "unit": "tokens/s"},
        "setup_s": {"value": 12.5, "unit": "s"}}
    assert line["device"]["kind"] == "TPU v5 lite"
    assert line["device"]["count"] == len(jax.devices())
    out.checks = [harness.Check("served_logit_gap", 0.7, 0.5)]
    assert harness.result_line(cell, run, out)["correct"] is False
    out.checks = [harness.Check("served_logit_gap", float("nan"), 0.5)]
    assert harness.result_line(cell, run, out)["correct"] is False


def test_the_seed_gives_the_same_weights_and_traffic():
    import numpy as np
    from perfbench import weights
    cfg = cells.small_qwen3()
    big = 2 ** 40 + 17
    a = weights.lm_params(cfg, weights.seed_key(big))
    b = weights.lm_params(cfg, weights.seed_key(big))
    c = weights.lm_params(cfg, weights.seed_key(big + 1))
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    job = harness.load_module("jobs", "serve")
    t = cells.SERVE_TRAFFIC
    assert np.array_equal(job.prompts(cfg, t, big, 3),
                          job.prompts(cfg, t, big, 3))
    assert job.prompts(cfg, t, big, 3).shape == (t["batch_slots"],
                                                 t["prompt_len"])


FAMILY_DATA = os.path.join(ROOT, "tests", "perfbench", "data", "qwen3-untied")


def _add_family(root, reference):
    """Adds the test family, its config, traffic, checks and cell to the
    tree at ``root`` as new files, and the cell's name to the lists of
    ``BENCHMARK.json``; no file the tree had is edited."""
    pb = root / "perfbench"
    shutil.copy(os.path.join(FAMILY_DATA, "family.py"),
                pb / "families" / "qwen3-untied.py")
    shutil.copy(os.path.join(FAMILY_DATA, reference),
                pb / "reference" / "qwen3-untied.py")
    shutil.copy(os.path.join(FAMILY_DATA, "config.json"),
                pb / "configs" / "qwen3-untied-tiny.json")
    (pb / "traffic" / "tiny.json").write_text(
        json.dumps(cells.SERVE_TRAFFIC))
    name = "serve.qwen3-untied-tiny.tiny"
    shutil.copy(pb / "checks" / "serve.qwen3-0.6b.decode.json",
                pb / "checks" / f"{name}.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "qwen3-untied-tiny", "source": "test",
        "file": "perfbench/configs/qwen3-untied-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": name, "config": "qwen3-untied-tiny", "traffic": "tiny",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("tokens_per_s", "mfu.serve"):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def _files(root):
    return {os.path.relpath(os.path.join(d, f), root):
            open(os.path.join(d, f), "rb").read()
            for d, _, fs in os.walk(root) for f in fs
            if f != "BENCHMARK.json"}


@pytest.mark.parametrize("reference,correct", [
    ("reference.py", True),
    ("reference_tied_head.py", False),    # planted: the Qwen3 files' head
])
def test_a_new_family_enters_by_files_alone(tmp_path, reference, correct):
    root = _copy_tree(tmp_path)
    before = _files(root)
    name = _add_family(root, reference)
    after = _files(root)
    assert all(after[k] == v for k, v in before.items())
    cell = harness.load_cell(name, root=str(root))
    assert cell.root == str(root)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["mfu.serve"]
    out = cells.run(cell, seconds=0.3)
    assert harness.is_correct(out) is correct, out.checks
    assert out.record["flops"] > 0
