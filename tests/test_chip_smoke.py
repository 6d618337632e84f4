"""chip_smoke.py's phases on the CPU at reduced size (kernels in interpret
mode), and its device gate. The script itself runs only on a TPU."""
import importlib.util
import os

import pytest

from repro.configs import get_config, reduce_config
from repro.configs.paper_cnns import RESNET18

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

LM = reduce_config(get_config("qwen3-0.6b"))


def test_kernel_phase_matches_reference():
    out = chip_smoke.kernel_phase(LM, interpret=True)
    for k in ("matmul_up", "matmul_down"):
        assert out[k]["max_err"] <= out[k]["max_bound"]
    assert out["act_clip"]["shape"] == [256, LM.d_ff]
    assert 0 < out["act_clip"]["zeros"] < 256 * LM.d_ff


def test_serve_phase_serves_every_request():
    out = chip_smoke.serve_phase(LM, n_requests=4, n_open_loop=3,
                                 prompt_len=16, max_new=8, batch_slots=2)
    assert out["decode_max_err"] <= chip_smoke.DECODE_RTOL * out["logit_scale"]
    assert out["generate"] == {"requests": 4, "new_tokens": 32}
    assert out["open_loop"]["requests"] == 3
    assert out["sampled_logit_arrays"] > 0


def test_search_phase_runs_vmapped_waves():
    out = chip_smoke.search_phase(reduce_config(RESNET18), iters=8,
                                  batch_size=4)
    assert out["trials"] == 8
    assert out["batch_shapes"] == [4]          # one compiled wave shape
    assert sum(out["dse_engines"].values()) > 0


def test_main_fails_without_a_tpu(monkeypatch, tmp_path, capsys):
    # with the variable set, main() configures no cache of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
