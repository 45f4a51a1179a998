"""The harness is driven by data and refuses to run without a chip."""
import os
import shutil
import subprocess
import sys

from harness_util import CELL, ROOT, drive, last_json, make_root


def run_script(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *args], capture_output=True, text=True, env=env,
                          timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = run_script(ROOT, "--workload", "granite-moe-1b-a400m-1chip.pdsgd.b1s1024",
                   "--seed", "3000000019", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert last_json(p.stdout) is None
    assert "no TPU" in p.stderr


def test_bare_directory_exits_nonzero_with_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_script(tmp_path, "--workload", "granite-moe-1b-a400m-1chip.pdsgd.b1s1024",
                   "--seed", "7", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert last_json(p.stdout) is None


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path, extra_metric="tokens_in_window")
    rc, out, err = drive(root, trace=1)
    assert rc == 0, err[-3000:]
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["tokens_in_window"]["value"] > 0
    assert "data_wait_ms" in out["metrics"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss0_gap", "loss_gap", "consensus0_gap",
                                  "update_gap"}
