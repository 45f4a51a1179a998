"""The harness is driven by data and refuses to run without a chip; a
cell whose traffic asks for a mesh runs over the cell's chips (four CPU
devices here)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness_util import (CELL, ROOT, SHARDED_CELL, drive, last_json,
                          make_root, make_sharded_root, run_child)


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


def _sharded(tmp_path, fault="none", flags=(), seed=3000000041):
    p = run_child(make_sharded_root(tmp_path, flags), fault=fault,
                  cell=SHARDED_CELL, devices=4, seed=seed)
    notes = {}
    for line in p.stdout.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        notes.setdefault(rec.get("phase"), rec)
    return p, notes


def test_sharded_cell_is_correct_with_every_leaf_on_every_chip(tmp_path):
    p, notes = _sharded(tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_json(p.stdout)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    placed = notes["placement"]
    assert placed["mesh"] == {"data": 2, "fsdp": 2, "model": 1}
    assert placed["leaves"] > 0
    assert placed["on_every_chip"] == placed["leaves"]
    assert notes["window"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["half_batch", "double_leaf"])
def test_sharded_broken_step_is_not_correct(tmp_path, fault):
    p, _ = _sharded(tmp_path, fault=fault)
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_json(p.stdout)
    assert out["correct"] is False, out["checks"]


def test_sharded_ring_layout_exits_with_no_result(tmp_path):
    p, _ = _sharded(tmp_path, flags=("--kernel-layout", "ring"))
    assert p.returncode == 1
    assert last_json(p.stdout) is None
    assert "does not compose" in p.stderr
