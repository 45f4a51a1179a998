"""The control and the planted faults at a size a test run holds: each
reading of `bench.control` against the small cell's own limits.  The
control here is bfloat16, the precision below the float32 the small
configuration states."""
import json

import pytest

from harness_util import CELL, make_root


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    from bench.check import numbers
    from bench.control import readings
    from bench.run import find_cell
    root = make_root(tmp_path_factory.mktemp("control"), size="smoke")
    cell = find_cell(root, CELL)
    runs = []
    for seed in (3, 3000000021):
        base, out = readings(cell, seed)
        runs.append({k: numbers(v, base) for k, v in out.items()})
    return cell.limits, runs


def failed(nums, limits):
    return [k for k in limits if nums[k] > limits[k]]


def test_sound_redraw_passes(readings):
    limits, runs = readings
    for r in runs:
        assert r["sound"]["loss0_gap"] == 0.0
        assert not failed(r["sound"], limits), r["sound"]


@pytest.mark.parametrize("name", ["control", "half_batch", "double_leaf",
                                  "unchanged"])
def test_control_and_faults_fail(readings, name):
    limits, runs = readings
    for r in runs:
        assert failed(r[name], limits), json.dumps(r[name])
