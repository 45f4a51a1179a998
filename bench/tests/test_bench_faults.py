"""A run with its timed path broken underneath comes out not correct: the
step returns its state unchanged; half of each batch is left out; one leaf
(an answer of the step) is moved double."""
import pytest

from harness_util import drive, make_root


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "double_leaf"])
def test_broken_step_is_not_correct(tmp_path, fault):
    rc, out, err = drive(make_root(tmp_path), fault=fault)
    assert rc == 0, err[-3000:]
    assert out["correct"] is False, out["checks"]


def test_sound_step_is_correct(tmp_path):
    rc, out, err = drive(make_root(tmp_path))
    assert rc == 0, err[-3000:]
    assert out["correct"] is True, out["checks"]
    assert {"tokens_per_s_per_chip", "setup_s"} <= set(out["metrics"])
