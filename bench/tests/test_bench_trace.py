"""The trace reduction on intervals laid out by hand, and on a small trace
recorded on a TPU v5e: a jitted matmul program and the obfuscate and gossip
kernels, three times, inside the harness's ``bench.*`` host spans (a 2 ms
sleep stands for taking the next chunk)."""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "small_chip.xplane.pb"
OPS = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("_gossip_update.1", 60,
                                                      65)]
SPANS = [("bench.window", 0, 100), ("bench.next_chunk", 0, 12),
         ("bench.dispatch", 30, 45), ("bench.wait", 45, 100)]


def test_union_of_busy_intervals():
    assert T.merge([(s, e) for _, s, e in OPS]) == [(10, 30), (40, 50),
                                                     (60, 65)]
    assert T.busy_ns(OPS, 0, 100) == 20 + 10 + 5
    assert T.busy_ns(OPS, 12, 45) == 18 + 5


def test_idle_gaps_labelled_by_the_open_host_span():
    assert T.idle_gaps(OPS, 0, 100) == [(0, 10), (30, 40), (50, 60),
                                        (65, 100)]
    assert T.labelled_gaps(OPS, SPANS, 0, 100) == [
        ["bench.wait", 35e-9], ["bench.next_chunk", 10e-9],
        ["bench.dispatch", 10e-9], ["bench.wait", 10e-9]]
    assert T.open_span(SPANS, 200) == "none"


def test_kernels_matched_by_name_only():
    assert T.op_name("%fusion.3 = bf16[8] fusion(bf16[8] %_gossip_update.1)"
                     ) == "fusion.3"
    assert [o[0] for o in T.matching(OPS, ("obfuscate", "GOSSIP"))] == [
        "_gossip_update.1"]
    assert T.top_ops(OPS, 2) == [["b", 15e-9], ["c", 10e-9]]
    # a loop's own time leaves out the operations of its body
    loop = [("while.1", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 60),
            ("fusion.1", 70, 90)]
    assert T.top_ops(loop) == [["fusion.1", 50e-9], ["while.1", 40e-9],
                               ["fusion.2", 10e-9]]


@pytest.fixture(scope="module")
def chip():
    return T.load(str(DATA))


def test_chip_trace_planes_and_window(chip):
    assert list(chip.device_ops) == ["/device:TPU:0"]
    lo, hi = chip.window()
    ops = chip.device_ops["/device:TPU:0"]
    busy = T.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    assert busy == sum(e - s for s, e in T.merge(
        (s, e) for _, s, e in T.clip(ops, lo, hi)))
    # every device operation ran inside the host's window: one clock
    assert all(lo <= s and e <= hi for _, s, e in ops)


def test_chip_trace_kernels_and_gaps(chip):
    ops = chip.device_ops["/device:TPU:0"]
    names = [o[0] for o in T.matching(ops, ("obfuscate", "gossip"))]
    assert names == ["_obfuscate_update_krng.1", "_gossip_update.1"] * 3
    lo, hi = chip.window()
    gaps = T.labelled_gaps(ops, chip.host_spans, lo, hi, 3)
    # the longest idle gaps are the 2 ms waits for the next chunk
    assert [g[0] for g in gaps] == ["bench.next_chunk"] * 3
    assert all(g[1] > 2e-3 for g in gaps)
