"""The trace reduction on a small recorded v5e trace
(`perfbench/testdata/tiny_train.xplane.pb.gz`, made by
`perfbench/tools/record_trace.py`: 4 dispatches of a tiny BERT's 8-step scan)
and on planes made by hand."""

import gzip
import os

import pytest

from perfbench.lib import trace_reduce
from perfbench.lib.manifest import BENCH_DIR


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny_train.xplane.pb"
    with gzip.open(os.path.join(BENCH_DIR, "testdata", "tiny_train.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return trace_reduce.read_planes(str(path))


def test_recorded_trace_planes(recorded):
    assert list(recorded["device"]) == ["/device:TPU:0"]
    assert len(recorded["modules"]["/device:TPU:0"]) == 4
    categories = {e[3] for e in recorded["device"]["/device:TPU:0"]}
    assert {"while", "convolution fusion", "loop fusion"} <= categories


def test_recorded_trace_numbers(recorded):
    r = trace_reduce.reduce_planes(recorded)
    # four runs of jit_multi in the trace: the window goes from the second
    # one's start to the fourth's, and holds two 8-step programs of 1.62 ms
    assert len(recorded["modules"]["/device:TPU:0"]) == 4
    assert r["cycles"] == 2
    assert r["busy_s"] == pytest.approx(2 * 1.618e-3, rel=5e-3)
    assert 0.4 < r["matmul_s"] / r["busy_s"] < 0.7
    assert r["window_s"] == pytest.approx(0.0170006, rel=1e-4)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10 and all(not n.startswith("while") for n, _ in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["between_dispatches", "between_dispatches"]
    assert all(6e-3 < g[1] < 8e-3 for g in gaps)


def test_union_and_gap_names_by_hand():
    ms = 1e6
    run = "jit_multi(1)"
    planes = {
        "device": {"/device:TPU:0": [
            ("%fusion.9 = ...", 1 * ms, 4 * ms, "loop fusion"),      # before the window
            ("%while.1 = ...", 10 * ms, 30 * ms, "while"),
            ("%fusion.1 = ...", 10 * ms, 20 * ms, "convolution fusion"),
            ("%fusion.2 = ...", 20 * ms, 30 * ms, "loop fusion"),
            ("%fusion.1 = ...", 60 * ms, 70 * ms, "convolution fusion"),
            ("%fusion.2 = ...", 65 * ms, 72 * ms, "loop fusion"),
            ("%fusion.2 = ...", 76 * ms, 80 * ms, "loop fusion"),
            ("%fusion.1 = ...", 110 * ms, 120 * ms, "convolution fusion")]},
        "modules": {"/device:TPU:0": [
            (run, 0.0, 4 * ms, ""), ("jit_small(2)", 5 * ms, 6 * ms, ""),
            (run, 10 * ms, 30 * ms, ""), (run, 60 * ms, 80 * ms, ""),
            (run, 110 * ms, 120 * ms, "")]}}
    r = trace_reduce.reduce_planes(planes)
    # the first run of the main program is left out; two cycles: 10-60, 60-110
    assert r["cycles"] == 2 and r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.036)       # 10-30, 60-72, 76-80
    assert r["matmul_s"] == pytest.approx(0.020)
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx(
        {"fusion.2": 0.021, "fusion.1": 0.020})
    # no program ran in 30-60 and 80-110; 72-76 lies inside the second run
    assert r["breakdown"]["idle_gaps"] == [
        ["between_dispatches", pytest.approx(0.030)],
        ["between_dispatches", pytest.approx(0.030)],
        ["inside_program", pytest.approx(0.004)]]
    planes["modules"] = {}
    whole = trace_reduce.reduce_planes(planes)       # no program run to mark cycles
    assert whole["cycles"] is None and whole["window_s"] == pytest.approx(0.119)


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce_planes({"device": {}, "modules": {}}) is None
