"""The command, rehearsed on the CPU at tiny sizes: one well-formed last line
that says `cpu`; without `--rehearse-cpu` it refuses to measure."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib.manifest import ROOT, load_manifest

ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}


def _run(*extra, timeout=300):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *extra], cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("workload", [w["name"] for w in load_manifest()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_one_well_formed_line(workload, trace):
    p = _run("--workload", workload, "--seed", str(2 ** 31 + 12345), "--seconds", "2",
             "--trace", str(trace), "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["failed"] == 0 and line["attempted"] >= 8 and line["attempted"] % 8 == 0
    manifest = load_manifest()
    if trace:
        # nothing ran on a device: no device metric is made up from the CPU
        assert set(line["metrics"]) == {"setup_compile_s", "setup_trace_s",
                                        "loader_wait_share"}
        assert "busy_s" not in line["device"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
        assert all(v["value"] > 0 for v in line["metrics"].values())
    for name, c in line["checks"].items():
        assert f"check {name} = " in p.stderr
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_refuses_to_measure_without_a_tpu():
    p = _run("--workload", "bert_base.finetune", "--seed", "1", "--seconds", "1",
             "--trace", "0", timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_refuses_where_the_program_is_missing(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "bert_base.finetune",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
