"""The benchmark command end to end on the CPU: every cell rehearsed (set-up,
window or traced solves, the check) on virtual devices at a sixteenth of
the size; the control's readings; and the runs that must print no
result -- without a TPU, and from a directory holding only the
benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "DLAF_TPU_"))}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _bench(*args, cwd=ROOT, script="benchmark/run.py", timeout=240):
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=_env(),
                          capture_output=True, text=True, timeout=timeout)


def _expected(cell: str, trace: int) -> set:
    group = BENCH["per_layer"] if trace else BENCH["end_to_end"]
    return {m["name"] for m in group if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    r = _bench("--workload", cell, "--seed", "3000000019", "--seconds", "0.5",
               "--trace", str(trace), "--rehearse")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip() == ""  # a rehearsal prints no result line
    line = [x for x in r.stderr.splitlines() if x.startswith("rehearsal (no chip")][-1]
    result = json.loads(line.split(": ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == next(
        w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    got = set(result["metrics"])
    if trace:
        # readers that find nothing to read on the CPU (no collective op is
        # named as on the chip) leave their metric out
        assert got <= _expected(cell, 1) and "device_idle_share" in got
        assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    else:
        assert got == _expected(cell, 0)
        assert all(m["value"] > 0 for m in result["metrics"].values())
    for name, check in result["checks"].items():
        assert check["value"] <= check["limit"], name
        assert f"check {name} = " in r.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_control_readings(cell):
    """The control path runs at rehearsal size and reports every compared
    number for both variants (the CPU ignores matmul precision, so the chip
    alone tells them apart: PERF.md)."""
    r = _bench("--workload", cell, "--seeds", "5", "--seconds", "0.1", "--rehearse",
               script="benchmark/control.py")
    assert r.returncode == 0, r.stderr[-3000:]
    rows = [json.loads(x) for x in r.stdout.splitlines()]
    assert [x["variant"] for x in rows] == ["sound", "control"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           next(w["config"] for w in BENCH["workloads"]
                                if w["name"] == cell) + ".json")) as f:
        limits = json.load(f)["limits"]
    for row in rows:
        assert set(row["checks"]) == set(limits)


def test_no_tpu_no_result():
    r = _bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_only_benchmark_files_no_result(tmp_path):
    """A directory with BENCHMARK.json and the files under ``paths`` alone
    (no library) exits non-zero with no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
               cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
