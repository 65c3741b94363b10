"""A new configuration, traffic mix, cell and per-layer metric are new files
and new ``BENCHMARK.json`` entries: in a copy of the benchmark with such
files dropped in, the new cell rehearses, reports the new metric, and
every file the benchmark already had is byte for byte what it was."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _digests(root, rels):
    return {r: hashlib.sha256(open(os.path.join(root, r), "rb").read()).hexdigest()
            for r in rels}


def test_new_cell_by_new_files_only(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench_dir = os.path.join(ROOT, "benchmark")
    shutil.copytree(bench_dir, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dlaf_tpu"), tmp_path / "dlaf_tpu")
    old = [os.path.relpath(os.path.join(d, f), tmp_path)
           for d, _, fs in os.walk(tmp_path / "benchmark") for f in fs]
    before = _digests(tmp_path, old)

    new = tmp_path / "benchmark"
    (new / "configs" / "throwaway-potrf-f32-n2048.json").write_text(json.dumps({
        "name": "throwaway-potrf-f32-n2048", "op": "potrf", "source": "test",
        "matrix_size": 2048, "block_size": 256, "type": "float32", "grid": [1, 1],
        "solve": {"backend": "distributed"}, "tune": {},
        "limits": {"offdiag_max_err": 1e-3, "offdiag_fro_err": 1e-3},
        "control": {"blas3_matmul_precision": "bfloat16"}}))
    (new / "traffic" / "throwaway_three_checked.json").write_text(json.dumps({
        "loop": "closed", "callers": 1, "checked_solves": 3, "trace_seconds": 0.2}))
    (new / "metrics" / "throwaway_traced_solves.py").write_text(
        "def read(ctx):\n    return float(ctx.solves)\n")
    bench["configs"].append({"name": "throwaway-potrf-f32-n2048", "source": "test",
                             "file": "benchmark/configs/throwaway-potrf-f32-n2048.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.1chip",
                               "config": "throwaway-potrf-f32-n2048",
                               "traffic": "throwaway_three_checked", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "throwaway_traced_solves", "unit": "solves",
                               "better": "higher", "source": "host_clock", "layer": "test",
                               "moves": "gflops_per_chip", "workloads": ["throwaway.1chip"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "DLAF_TPU_"))}
    r = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "throwaway.1chip",
                        "--seed", "4000000007", "--seconds", "0.3", "--trace", "1",
                        "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stderr.splitlines() if x.startswith("rehearsal (no chip")][-1]
    result = json.loads(line.split(": ", 1)[1])
    assert result["correct"] is True
    assert result["metrics"]["throwaway_traced_solves"]["value"] >= 1
    assert "device_idle_share" not in result["metrics"]  # not listed for this cell
    assert _digests(tmp_path, old) == before
