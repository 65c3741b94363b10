"""The readers of the per-layer metrics that read the library's program
names and host spans (``benchmark/metrics/``), on synthetic reductions
built with ``benchmark/trace_reduce.py``: each returns the expected value,
and nothing where its program or span is absent."""
import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WINDOW = "bench/traced_window"


def _load(rel):
    path = os.path.join(ROOT, "benchmark", rel)
    name = "readers_" + rel.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tr():
    return _load("trace_reduce.py")


def _reader(metric):
    return _load(f"metrics/{metric}.py")


def _ctx(tr, devices, host, solves=2, lo=0, hi=10_000_000_000):
    return SimpleNamespace(trace=tr.reduce(devices, host, lo, hi), solves=solves)


def _two_chips(tr, program):
    """Per chip: 3 s in ``program`` (a loop holding a 1 s fusion), 1 s in
    another program; window 10 s."""
    s = 1_000_000_000
    ops = [tr.Op("while.1", 0, 3 * s, program), tr.Op("fusion.2", s, 2 * s, program),
           tr.Op("fusion.9", 4 * s, 5 * s, "jit_dc_params")]
    return {"/device:TPU:0": ops, "/device:TPU:1": list(ops)}


def test_sbr_device_seconds(tr):
    ctx = _ctx(tr, _two_chips(tr, "jit_sbr_chunk"), [(WINDOW, 0, 10**10)], solves=2)
    assert _reader("heev_sbr_device_s").read(ctx) == pytest.approx(1.5)


@pytest.mark.parametrize("program", ["jit__unknown", "jit_sbr_bt_apply"])
def test_sbr_device_seconds_absent(tr, program):
    ctx = _ctx(tr, _two_chips(tr, program), [(WINDOW, 0, 10**10)])
    assert _reader("heev_sbr_device_s").read(ctx) is None


@pytest.mark.parametrize("metric, span", [("heev_chase_host_s", "band_stage/chase/native"),
                                          ("heev_bt_factors_host_s", "bt_band/factors")])
def test_host_span_seconds(tr, metric, span):
    """Two solves' spans inside the window, one clipped by its end, one
    outside it, and a span of another name."""
    host = [(WINDOW, 100, 1100), (span, 200, 300), (span, 600, 750),
            (span, 1050, 1250), (span, 1500, 1600), ("band_stage/chase/phases", 300, 900)]
    ctx = _ctx(tr, _two_chips(tr, "jit_sbr_chunk"), host, solves=2, lo=100, hi=1100)
    assert _reader(metric).read(ctx) == pytest.approx((100 + 150 + 50) / 2 / 1e9)


@pytest.mark.parametrize("metric", ["heev_chase_host_s", "heev_bt_factors_host_s"])
def test_host_span_seconds_absent(tr, metric):
    host = [(WINDOW, 0, 1000), ("band_stage/chase", 0, 500), ("bt_band", 500, 900)]
    ctx = _ctx(tr, _two_chips(tr, "jit_sbr_chunk"), host, lo=0, hi=1000)
    assert _reader(metric).read(ctx) is None


@pytest.mark.parametrize("loads, solves, expected", [
    ([], 4, 0.0),
    ([(250, 260)], 1, 1.0),
    ([(250, 260), (700, 720), (1200, 1300)], 2, 1.0),  # the third starts after the window
])
def test_programs_loaded_per_solve(tr, loads, solves, expected):
    host = [(WINDOW, 100, 1100), ("bench/solve", 100, 1100)]
    host += [("program_load/red2band/jit_transpose", s, e) for s, e in loads]
    ctx = _ctx(tr, _two_chips(tr, "jit_cholesky"), host, solves=solves, lo=100, hi=1100)
    assert _reader("programs_loaded_per_solve").read(ctx) == pytest.approx(expected)


def test_programs_loaded_per_solve_needs_a_library_that_marks_loads(tr, monkeypatch):
    """A library without the load marks (an older one) gives nothing to
    read, not a 0 it never measured."""
    import dlaf_tpu.obs

    monkeypatch.delattr(dlaf_tpu.obs, "program_loads")
    host = [(WINDOW, 100, 1100)]
    ctx = _ctx(tr, _two_chips(tr, "jit_cholesky"), host, lo=100, hi=1100)
    assert _reader("programs_loaded_per_solve").read(ctx) is None
