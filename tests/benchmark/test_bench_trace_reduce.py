"""The benchmark's trace reduction (benchmark/trace_reduce.py): interval
arithmetic on known intervals, and the loader on a trace recorded on the
CPU with a known host wait in it."""
import importlib.util
import os
import shutil
import tempfile
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def tr():
    path = os.path.join(ROOT, "benchmark", "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("bench_trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    import sys

    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 10), (5, 20), (30, 40)], [(0, 20), (30, 40)]),
    ([(30, 40), (0, 10), (10, 12)], [(0, 12), (30, 40)]),
    ([(0, 50), (10, 20), (5, 5)], [(0, 50)]),
])
def test_union(tr, intervals, merged):
    assert tr.union(intervals) == merged
    assert tr.length(merged) == sum(e - s for s, e in merged)


@pytest.mark.parametrize("a, b, rest", [
    ([(0, 100)], [(10, 20), (50, 60)], [(0, 10), (20, 50), (60, 100)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(tr, a, b, rest):
    assert tr.subtract(a, b) == rest


def test_one_device_known_intervals(tr):
    """Compute [0, 30) overlapped by a collective [20, 50); a lone
    collective-permute-done [60, 70); window [0, 100)."""
    ops = [tr.Op("fusion.7", 0, 30, "jit(f)/chol.trailing_update/dot"),
           tr.Op("all-reduce.3", 20, 50),
           tr.Op("collective-permute-done.1", 60, 70),
           tr.Op("fusion.9", 95, 130)]
    d = tr.reduce_device(ops, 0, 100)
    assert d.busy_ns == 50 + 10 + 5
    assert d.collective_ns == 40
    assert d.collective_exposed_ns == 20 + 10
    assert d.gaps == [(50, 60), (70, 95)]
    assert d.op_ns == {"jit(f)/chol.trailing_update/dot/fusion.7": 30, "all-reduce.3": 30,
                       "collective-permute-done.1": 10, "fusion.9": 5}


def test_nested_ops_self_time(tr):
    """A loop op holds its body's ops on the same line: its self time is
    what they leave, and it is no compute that hides a collective."""
    ops = [tr.Op("while.1", 0, 100, "jit_k"), tr.Op("fusion.2", 10, 40, "jit_k"),
           tr.Op("collective-permute-done.5", 50, 60, "jit_k")]
    d = tr.reduce_device(ops, 0, 100)
    assert d.busy_ns == 100
    assert d.collective_exposed_ns == 10
    assert d.op_ns == {"jit_k/while.1": 60, "jit_k/fusion.2": 30,
                       "jit_k/collective-permute-done.5": 10}


@pytest.mark.parametrize("text, name", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop", "fusion.12"),
    ("%custom-call.158 = f32[4]{0} custom-call(f32[4]{0} %b), "
     'custom_call_target="InvertDiagBlocksLowerTriangular"',
     "custom-call.158 (InvertDiagBlocksLowerTriangular)"),
    ('%potrf_tile.4 = f32[512,512]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"',
     "potrf_tile.4"),
    ("%collective-permute-start.2 = (f32[8]) collective-permute-start(%x)",
     "collective-permute-start.2"),
])
def test_instruction_names(tr, text, name):
    """An op that reads a collective's result is no collective."""
    assert tr.instruction(text) == name
    assert bool(tr.COLLECTIVE.search(tr.instruction(text))) == name.startswith("collective")


def test_means_over_devices_and_gap_labels(tr):
    devices = {"/device:TPU:0": [tr.Op("fusion.1", 0, 60)],
               "/device:TPU:1": [tr.Op("fusion.1", 0, 20), tr.Op("all-gather.2", 20, 40)]}
    host = [("bench/traced_window", 0, 100), ("band_stage", 50, 100),
            ("band_stage/chase", 55, 90)]
    red = tr.reduce(devices, host, 0, 100)
    assert red.busy_s() == pytest.approx(50e-9)
    assert red.window_s() == pytest.approx(100e-9)
    assert red.idle_share() == pytest.approx(0.5)
    assert red.collective_exposed_share() == pytest.approx((0 + 20 / 40) / 2)
    assert red.top_ops(1) == [["fusion.1", pytest.approx(40e-9)]]
    # the longest gap, [40, 100) on device 1, has its middle under the chase
    assert red.top_gaps(2) == [["band_stage/chase", pytest.approx(60e-9)],
                               ["band_stage/chase", pytest.approx(40e-9)]]
    assert tr.window_of(host, "bench/traced_window") == (0, 100)
    assert tr.window_of(host, "missing") is None


def test_recorded_cpu_trace(tr):
    """Device work, a 200 ms host wait, device work: the CPU's XLA threads
    stand in for a chip (as in a rehearsal), and the longest idle gap is
    the wait, labelled by the host annotation open over it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host_wait"):
                time.sleep(0.2)
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    try:
        devices, host = tr.load(tr.find_xplane(logdir), device_plane=r"^/host:CPU$",
                                op_line=r"^tf_XLA")
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    lo, hi = tr.window_of(host, "window")
    red = tr.reduce(devices, host, lo, hi)
    assert red.n == 1
    assert 0.2 <= red.window_s() < 5
    assert 0 < red.busy_s() <= red.window_s() - 0.2
    label, seconds = red.top_gaps(1)[0]
    assert label == "host_wait"
    assert 0.19 <= seconds <= 0.2 + 0.05
    assert red.idle_share() >= 0.2 / red.window_s()
