"""Program names, HEEV host spans and the program-load counter.

A small HEEV on the path the chip runs (pipeline, SBR to band 32, native
chase, as the benchmark's CPU rehearsal configures it) and a small
distributed POTRF, through the public entries, with their compiles logged:
every program is named after its plan op (``plan.jit``), the HEEV run
enters the host spans a profile names its gaps by, and each compile or
load is charged to the phase open around it.
"""
import logging
import re
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlaf_tpu.testing as tu
from dlaf_tpu import obs, plan, tune
from dlaf_tpu.algorithms.cholesky import cholesky_factorization
from dlaf_tpu.algorithms.eigensolver import hermitian_eigensolver
from dlaf_tpu.matrix.matrix import DistributedMatrix
from dlaf_tpu.plan import core as plan_core

COMPILED = re.compile(r"Finished XLA compilation of (\S+) in")
HEEV_KNOBS = dict(eigensolver_sbr_band=32, eigensolver_min_band=100,
                  band_chase_backend="native")


class _Names(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        m = COMPILED.search(record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.fixture(scope="module")
def runs(grid_1x1, grid_2x4):
    """Every program compiled by one HEEV (n=256, nb=64, 1x1) and one POTRF
    (n=64, nb=16, 2x4), from an empty plan cache; the phases the HEEV run
    entered; and the loads of a second HEEV solve, by phase."""
    from dlaf_tpu.native import get_lib

    if get_lib() is None:
        pytest.skip("the native band chase did not build")
    tp = tune.get_tune_parameters()
    saved = {k: getattr(tp, k) for k in HEEV_KNOBS}
    handler = _Names()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    plan.reset()
    jax.clear_caches()
    tp.update(**HEEV_KNOBS)
    try:
        a = tu.random_hermitian_pd(256, np.float32, seed=5)
        with jax.log_compiles(True):
            obs.trace.start_phase_log()
            res = hermitian_eigensolver(
                "L", DistributedMatrix.from_global(grid_1x1, np.tril(a), (64, 64)),
                backend="pipeline")
            jax.block_until_ready(res.eigenvectors.data)
            phases = obs.trace.stop_phase_log()
            spd = tu.random_hermitian_pd(64, np.float32, seed=6)
            fac = cholesky_factorization(
                "L", DistributedMatrix.from_global(grid_2x4, np.tril(spd), (16, 16)))
            jax.block_until_ready(fac.data)
        keys = plan_core.keys()
        before = obs.program_loads()
        res = hermitian_eigensolver(
            "L", DistributedMatrix.from_global(grid_1x1, np.tril(a), (64, 64)),
            backend="pipeline")
        jax.block_until_ready(res.eigenvectors.data)
        second = _delta(before, obs.program_loads())
    finally:
        logger.removeHandler(handler)
        tp.update(**saved)
    return dict(names=handler.names, phases=phases, keys=keys, second=second)


def _delta(before, after) -> dict:
    out = {}
    for where, counts in after.items():
        d = {k: v - before.get(where, {}).get(k, 0) for k, v in counts.items()}
        if any(d.values()):
            out[where] = d
    return out


def test_every_program_named(runs):
    names = runs["names"]
    assert names, "no compile was logged"
    unnamed = [n for n in names if "_unknown" in n or "lambda" in n]
    assert not unnamed, unnamed
    for op in ("sbr_chunk", "transpose", "red2band", "band_gather",
               "bt_band_dist", "cholesky"):
        assert f"jit({op})" in names, (op, sorted(set(names)))


def test_plan_entries_named_after_their_op(runs):
    entries = [(key[0], plan_core.lookup(key)) for key in runs["keys"]]
    jitted = [(op, fn) for op, fn in entries if callable(fn)]
    assert {"sbr_chunk", "red2band", "cholesky"} <= {op for op, _ in jitted}
    assert all(fn.__name__ == op for op, fn in jitted), \
        [(op, fn.__name__) for op, fn in jitted if fn.__name__ != op]


@pytest.mark.parametrize("name", ["heev", "band_stage/sbr/chunk", "band_stage/sbr/readback",
                                  "band_stage/chase/native", "band_stage/chase/phases",
                                  "bt_band/factors", "bt_band/apply", "tridiag/leaves"])
def test_heev_enters_host_span(runs, name):
    assert name in runs["phases"], sorted(set(runs["phases"]))


def test_heev_stage_keys_unchanged(runs):
    """The stage timer's keys (``heev_stage_s.*``) are the stages', not the
    finer spans'."""
    from dlaf_tpu.common import stagetimer

    assert runs["phases"][0] == "heev"
    stagetimer.start()
    try:
        with obs.stage("tridiag"), obs.trace.phase("tridiag/leaves"):
            pass
    finally:
        stages = stagetimer.stop()
    assert list(stages) == ["tridiag"]


def test_second_heev_solve_loads_only_transpose(runs):
    """Once warm, a solve compiles one program: ``transpose``'s, which it
    builds anew on every call, charged to ``red2band``."""
    assert runs["second"] == {"red2band": {"compiled": 1, "loaded": 0}}


def test_fresh_program_counts_once_under_its_stage():
    x = jnp.ones(8)
    f = plan.jit("counted_once", lambda v: v * 3)
    before = obs.program_loads()
    with obs.stage("x_counter_stage"):
        f(x)
    first = _delta(before, obs.program_loads())
    with obs.stage("x_counter_stage"):
        f(x)
    second = _delta(before, obs.program_loads())
    assert first == {"x_counter_stage": {"compiled": 1, "loaded": 0}}
    assert second == first


def test_load_counted_under_innermost_phase_and_outside_any():
    x = jnp.ones(4)
    before = obs.program_loads()
    with obs.stage("outer_stage"), obs.trace.phase("outer_stage/inner"):
        plan.jit("inner_prog", lambda v: v - 1)(x)
    plan.jit("bare_prog", lambda v: v + 2)(x)
    got = _delta(before, obs.program_loads())
    assert got["outer_stage/inner"] == {"compiled": 1, "loaded": 0}
    assert "outer_stage" not in got
    assert got[obs.trace.NO_PHASE]["compiled"] >= 1


def test_listeners_registered_once(tmp_path):
    from jax._src import monitoring

    from dlaf_tpu.obs import metrics

    def ours():
        lists = (monitoring._event_listeners, monitoring._event_duration_secs_listeners,
                 monitoring._scalar_listeners)
        return [len([f for f in fs if f.__module__.startswith("dlaf_tpu")]) for fs in lists]

    plan_core.register_monitoring()
    counts = ours()
    for i in range(2):
        import dlaf_tpu.obs.metrics  # noqa: F401
        import dlaf_tpu.plan.core  # noqa: F401

        metrics.enable(str(tmp_path / f"m{i}.jsonl"))
        metrics.close()
        plan_core.register_monitoring()
        plan.compile_counts()
    assert ours() == counts == [1, 1, 1]


def test_compile_records_still_reach_the_metrics_stream(tmp_path):
    from dlaf_tpu.obs import metrics

    path = str(tmp_path / "m.jsonl")
    metrics.enable(path)
    try:
        plan.jit("streamed_prog", lambda v: v * 5)(jnp.ones(3))
    finally:
        metrics.close()
    recs = metrics.read_jsonl(path)
    assert any(r["kind"] == "compile" and r["event"].endswith("backend_compile_duration")
               for r in recs)


def test_jit_keeps_static_argnames_and_donation():
    f = plan.jit("scaled", lambda v, k: v * k, static_argnames=("k",), donate_argnums=(0,))
    assert f.__name__ == "scaled"
    np.testing.assert_allclose(f(jnp.ones(3), k=4), 4.0)
    text = f.lower(jnp.ones(3), k=4).as_text()
    assert "module @jit_scaled" in text


def test_load_span_on_the_profiler_clock():
    """A compile inside a phase lies under ``program_load/<phase>/jit_<op>``
    on the host timeline, inside the phase's own span."""
    from jax.profiler import ProfileData

    x = jnp.ones(16)
    f = plan.jit("traced_load", lambda v: jnp.tanh(v))
    logdir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(logdir)
        try:
            with obs.trace.phase("load_phase"):
                f(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        import glob
        import os

        path = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
        host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
                for line in plane.lines for ev in line.events]
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    spans = {name: (s, e) for name, s, e in host}
    load = spans["program_load/load_phase/jit_traced_load"]
    outer = spans["load_phase"]
    assert outer[0] <= load[0] < load[1] <= outer[1]
