"""Plan cache: key completeness, unified-registry behavior, autotuner
parity, and the zero-recompile cold start (ISSUE 13 acceptance).

The key-completeness tests are the live half of the DLAF001 contract:
every trace-time knob must flip ``plan.trace_suffix()`` (and therefore
every plan key) — a knob outside the key is a dead knob.
"""
import json
import os
import subprocess
import sys
from contextlib import contextmanager

import pytest

import jax

from dlaf_tpu import tune
from dlaf_tpu.obs import metrics as om
from dlaf_tpu.plan import autotune
from dlaf_tpu.plan import core as plan_core
from dlaf_tpu.serve import bucketing
from dlaf_tpu.serve.context import serving


@contextmanager
def _tuned(**kw):
    tune.initialize(**kw)
    try:
        yield
    finally:
        tune.initialize()


@pytest.fixture(autouse=True)
def _fresh_plan():
    plan_core.reset()
    yield
    plan_core.reset()
    autotune.clear_profile()


# ------------------------------------------------------- key completeness

_KNOB_FLIPS = [
    ("collectives_impl", "psum", "v2"),
    ("panel_trsm_pallas", False, True),
    ("gemm_precision", "default", "bf16x6"),
    ("bucket_segment_ratio", 1.26, 2.0),
    ("trsm_lookahead", False, True),
    ("cholesky_lookahead", False, True),
]


@pytest.mark.parametrize("knob,a,b", _KNOB_FLIPS,
                         ids=[k for k, _, _ in _KNOB_FLIPS])
def test_plan_key_flips_with_tune_knob(knob, a, b):
    """Every trace-time tune knob must flip the plan key — the property
    DLAF001 checks statically, asserted live for the full knob set."""
    with _tuned(**{knob: a}):
        ka = plan_core.plan_key("op", (1,))
    with _tuned(**{knob: b}):
        kb = plan_core.plan_key("op", (1,))
    assert ka != kb, f"flipping {knob} did not change the plan key"


def test_plan_key_flips_with_serving_token():
    base = plan_core.plan_key("op", (1,))
    with serving(("potrf", 256)):
        tok = plan_core.plan_key("op", (1,))
    assert base != tok
    assert plan_core.plan_key("op", (1,)) == base


def test_plan_key_flips_with_profile_fingerprint(tmp_path):
    base = plan_core.plan_key("op", (1,))
    prof = tmp_path / "profile.json"
    prof.write_text(json.dumps({
        "schema": autotune.PROFILE_SCHEMA, "entries": [], "auto": {}}))
    autotune.load_profile(str(prof))
    try:
        assert plan_core.plan_key("op", (1,)) != base
    finally:
        autotune.clear_profile()
    assert plan_core.plan_key("op", (1,)) == base


def test_plan_key_static_part_and_op_distinguish():
    assert plan_core.plan_key("a", (1,)) != plan_core.plan_key("b", (1,))
    assert plan_core.plan_key("a", (1,)) != plan_core.plan_key("a", (2,))


# ------------------------------------------------------- registry behavior

def test_cached_hit_miss_and_evict_counters():
    builds = []

    def build():
        builds.append(1)
        return lambda: "exe"

    f1 = plan_core.cached("t", (1,), build)
    f2 = plan_core.cached("t", (1,), build)
    assert f1 is f2 and len(builds) == 1
    st = plan_core.stats()
    assert st["hit"] == 1 and st["miss"] == 1 and st["build"] == 1
    assert st["entries"] == 1 and st["hit_rate"] == 0.5

    assert plan_core.evict(plan_core.plan_key("t", (1,)))
    assert not plan_core.evict(plan_core.plan_key("t", (1,)))
    assert plan_core.stats()["entries"] == 0


def test_cached_emits_plan_events(tmp_path):
    path = tmp_path / "m.jsonl"
    om.enable(str(path))
    try:
        plan_core.cached("evt", (), lambda: (lambda: None))
        plan_core.cached("evt", (), lambda: (lambda: None))
    finally:
        om.close()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    plan = [r for r in recs if r.get("kind") == "plan"]
    events = [r["event"] for r in plan]
    assert "miss" in events and "build" in events and "hit" in events
    build = next(r for r in plan if r["event"] == "build")
    assert build["op"] == "evt" and build["seconds"] >= 0
    assert "compiles" in build and "aot_loads" in build


def test_compiled_cache_delegates_to_plan(grid_1x1):
    """The serve LRU is a view over the plan registry: a CompiledCache
    build lands in plan storage, and LRU eviction releases the plan
    entry."""
    cache = bucketing.CompiledCache(capacity=1)
    cache.get(("k1", 1), lambda: (lambda: "e1"))
    assert plan_core.stats()["entries"] == 1
    cache.get(("k2", 2), lambda: (lambda: "e2"))  # evicts k1
    st = plan_core.stats()
    assert st["entries"] == 1 and st["evict"] == 1


# ------------------------------------------------------- autotuner parity

def test_autotune_defaults_match_hand_tuned_rules():
    """With no profile loaded, every analytical rule reproduces the
    hand-tuned default bit-identically (the model is a refactor)."""
    assert autotune.block_size("potrf", 96) == 96
    assert autotune.block_size("potrf", 4096) == 128
    assert autotune.grid_shape(8) == (2, 4)
    assert autotune.grid_shape(7) == (1, 7)
    assert autotune.collectives_tier("cpu") == "psum"
    assert autotune.collectives_tier("tpu") == "v2"
    lim = int(tune.get_tune_parameters().serve_batch_shard_max_n)
    assert autotune.shard_batch("potrf", lim) is True
    assert autotune.shard_batch("potrf", lim + 1) is False
    assert autotune.gemm_tier_override() is None


def test_autotune_profile_overrides_and_decision(tmp_path):
    prof = tmp_path / "profile.json"
    prof.write_text(json.dumps({
        "schema": autotune.PROFILE_SCHEMA,
        "entries": [{"op": "potrf", "n": 512, "dtype": "<f4",
                     "choice": {"nb": 64, "shard_batch": True}}],
        "auto": {"collectives_impl": "psum", "gemm_precision": "bf16x3"},
    }))
    autotune.load_profile(str(prof))
    assert autotune.profile_fingerprint()
    assert autotune.block_size("potrf", 512, "float32") == 64
    assert autotune.shard_batch("potrf", 512, "float32") is True
    assert autotune.collectives_tier("tpu") == "psum"
    assert autotune.gemm_tier_override() == "bf16x3"
    d = autotune.decide("potrf", 512, "float32", ndevices=8, backend="cpu")
    assert d.source == "profile" and d.nb == 64
    # unmatched geometry falls back to the analytic rules
    assert autotune.block_size("potrf", 256, "float32") == 128
    assert autotune.decide("eigh", 256, ndevices=8).source == "analytic"


def test_autotune_bad_profile_rejected(tmp_path):
    from dlaf_tpu.health import ConfigurationError

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ConfigurationError):
        autotune.load_profile(str(bad))
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"schema": "nope/9"}))
    with pytest.raises(ConfigurationError):
        autotune.load_profile(str(wrong))


def test_sweep_cli_writes_loadable_profile(tmp_path):
    from dlaf_tpu.plan import sweep

    out = tmp_path / "profile.json"
    assert sweep.main(["--ops", "potrf", "--ns", "16", "--nbs", "16",
                       "--batch", "1", "--repeat", "1",
                       "--out", str(out)]) == 0
    prof = autotune.load_profile(str(out))
    assert prof["schema"] == autotune.PROFILE_SCHEMA
    assert prof["entries"]
    assert autotune.profile_fingerprint()


# ------------------------------------------------- zero-recompile cold start

@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir_resolution(tmp_path, env_dir):
    """Importing the miniapp harness keeps JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says, as is; without it the cache is the
    fixed in-checkout default (a fresh interpreter: the choice is made at
    import)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("DLAF_TPU_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR")}
    want = str(tmp_path / "x") if env_dir else tune.DEFAULT_COMPILE_CACHE
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = ("import jax, dlaf_tpu.miniapp.common; "
            "print(jax.config.jax_compilation_cache_dir)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == want
    assert tune.DEFAULT_COMPILE_CACHE == os.path.join(root, ".jax_cache")


def test_zero_recompile_warm_cache(tmp_path, grid_1x1):
    """ISSUE 13 acceptance oracle, in-process: with the persistent
    compilation cache warm, replaying the same bucket ladder after
    dropping every in-memory executable performs ZERO backend compiles —
    every plan is an AOT load.  (The cross-process version is
    scripts/plan_cold_start.py, run by the CI lane.)"""
    cache_dir = tune.setup_compile_cache(
        str(tmp_path / "xla"), min_compile_s=0, force=True)
    assert cache_dir
    jax.clear_caches()  # earlier tests' in-memory executables are not "cold"
    try:
        cold = plan_core.warmup(
            buckets=(16,), ops=("potrf", "posv"), grid=grid_1x1,
            cache=bucketing.CompiledCache())
        assert cold["plans"] == 2
        assert cold["compiles"] > 0, "cold pass should compile"

        # Emulate a fresh process: drop the plan registry and every
        # in-memory jit executable; only the on-disk cache survives.
        plan_core.reset()
        jax.clear_caches()

        warm = plan_core.warmup(
            buckets=(16,), ops=("potrf", "posv"), grid=grid_1x1,
            cache=bucketing.CompiledCache())
        assert warm["compiles"] == 0, (
            f"warm replay recompiled: {warm['compiles']} backend compiles"
        )
        assert warm["aot_loads"] > 0
        assert all(r["compiles"] == 0 for r in warm["records"])
    finally:
        tune.disable_compile_cache()
        plan_core.reset()
        jax.clear_caches()


def test_warmup_emits_plan_warmup_events(tmp_path, grid_1x1):
    path = tmp_path / "m.jsonl"
    om.enable(str(path))
    try:
        plan_core.warmup(buckets=(16,), ops=("potrf",), grid=grid_1x1,
                         cache=bucketing.CompiledCache())
    finally:
        om.close()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    warm = [r for r in recs if r.get("kind") == "plan"
            and r.get("event") == "warmup"]
    assert len(warm) == 1
    r = warm[0]
    assert r["op"] == "potrf" and r["n"] == 16
    assert {"seconds", "compiles", "aot_loads"} <= set(r)


def test_warmup_unknown_op_rejected(grid_1x1):
    from dlaf_tpu.health import ConfigurationError

    with pytest.raises(ConfigurationError):
        plan_core.warmup(buckets=(16,), ops=("getrf",), grid=grid_1x1)
