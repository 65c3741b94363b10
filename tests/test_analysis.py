"""dlaf_tpu.analysis — SPMD/trace-safety linter (ISSUE 8).

Covers the four rule families on minimal in-memory fixtures (one true
positive and one clean negative each), the suppression and baseline
round-trips, and — the acceptance core — four "reverted known bug" tests
that mutate the REAL tree back to a shipped bug and assert the linter
produces exactly the expected finding: the serve ``trsm_lookahead`` key
omission (DLAF001), a dropped Mosaic ``collective_id`` (DLAF002, the PR-6
semaphore-sharing class), a host sync inside the jitted DMA ring
(DLAF003), and the gateway dispatch-under-lock livelock (DLAF004).  The
meta-test at the bottom asserts the shipped tree is clean modulo the
checked-in baseline.

The linter never imports the linted files, so everything here is pure
AST work — no mesh, no compiles.
"""
import os
import textwrap

from dlaf_tpu.analysis import engine
from dlaf_tpu.analysis.__main__ import repo_root
from dlaf_tpu.analysis.engine import SourceFile
from dlaf_tpu.analysis.project import Project
from dlaf_tpu.analysis.rules import cache_keys, collectives, locks, program_names, purity

TUNE_FIXTURE = """
from dataclasses import dataclass

@dataclass
class TuneParameters:
    panel_width: int = 8
    lookahead: bool = False
    segment_ratio: float = 1.5

def get_tune_parameters():
    return TuneParameters()
"""


def _project(sources, with_tune=True):
    """Indexed Project over in-memory sources ({rel_path: text})."""
    if with_tune:
        sources = {"dlaf_tpu/tune.py": TUNE_FIXTURE, **sources}
    files = [
        SourceFile.from_text("/virtual/" + rel, rel, textwrap.dedent(text))
        for rel, text in sources.items()
    ]
    return Project(files).index()


def _real_tree_project(mutate_rel=None, mutate=None):
    """The real dlaf_tpu tree, optionally with one file's text mutated."""
    root = repo_root()
    files, errors = engine.load_files([os.path.join(root, "dlaf_tpu")], root=root)
    assert not errors
    if mutate_rel is not None:
        for i, f in enumerate(files):
            if f.rel == mutate_rel:
                text = mutate(f.text)
                assert text != f.text, f"mutation did not change {mutate_rel}"
                files[i] = SourceFile.from_text(f.path, f.rel, text)
                break
        else:
            raise AssertionError(f"{mutate_rel} not in the scanned tree")
    return Project(files).index()


# ------------------------------------------------------- DLAF001 cache keys


def _knob_findings(findings):
    """Key-coverage findings only (drop the module-dict-placement ones)."""
    return [f for f in findings if "module-level cache dict" not in f.message]


def test_dlaf001_dict_store_flags_missing_knob():
    proj = _project({"dlaf_tpu/algorithms/fact.py": """
        from dlaf_tpu.tune import get_tune_parameters

        _kernel_cache = {}

        def _build(n):
            p = get_tune_parameters()
            return ("exe", n, p.panel_width, p.lookahead)

        def factor(n):
            key = (n, get_tune_parameters().panel_width)
            if key not in _kernel_cache:
                _kernel_cache[key] = _build(n)
            return _kernel_cache[key]
    """})
    findings = _knob_findings(cache_keys.check(proj))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "DLAF001" and f.symbol == "factor"
    assert "lookahead" in f.message and "panel_width" not in f.message
    assert "read in _build" in f.message


def test_dlaf001_complete_key_and_derived_elements_are_clean():
    # lookahead enters the key through a derived local (variant = _variant())
    proj = _project({"dlaf_tpu/algorithms/fact.py": """
        from dlaf_tpu.tune import get_tune_parameters

        _kernel_cache = {}

        def _variant():
            return "la" if get_tune_parameters().lookahead else "plain"

        def _build(n):
            p = get_tune_parameters()
            return ("exe", n, p.panel_width, p.lookahead)

        def factor(n):
            variant = _variant()
            key = (n, variant, get_tune_parameters().panel_width)
            if key not in _kernel_cache:
                _kernel_cache[key] = _build(n)
            return _kernel_cache[key]
    """})
    assert _knob_findings(cache_keys.check(proj)) == []


def test_dlaf001_compiled_cache_builder_only_reads():
    """CompiledCache form: only the BUILDER's knobs count — the driver's
    admission reads (capacity-style knobs) are not trace state."""
    proj = _project({"dlaf_tpu/serve/drv.py": """
        from dlaf_tpu.tune import get_tune_parameters

        def _builder():
            return get_tune_parameters().lookahead

        def driver(cache, n):
            cap = get_tune_parameters().panel_width  # admission, not trace
            key = (n,)
            return cache.get(key, _builder)
    """})
    findings = cache_keys.check(proj)
    assert len(findings) == 1
    assert "lookahead" in findings[0].message
    assert "panel_width" not in findings[0].message


def test_dlaf001_sentinel_stores_ignored():
    proj = _project({"dlaf_tpu/algorithms/fact.py": """
        from dlaf_tpu.tune import get_tune_parameters

        _fail_cache = {}

        def mark(n):
            w = get_tune_parameters().panel_width
            _fail_cache[(n,)] = True
            return w
    """})
    assert _knob_findings(cache_keys.check(proj)) == []


def test_dlaf001_module_level_cache_dict_outside_plan_flagged():
    """A new ad-hoc module-level cache dict is a finding in its own right:
    the plan registry is the single audited cache site."""
    proj = _project({"dlaf_tpu/algorithms/fact.py": """
        _kernel_cache = {}

        def noop():
            return None
    """})
    findings = cache_keys.check(proj)
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "DLAF001" and f.symbol == "_kernel_cache"
    assert "module-level cache dict" in f.message
    assert "dlaf_tpu.plan.cached" in f.message


def test_dlaf001_module_level_cache_dict_inside_plan_exempt():
    proj = _project({"dlaf_tpu/plan/core.py": """
        _cache = {}

        def noop():
            return None
    """})
    assert cache_keys.check(proj) == []


def test_dlaf001_plan_cached_flags_missing_static_knob():
    """plan form: a knob read under the builder that is neither in the
    static key nor in trace_suffix() must be flagged."""
    proj = _project({"dlaf_tpu/algorithms/fact.py": """
        from dlaf_tpu.tune import get_tune_parameters
        from dlaf_tpu.plan import core as _plan

        def factor(n):
            def build():
                p = get_tune_parameters()
                return ("exe", n, p.lookahead)
            key = (n, get_tune_parameters().panel_width)
            return _plan.cached("factor", key, build)
    """})
    findings = _knob_findings(cache_keys.check(proj))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "DLAF001" and f.symbol == "factor"
    assert "lookahead" in f.message and "panel_width" not in f.message


def test_dlaf001_plan_cached_suffix_covers_ambient_knobs():
    """Knobs carried by plan.core.trace_suffix() need not appear in the
    per-site static key — that is the point of the unification."""
    proj = _project({
        "dlaf_tpu/plan/core.py": """
            from dlaf_tpu.tune import get_tune_parameters

            def trace_suffix():
                p = get_tune_parameters()
                return (bool(p.lookahead),)
        """,
        "dlaf_tpu/algorithms/fact.py": """
            from dlaf_tpu.tune import get_tune_parameters
            from dlaf_tpu.plan import core as _plan

            def factor(n):
                def build():
                    p = get_tune_parameters()
                    return ("exe", n, p.lookahead)
                key = (n,)
                return _plan.cached("factor", key, build)
        """,
    })
    assert _knob_findings(cache_keys.check(proj)) == []


# ------------------------------------------- DLAF002 collective symmetry


def test_dlaf002_rank_guarded_collective_flagged():
    proj = _project({"dlaf_tpu/comm/step.py": """
        from dlaf_tpu.comm import collectives as coll

        def step(x, axis):
            myr, myc = coll.my_rank()
            if myr == 0:
                x = coll.bcast(x, axis)
            return x
    """}, with_tune=False)
    findings = collectives.check(proj)
    assert len(findings) == 1
    assert findings[0].rule == "DLAF002"
    assert "bcast" in findings[0].message


def test_dlaf002_unguarded_collective_clean():
    proj = _project({"dlaf_tpu/comm/step.py": """
        from dlaf_tpu.comm import collectives as coll

        def step(x, axis):
            r = coll.my_rank()
            x = coll.bcast(x, axis)  # every rank issues it
            if r == 0:
                y = 2  # rank-dependent, but no collective inside
            return x
    """}, with_tune=False)
    assert collectives.check(proj) == []


def test_dlaf002_collective_id_discipline():
    proj = _project({"dlaf_tpu/ops/ring.py": """
        def missing(yf, h):
            return dma_ring_exchange(yf, h, "r", ("r",), False)

        def positional_ok(yf, h):
            return dma_ring_exchange(
                yf, h, "r", ("r",), False, collective_id_for("x", "r")
            )

        def keyword_ok(yf, h):
            return dma_ring_exchange(
                yf, h, "r", ("r",), collective_id=collective_id_for("x", "r")
            )

        def literal(yf, h):
            return dma_ring_exchange(yf, h, "r", ("r",), False, collective_id=3)
    """}, with_tune=False)
    findings = collectives.check(proj)
    by_symbol = {f.symbol: f for f in findings}
    assert set(by_symbol) == {"missing", "literal"}
    assert "without an explicit collective_id" in by_symbol["missing"].message
    assert "collective_id=3" in by_symbol["literal"].message


# ------------------------------------------------- DLAF003 trace purity


def test_dlaf003_host_sync_in_jitted_body():
    proj = _project({"dlaf_tpu/ops/kern.py": """
        import jax
        import time

        def body(x):
            v = x.sum().item()
            return v + time.time()

        def run(x):
            return jax.jit(body)(x)
    """}, with_tune=False)
    findings = purity.check(proj)
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert ".item()" in msgs and "time.time" in msgs
    assert all(f.symbol == "body" for f in findings)


def test_dlaf003_decorated_jit_and_float_on_param():
    proj = _project({"dlaf_tpu/ops/kern.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def kernel(x, n):
            return float(x)
    """}, with_tune=False)
    findings = purity.check(proj)
    assert len(findings) == 1
    assert "'float()' on traced argument 'x'" in findings[0].message


def test_dlaf003_propagates_through_calls_and_stops_at_escapes():
    proj = _project({"dlaf_tpu/ops/kern.py": """
        import jax
        import numpy as np

        def check_finite(x):
            return bool(np.asarray(x).all())  # allowlisted escape

        def helper(x):
            return np.asarray(x)  # reached from a traced body: flagged

        def body(x):
            check_finite(x)
            return helper(x)

        def run(x):
            return jax.jit(body)(x)
    """}, with_tune=False)
    findings = purity.check(proj)
    assert len(findings) == 1
    assert findings[0].symbol == "helper" and "np.asarray" in findings[0].message


def test_dlaf003_untraced_code_clean():
    proj = _project({"dlaf_tpu/obs/meter.py": """
        import time

        def wall(x):
            return time.monotonic(), x.item()
    """}, with_tune=False)
    assert purity.check(proj) == []


def test_dlaf003_span_emitter_in_jitted_body():
    """obs.spans calls are host-side orchestration markers: inside a traced
    region they emit once at trace time with garbage timing (ISSUE 10)."""
    proj = _project({"dlaf_tpu/ops/kern.py": """
        import jax
        from dlaf_tpu.obs import spans

        def body(x):
            with spans.span("tile"):
                return x * 2

        def run(x):
            return jax.jit(body)(x)
    """}, with_tune=False)
    findings = purity.check(proj)
    assert len(findings) == 1
    assert findings[0].rule == "DLAF003" and findings[0].symbol == "body"
    assert "span emitter 'spans.span()'" in findings[0].message


def test_dlaf003_flight_recorder_in_shard_mapped_body():
    proj = _project({"dlaf_tpu/ops/kern.py": """
        import jax
        from jax.experimental.shard_map import shard_map
        from dlaf_tpu.obs import flight as oflight

        def tile(x):
            oflight.record("probe", x=1)
            return x + 1

        def run(mesh, x):
            return shard_map(tile, mesh=mesh, in_specs=None, out_specs=None)(x)
    """}, with_tune=False)
    findings = purity.check(proj)
    assert len(findings) == 1
    assert "flight-recorder call 'oflight.record()'" in findings[0].message


def test_dlaf003_span_in_host_orchestration_clean():
    """The supported pattern: spans/flight in plain host functions (even
    ones that CALL jitted kernels) are not traced code — no finding."""
    proj = _project({"dlaf_tpu/serve/orch.py": """
        import jax
        from dlaf_tpu.obs import flight as oflight
        from dlaf_tpu.obs import spans

        def kernel(x):
            return x * 2

        def dispatch(x):
            with spans.span("dispatch"):
                h = spans.start_request("req")
                try:
                    return jax.jit(kernel)(x)
                except Exception:
                    oflight.auto_dump("dispatch_error")
                    raise
                finally:
                    spans.finish_request(h)
    """}, with_tune=False)
    assert purity.check(proj) == []


# --------------------------------------------- DLAF004 serve lock discipline


LOCK_FIXTURE = """
    import threading
    import time

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._done_cond = threading.Condition()

        def bad(self, fut, reqs):
            with self._lock:
                time.sleep(0.1)
                fut.result()
                fut.set_result(1)

        def _push_locked(self, rep, reqs):
            rep.adopt(reqs)

        def ok(self, fut):
            with self._lock:
                self.count = 1
            fut.set_result(2)

        def wait_ok(self):
            with self._done_cond:
                self._done_cond.wait()

        def wait_bad(self, other):
            with self._done_cond:
                other.evt.wait()
"""


def test_dlaf004_blocking_and_completion_under_lock():
    proj = _project({"dlaf_tpu/serve/fake.py": LOCK_FIXTURE}, with_tune=False)
    findings = locks.check(proj)
    got = sorted((f.symbol, f.message.split(" — ")[0]) for f in findings)
    assert got == [
        ("Pool._push_locked", "blocking call 'rep.adopt()' while holding <caller>"),
        ("Pool.bad", "'fut.set_result()' completes a future while holding self._lock"),
        ("Pool.bad", "blocking call 'fut.result()' while holding self._lock"),
        ("Pool.bad", "time.sleep while holding self._lock"),
        ("Pool.wait_bad",
         "'other.evt.wait()' waits on a different primitive than the held "
         "self._done_cond"),
    ]


def test_dlaf004_scope_is_serve_and_resilience_only():
    proj = _project({"dlaf_tpu/ops/fake.py": LOCK_FIXTURE}, with_tune=False)
    assert locks.check(proj) == []


# ------------------------------------------------ DLAF005 program names


NAMES_FIXTURE = """
    import functools
    from functools import partial

    import jax
    from jax import jit

    def kernel(x, b):
        return x * b

    def build(b):
        f1 = jax.jit(partial(kernel, b=b), donate_argnums=(0,))
        f2 = jit(lambda x: x + 1)
        f3 = jax.jit(functools.partial(kernel, b=2))
        return f1, f2, f3

    @partial(jax.jit, static_argnums=(1,))
    def named(x, b):
        return x * b

    def build_named(b, plan):
        def run(x):
            return kernel(x, b)

        return jax.jit(run), plan.jit("kernel", partial(kernel, b=b))
"""


def test_dlaf005_unnamed_programs_flagged():
    proj = _project({"dlaf_tpu/algorithms/fake.py": NAMES_FIXTURE}, with_tune=False)
    got = sorted((f.line, f.symbol, f.message.split(" — ")[0])
                 for f in program_names.check(proj))
    assert got == [
        (12, "build", "jax.jit of a partial compiles an unnamed program"),
        (13, "build", "jax.jit of a lambda compiles an unnamed program"),
        (14, "build", "jax.jit of a partial compiles an unnamed program"),
    ]


def test_dlaf005_named_programs_and_code_outside_the_library_clean():
    """A jitted ``def``, ``@partial(jax.jit, ...)`` and ``plan.jit`` keep a
    name; tests and scripts may jit what they like."""
    named = NAMES_FIXTURE.split("    def build(b):")[0] + NAMES_FIXTURE.split(
        "        return f1, f2, f3\n")[1]
    proj = _project({"dlaf_tpu/algorithms/fake.py": named}, with_tune=False)
    assert program_names.check(proj) == []
    proj = _project({"scripts/fake.py": NAMES_FIXTURE}, with_tune=False)
    assert program_names.check(proj) == []


# -------------------------------------------- suppressions, baseline, CLI


def test_run_suppression_and_baseline_roundtrip(tmp_path):
    serve_dir = tmp_path / "dlaf_tpu" / "serve"
    serve_dir.mkdir(parents=True)
    bad = textwrap.dedent("""
        import time

        class G:
            def _go_locked(self, rep, reqs):
                time.sleep(0.5)
    """)
    target = serve_dir / "g.py"
    target.write_text(bad)

    res = engine.run([str(tmp_path)], root=str(tmp_path), rules=[locks])
    assert not res.ok and len(res.new) == 1
    assert res.new[0].rule == "DLAF004"

    # baseline the finding: the identical run now passes, nothing stale
    bl = tmp_path / engine.BASELINE_NAME
    engine.write_baseline(str(bl), res.findings)
    res2 = engine.run([str(tmp_path)], root=str(tmp_path), rules=[locks],
                      baseline_path=str(bl))
    assert res2.ok and res2.findings and not res2.new
    assert not res2.stale_baseline

    # line drift must not break the baseline (identity is line-free)
    target.write_text("\n\n" + bad)
    res3 = engine.run([str(tmp_path)], root=str(tmp_path), rules=[locks],
                      baseline_path=str(bl))
    assert res3.ok and not res3.new and not res3.stale_baseline

    # fixing the bug surfaces the stale baseline entry for ratchet-down
    target.write_text(bad.replace("time.sleep(0.5)", "pass"))
    res4 = engine.run([str(tmp_path)], root=str(tmp_path), rules=[locks],
                      baseline_path=str(bl))
    assert res4.ok and not res4.findings
    assert len(res4.stale_baseline) == 1

    # inline suppression (standalone comment above the line) with a reason
    target.write_text(bad.replace(
        "        time.sleep(0.5)",
        "        # dlaf: ignore[DLAF004] deliberate: backoff by design\n"
        "        time.sleep(0.5)",
    ))
    res5 = engine.run([str(tmp_path)], root=str(tmp_path), rules=[locks])
    assert res5.ok and not res5.findings
    assert len(res5.suppressed) == 1
    assert res5.suppressed[0].suppress_reason == "deliberate: backoff by design"

    # JSON report shape
    js = res5.to_json()
    assert js["tool"] == "dlaf_tpu.analysis" and js["schema"] == 1
    assert js["ok"] is True and len(js["suppressed"]) == 1


def test_suppression_requires_matching_rule():
    proj_src = """
        import time

        class G:
            def _go_locked(self):
                time.sleep(0.5)  # dlaf: ignore[DLAF001] wrong rule id
    """
    files = [SourceFile.from_text("/v/g.py", "dlaf_tpu/serve/g.py",
                                  textwrap.dedent(proj_src))]
    findings = locks.check(Project(files).index())
    active, suppressed = engine.apply_suppressions(
        findings, {f.rel: f for f in files})
    assert len(active) == 1 and not suppressed


def test_parse_errors_become_dlaf000(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    res = engine.run([str(tmp_path)], root=str(tmp_path), rules=[])
    assert not res.ok
    assert res.new[0].rule == "DLAF000"


# --------------------------------------------------- reverted known bugs


def test_reverted_bug_dlaf001_trsm_lookahead_key_omission():
    """Deleting the trsm_lookahead element from plan.core.trace_suffix()
    must re-open the dead-knob hole at every cache site at once — the
    serve posv executable is the historical instance of this bug class."""
    proj = _real_tree_project(
        "dlaf_tpu/plan/core.py",
        lambda text: text.replace("bool(p.trsm_lookahead),", "", 1),
    )
    findings = [f for f in cache_keys.check(proj)
                if f.path == "dlaf_tpu/serve/batched.py"
                and "trsm_lookahead" in f.message]
    assert findings, "linter no longer catches the trsm_lookahead omission"
    assert all("_build_posv_matrix_exec" in f.message for f in findings)


def test_reverted_bug_dlaf002_dropped_collective_id():
    """Dropping the explicit collective_id from the fused-ring call is the
    PR-6 semaphore-sharing bug class."""
    proj = _real_tree_project(
        "dlaf_tpu/ops/pallas_panel_exchange.py",
        lambda text: text.replace(
            "False, collective_id_for(kind, axis)", "False"),
    )
    findings = [f for f in collectives.check(proj)
                if f.path == "dlaf_tpu/ops/pallas_panel_exchange.py"]
    assert len(findings) == 1
    assert "without an explicit collective_id" in findings[0].message


def test_reverted_bug_dlaf002_consume_ring_dropped_collective_id():
    """The same bug class on the fused trailing-update consumer: dropping
    the explicit id from the dma_ring_consume call site would silently
    share id 0 with every other ring the scheduler can overlap with."""
    proj = _real_tree_project(
        "dlaf_tpu/ops/pallas_trailing_update.py",
        lambda text: text.replace(
            '\n            ppe.collective_id_for("consume", ring_axis),', ""),
    )
    findings = [f for f in collectives.check(proj)
                if f.path == "dlaf_tpu/ops/pallas_trailing_update.py"]
    assert len(findings) == 1
    assert "dma_ring_consume without an explicit collective_id" \
        in findings[0].message


def test_reverted_bug_dlaf003_host_sync_in_dma_ring():
    """A .item() debug probe inside the jitted DMA ring entry point is the
    classic silent per-call device sync."""
    def mutate(text):
        head, _, tail = text.partition("def dma_ring_exchange")
        tail = tail.replace(
            "    n = _axis_size(ring_axis)\n",
            "    n = _axis_size(ring_axis)\n    _dbg = yf.sum().item()\n",
            1,
        )
        return head + "def dma_ring_exchange" + tail

    proj = _real_tree_project("dlaf_tpu/ops/pallas_panel_exchange.py", mutate)
    findings = [f for f in purity.check(proj)
                if f.path == "dlaf_tpu/ops/pallas_panel_exchange.py"
                and f.symbol == "dma_ring_exchange"]
    assert len(findings) == 1
    assert ".item()" in findings[0].message


def test_reverted_bug_dlaf004_gateway_dispatch_under_lock():
    """Renaming Gateway._dispatch back to the lock-held convention models
    the shipped livelock: route/adopt under the dispatcher condition."""
    proj = _real_tree_project(
        "dlaf_tpu/serve/gateway.py",
        lambda text: text.replace(
            "def _dispatch(self, key, fb, live)",
            "def _dispatch_locked(self, key, fb, live)"),
    )
    findings = [f for f in locks.check(proj)
                if f.path == "dlaf_tpu/serve/gateway.py"
                and f.symbol == "Gateway._dispatch_locked"]
    assert any("adopt" in f.message for f in findings)


# ------------------------------------------------------------- meta-test


def test_shipped_tree_clean_modulo_baseline():
    """`python -m dlaf_tpu.analysis` must exit 0 on the shipped tree."""
    root = repo_root()
    paths = [p for p in (os.path.join(root, "dlaf_tpu"),
                         os.path.join(root, "scripts")) if os.path.isdir(p)]
    res = engine.run(paths, root=root,
                     baseline_path=os.path.join(root, engine.BASELINE_NAME))
    assert res.ok, engine.render_human(res)
    assert not res.stale_baseline, res.stale_baseline


def test_report_metrics_analysis_rollup(tmp_path, capsys):
    """scripts/report_metrics.py renders the analysis roll-up for a findings
    JSON (the CI static-analysis lane feeds it `analysis.json`) and still
    treats everything else as a metrics JSONL stream."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "report_metrics", os.path.join(repo_root(), "scripts", "report_metrics.py")
    )
    rm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rm)

    root = repo_root()
    res = engine.run([os.path.join(root, "dlaf_tpu", "analysis")], root=root)
    doc = res.to_json()
    clean = tmp_path / "analysis.json"
    clean.write_text(json.dumps(doc))
    assert rm.summarize(str(clean)) == 0
    out = capsys.readouterr().out
    assert "dlaf_tpu.analysis findings" in out
    assert "DLAF003" in out          # every rule id listed, firing or not
    assert "analysis: clean" in out

    doc["ok"] = False
    doc["new"] = [{"rule": "DLAF001"}]
    doc["counts_by_rule"] = {"DLAF001": 1}
    doc["findings"] = [{"rule": "DLAF001", "path": "dlaf_tpu/x.py", "line": 3,
                        "col": 0, "symbol": "f", "message": "knob outside key"}]
    dirty = tmp_path / "dirty.json"
    dirty.write_text(json.dumps(doc))
    assert rm.summarize(str(dirty)) == 1
    assert "FINDINGS OUTSIDE BASELINE" in capsys.readouterr().out

    # anything that is not an analysis report falls through to the JSONL reader
    assert rm._load_analysis_doc(str(tmp_path / "missing.jsonl")) is None
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"tool": "something_else"}))
    assert rm._load_analysis_doc(str(other)) is None
