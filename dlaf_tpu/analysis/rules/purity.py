"""DLAF003 — trace purity: no host syncs or wall-clock reads in traced code.

A ``.item()``, ``np.asarray``, ``jax.device_get``, ``float()`` on a traced
array, a ``time.*`` read or host RNG draw inside a ``jit`` / ``shard_map``
/ ``pallas_call`` region either blocks the async dispatch queue (device
sync per call — the classic silent 10x) or bakes one trace-time value into
the compiled executable (a timestamp or random draw that never changes
again).  Legitimate escapes go through ``jax.pure_callback`` /
``io_callback`` / ``jax.debug.*``; the one deliberate sync in this
codebase is ``health.check_finite`` (allowlisted).

Regions are discovered per file with nested-def granularity: a function
is *traced* when it is handed to a trace-introducing call (``jax.jit``,
``coll.spmd``, ``shard_map(_compat)``, ``vmap``/``pmap``, the
``lax.fori_loop``/``scan``/``while_loop``/``cond`` bodies,
``pallas_call``) directly, via ``partial``, as a lambda, or carries a
trace-introducing decorator (``@jax.jit`` / ``@partial(jax.jit, ...)``) — then
tracedness propagates through same-file and cross-module calls (the
engine's call graph), stopping at the callback escapes and the allowlist.

``float()``/``bool()`` are flagged only on direct parameters of a *seed*
traced function (those are traced arrays by construction); deeper values
are usually Python statics and would drown the rule in false positives.
"""
from __future__ import annotations

import ast

from dlaf_tpu.analysis.engine import Finding
from dlaf_tpu.analysis.project import dotted_name

RULE = "DLAF003"
SUMMARY = "host sync / wall clock / host RNG inside jit, shard_map or pallas_call"

#: call name (last component) -> index/indices of the traced callable operand
TRACE_INTRODUCERS = {
    "jit": (0,),
    "vmap": (0,),
    "pmap": (0,),
    "checkpoint": (0,),
    "remat": (0,),
    "pallas_call": (0,),
    "shard_map": (0,),
    "spmd": (1,),          # coll.spmd(grid, fn, ...)
    "fori_loop": (2,),     # lax.fori_loop(lo, hi, body, init)
    "scan": (0,),
    "while_loop": (0, 1),
    "cond": (1, 2, 3),
    "switch": None,        # lax.switch(i, [fns...]) — handled specially
}

#: Propagation stops here: these escape the trace by design.
ESCAPES = frozenset({
    "pure_callback", "io_callback", "callback", "debug_callback",
    "check_finite",     # health's deliberate on-chip->host sync point
})

TIME_FUNCS = frozenset({
    "time", "monotonic", "perf_counter", "process_time", "sleep",
    "monotonic_ns", "perf_counter_ns", "time_ns",
})

#: obs.spans emitters — host-side wall-clock instrumentation that must stay
#: in orchestration code: inside a traced region each runs ONCE at trace
#: time with garbage timing and leaks contextvar state into the trace.
SPAN_EMITTERS = frozenset({
    "span", "start_request", "finish_request", "mark_phase", "emit_span",
})

#: obs.flight recorder entry points — same constraint as spans.
FLIGHT_EMITTERS = frozenset({"record", "dump", "auto_dump"})


def _last(name: str | None) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _head(name: str | None) -> str:
    return name.split(".", 1)[0] if name else ""


class _Region:
    """One def (possibly nested) plus where to look things up."""

    __slots__ = ("node", "file", "name", "seed", "parent")

    def __init__(self, node, file, name, parent=None):
        self.node = node
        self.file = file
        self.name = name
        self.seed = False
        self.parent = parent


def _collect_defs(file):
    """Every def in the file (any nesting), plus name->region scoping maps."""
    regions = {}

    def visit(node, parent):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                reg = _Region(sub, file, sub.name, parent)
                regions[id(sub)] = reg
                visit(sub, reg)
            else:
                visit(sub, parent)

    visit(file.tree, None)
    return regions


def _resolve_local(regions, scope, name):
    """The def named ``name`` visible from ``scope`` (nearest nesting first)."""
    candidates = [r for r in regions.values() if r.name == name]
    if not candidates:
        return None
    # prefer one sharing the longest ancestry with `scope`
    def depth_shared(r):
        anc = set()
        s = scope
        while s is not None:
            anc.add(id(s.node))
            s = s.parent
        d, p = 0, r.parent
        while p is not None:
            if id(p.node) in anc:
                d += 1
            p = p.parent
        return d

    return max(candidates, key=depth_shared)


def _traced_operands(call):
    name = _last(dotted_name(call.func))
    if name not in TRACE_INTRODUCERS:
        return []
    if name == "switch":
        ops = []
        for arg in call.args[1:]:
            if isinstance(arg, (ast.List, ast.Tuple)):
                ops.extend(arg.elts)
            else:
                ops.append(arg)
        return ops
    idxs = TRACE_INTRODUCERS[name]
    return [call.args[i] for i in idxs if i < len(call.args)]


def _unwrap(operand):
    """Peel partial(f, ...) down to f."""
    while isinstance(operand, ast.Call) and _last(dotted_name(operand.func)) == "partial" \
            and operand.args:
        operand = operand.args[0]
    return operand


def _decorated_traced(node) -> bool:
    """True when a def carries a trace-introducing decorator: ``@jax.jit``,
    ``@jit(...)`` or ``@functools.partial(jax.jit, ...)``."""
    for dec in node.decorator_list:
        if _last(dotted_name(dec)) in TRACE_INTRODUCERS:
            return True
        if isinstance(dec, ast.Call):
            fn = _last(dotted_name(dec.func))
            if fn in TRACE_INTRODUCERS:
                return True
            if fn == "partial" and dec.args \
                    and _last(dotted_name(dec.args[0])) in TRACE_INTRODUCERS:
                return True
    return False


def check(project):
    findings = []
    # region discovery is per-file; cross-module propagation goes through the
    # project call graph at top-level-function granularity
    per_file = {f.rel: _collect_defs(f) for f in project.files}
    traced: list = []
    lambda_seeds: list = []   # (file, lambda node)
    # map: enclosing region for any node — walk with scope tracking
    def scan(f, regions, node, scope):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan(f, regions, sub, regions[id(sub)])
                continue
            if isinstance(sub, ast.Call):
                for op in (_unwrap(o) for o in _traced_operands(sub)):
                    if isinstance(op, ast.Lambda):
                        lambda_seeds.append((f, op))
                    else:
                        name = dotted_name(op)
                        if name and "." not in name:
                            reg = _resolve_local(regions, scope, name)
                            if reg is not None and not reg.seed:
                                reg.seed = True
                                traced.append(reg)
                            elif reg is None:
                                qn = project.resolve_name(f.module, None, name)
                                if qn in project.functions:
                                    info = project.functions[qn]
                                    tf = project.by_module.get(info.module)
                                    if tf is not None:
                                        treg = per_file[tf.rel].get(id(info.node))
                                        if treg is not None and not treg.seed:
                                            treg.seed = True
                                            traced.append(treg)
            scan(f, regions, sub, scope)

    for f in project.files:
        regions = per_file[f.rel]
        for reg in regions.values():
            if _decorated_traced(reg.node) and not reg.seed:
                reg.seed = True
                traced.append(reg)
        scan(f, regions, f.tree, None)

    # propagate tracedness through calls (same file by scope, cross-module
    # by the project graph); bounded worklist
    marked = {id(r.node) for r in traced}
    work = list(traced)
    while work:
        reg = work.pop()
        regions = per_file[reg.file.rel]
        for sub in ast.walk(reg.node):
            if not isinstance(sub, ast.Call):
                continue
            name = dotted_name(sub.func)
            last = _last(name)
            if last in ESCAPES:
                continue
            target_reg = None
            if name and "." not in name:
                target_reg = _resolve_local(regions, reg, name)
            if target_reg is None and name:
                qn = project.resolve_call(reg.file.module, None, sub.func)
                if qn in project.functions:
                    info = project.functions[qn]
                    if _last(info.qualname) in ESCAPES:
                        continue
                    tf = project.by_module.get(info.module)
                    if tf is not None:
                        target_reg = per_file[tf.rel].get(id(info.node))
            if target_reg is not None and id(target_reg.node) not in marked:
                marked.add(id(target_reg.node))
                work.append(target_reg)

    all_regions = [r for fr in per_file.values() for r in fr.values()
                   if id(r.node) in marked]
    for reg in all_regions:
        findings.extend(_scan_region(project, reg))
    for f, lam in lambda_seeds:
        findings.extend(_scan_body(project, f, lam, "<lambda>", seed_params=set()))
    return findings


def _np_aliases(file):
    """Local aliases of the numpy module (usually {'np'})."""
    import ast as _ast

    out = set()
    for node in file.tree.body:
        if isinstance(node, _ast.Import):
            for alias in node.names:
                if alias.name == "numpy":
                    out.add(alias.asname or "numpy")
    return out


def _scan_region(project, reg):
    params = set()
    if reg.seed:
        a = reg.node.args
        params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs
                  if p.arg not in ("self", "cls")}
    return _scan_body(project, reg.file, reg.node, reg.name, seed_params=params)


def _scan_body(project, file, node, symbol, *, seed_params):
    findings = []
    np_names = _np_aliases(file)

    def flag(sub, msg):
        findings.append(Finding(
            rule=RULE, path=file.rel, line=sub.lineno, col=sub.col_offset,
            symbol=symbol, message=msg,
        ))

    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        name = dotted_name(sub.func)
        last = _last(name)
        head = _head(name)
        if isinstance(sub.func, ast.Attribute) and sub.func.attr == "item" \
                and not sub.args:
            flag(sub, "'.item()' host sync inside traced code — device round "
                      "trip per call; keep it on-device or move outside the jit")
        elif last == "device_get" or (name == "jax.device_get"):
            flag(sub, "'jax.device_get' inside traced code — host transfer "
                      "at trace time; return the value instead")
        elif head in np_names and last in ("asarray", "array", "copy") \
                and name.count(".") == 1:
            flag(sub, f"'{name}()' inside traced code materializes a traced "
                      f"value on host — use jnp.{last} or hoist to trace setup")
        elif head == "time" and last in TIME_FUNCS and name.count(".") == 1:
            flag(sub, f"'{name}()' inside traced code bakes one trace-time "
                      f"clock read into the executable (and never updates)")
        elif (head in np_names and ".random." in (name or "")) or \
                (head == "random" and name and name.count(".") == 1):
            flag(sub, f"host RNG '{name}()' inside traced code — one draw at "
                      f"trace time, constant forever; use jax.random")
        elif last in SPAN_EMITTERS and head in ("spans", "ospans", "obs", "_spans"):
            flag(sub, f"span emitter '{name}()' inside traced code — spans are "
                      f"host-side orchestration markers (one garbage-timed emit "
                      f"at trace time); move it outside the jit/shard_map")
        elif last in FLIGHT_EMITTERS and head in ("flight", "oflight"):
            flag(sub, f"flight-recorder call '{name}()' inside traced code — "
                      f"the ring/dump is host state; hook failures in the "
                      f"orchestration layer, not the traced body")
        elif last in ("float", "bool") and isinstance(sub.func, ast.Name) \
                and sub.args and isinstance(sub.args[0], ast.Name) \
                and sub.args[0].id in seed_params:
            flag(sub, f"'{last}()' on traced argument "
                      f"'{sub.args[0].id}' — concretizes a traced value "
                      f"(ConcretizationTypeError on abstract tracers, silent "
                      f"sync otherwise)")
    return findings
