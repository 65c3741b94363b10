#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this host.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``benchmark/configs/<config>.json``: the op,
sizes, grid and library knobs), a traffic mix
(``benchmark/traffic/<traffic>.json``) and a chip count.  The op's own file
(``benchmark/ops/<op>.py``) generates the input, calls the library, counts
the operations and holds the float64 host reference.  A per-layer metric is
read by ``benchmark/metrics/<metric>.py``.  Nothing here names a cell, a
configuration or a metric: a new one is new files and new entries.

One run, in one process that holds every chip of the cell:

1. set-up: the library's knobs from the configuration, the input generated
   on the device from ``--seed`` in one jitted call, one warm-up solve that
   compiles (or loads from ``<checkout>/.jax_cache``) every program;
2. ``--trace 0``: back-to-back solves, each on a fresh device copy of the
   input and waited for, until ``--seconds`` have passed (the solve in
   flight is finished), with a seeded sample of their outputs kept;
   ``--trace 1``: one solve under the library's stage timer, then solves
   under the profiler for the traffic's ``trace_seconds`` (at least one);
3. the kept outputs gathered, the device state freed, and each compared
   with the host reference, which takes its input from the op's generator
   run again by XLA on the host CPU from the same key (nothing the library
   lays out or reads back); every number compared is printed beside its
   limit, on standard error and last in the result line;
4. one JSON line on standard output: ``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the same path on
virtual CPU devices at a sixteenth of the size and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.time()  # set-up is measured from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the device kind whose peaks a CPU rehearsal borrows to exercise the
# roofline arithmetic; its numbers are never printed as a result
REHEARSE_KIND = "TPU v5 lite"
TRACE_WINDOW = "bench/traced_window"


class BenchError(SystemExit):
    """A run that cannot produce a result: exit non-zero, print none."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchError(f"cannot read {path}: {e}") from None


def load_module(path: str):
    """Import a file of the benchmark by its path (metric names hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing {path}")
    name = "bench_" + os.path.relpath(path, HERE).replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT) -> SimpleNamespace:
    """Everything a run of ``workload`` needs, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    here = os.path.join(root, "benchmark")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return SimpleNamespace(
        name=workload,
        chips=int(cell["chips"]),
        config=cfg,
        traffic=load_json(os.path.join(here, "traffic", cell["traffic"] + ".json")),
        op=load_module(os.path.join(here, "ops", cfg["op"] + ".py")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[(m, load_module(os.path.join(here, "metrics", m["name"] + ".py")))
                   for m in bench["per_layer"] if applies(m)],
        peaks=load_json(os.path.join(here, "peaks.json")),
    )


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="virtual CPU devices, a sixteenth of the size, no result line")
    return p.parse_args(argv)


def _environment(rehearse: bool, chips: int) -> None:
    """Before JAX is imported: the configuration alone sets the library's
    knobs, and the compile cache lives at a fixed path in the checkout."""
    for k in [k for k in os.environ if k.startswith("DLAF_TPU_")]:
        del os.environ[k]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        os.environ["DLAF_TPU_COMPILE_CACHE"] = ""
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        platforms = os.environ.get("JAX_PLATFORMS", "")
        if platforms and "cpu" not in platforms.split(","):
            # the reference's input is generated on the host CPU
            os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


class CompileCounter:
    """Counts, while ``active``, the programs JAX compiles or loads from
    the persistent cache (``count``) and the loads among them."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.active, self.count, self.loads = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1

    def _hit(self, event, **_):
        if self.active and event == self.HIT:
            self.loads += 1


class Run:
    """One run of one cell: set-up, the window or the traced solves, the
    check."""

    def __init__(self, cell, args, jax, devices, rehearse: bool, control: bool = False):
        """``control``: the configuration's ``control`` knobs on top of its
        own (``benchmark/control.py``; the benchmark's runs never set it)."""
        import jax.numpy as jnp
        import numpy as np

        import dlaf_tpu as dt
        from dlaf_tpu import tune
        from dlaf_tpu.matrix import layout

        self.cell, self.args, self.jax, self.jnp, self.np, self.dt = cell, args, jax, jnp, np, dt
        self.rehearse = rehearse
        cfg = cell.config
        self.n, self.nb = int(cfg["matrix_size"]), int(cfg["block_size"])
        if rehearse:
            self.n, self.nb = self.n // 16, self.nb // 4
        self.dtype = np.dtype(cfg["type"])
        self.complex = self.dtype.kind == "c"
        knobs = dict(cfg.get("tune", {}))
        if rehearse:
            knobs.update(getattr(cell.op, "REHEARSE_TUNE", {}))
        if control:
            knobs.update(cfg["control"])
        tune.initialize(**knobs)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.grid = dt.Grid.create(dt.Size2D(*cfg["grid"]), devices)
        if tuple(self.grid.grid_size) != tuple(cfg["grid"]):
            raise BenchError(f"grid {self.grid.grid_size}, configuration asks {cfg['grid']}")
        self.devices = list(self.grid.mesh.devices.flat)
        self.dist = dt.Distribution(dt.Size2D(self.n, self.n), dt.Size2D(self.nb, self.nb),
                                    self.grid.grid_size, dt.Index2D(0, 0))
        self.solve_kwargs = dict(cfg.get("solve", {}))
        self.compiles = CompileCounter(jax)
        op, n, dtype, dist = cell.op, self.n, self.dtype, self.dist

        def generate(key):
            a = op.generate(jax, jnp, key, n, dtype)
            return layout.pack(layout.pad_global(a, dist), dist)

        self._generate = jax.jit(generate, out_shardings=self.grid.stacked_sharding())
        self._generate_host = jax.jit(lambda key: op.generate(jax, jnp, key, n, dtype))
        self._copy = jax.jit(jnp.copy, out_shardings=self.grid.stacked_sharding())

    # --- inputs and solves -------------------------------------------------
    def key(self, device=None):
        """The input's threefry key from ``--seed`` (any size), on ``device``."""
        words = self.np.random.SeedSequence([self.args.seed, 0]).generate_state(2, self.np.uint32)
        return self.jax.random.wrap_key_data(self.jax.device_put(words, device))

    def make_input(self):
        traffic = self.cell.traffic
        if traffic.get("loop") != "closed" or int(traffic.get("callers", 1)) != 1:
            raise BenchError("the generator drives a closed loop with one caller")
        self.input = self._generate(self.key())
        self.jax.block_until_ready(self.input)

    def reference_input(self):
        """The input for the reference: the op's generator on the host CPU
        from the same key.  Threefry and the generators' arithmetic are
        exact, so it is the device's input bit for bit."""
        cpu = self.jax.devices("cpu")[0]
        return self.np.asarray(self._generate_host(self.key(cpu)))

    def solve(self):
        """One solve on a fresh device copy of the input, waited for."""
        jax = self.jax
        with jax.profiler.TraceAnnotation("bench/solve"):
            mat = self.dt.DistributedMatrix(self.dist, self.grid, self._copy(self.input))
            out = self.cell.op.solve(self.dt, mat, **self.solve_kwargs)
            jax.block_until_ready(self.cell.op.outputs(out))
        return out

    def loop(self, seconds: float, keep: int):
        """Closed loop, one caller: solves back to back until ``seconds``
        have passed, the one in flight finished.  Keeps a reservoir sample,
        drawn from the seed, of ``keep`` outputs."""
        rng = self.np.random.default_rng([self.args.seed, 1])
        kept, times, failed = [], [], 0
        self.compiles.active = True
        t0 = end = time.perf_counter()
        i = 0
        while True:
            s = time.perf_counter()
            try:
                out = self.solve()
            except Exception as e:  # a failed solve counts; the loop goes on
                print(f"benchmark: solve {i} raised {e!r}", file=sys.stderr)
                failed += 1
                out = None
            end = time.perf_counter()
            times.append(end - s)
            if out is not None:
                if len(kept) < keep:
                    kept.append(out)
                else:
                    j = int(rng.integers(0, i + 1))
                    if j < keep:
                        kept[j] = out
            i += 1
            if end - t0 >= seconds:
                break
        self.compiles.active = False
        return SimpleNamespace(t0=t0, end=end, times=times, kept=kept, failed=failed,
                               attempted=i)

    # --- set-up, window, trace ---------------------------------------------
    def setup(self, phases=None):
        """The input, then one warm-up solve that compiles or loads every
        program of the solve; ``phases`` gets the seconds of each."""
        phases = {} if phases is None else phases
        t = time.time()
        self.make_input()
        phases["inputs"] = time.time() - t
        t = time.time()
        self.solve()
        phases["warmup_solve"] = time.time() - t
        for check in getattr(self.cell.op, "SETUP_CHECKS", ()):
            check(self)

    def window(self):
        w = self.loop(self.args.seconds, int(self.cell.traffic.get("checked_solves", 1)))
        seconds = w.end - w.t0
        done = w.attempted - w.failed
        values = {
            "gflops_per_chip": self.cell.op.flops(self.n, self.complex) * done / seconds
            / len(self.devices) / 1e9,
            "solve_p95_s": statistics.quantiles(w.times, n=20, method="inclusive")[18]
            if len(w.times) > 1 else w.times[0],
        }
        w.values = values
        return w

    def traced(self):
        """Stage seconds from one solve under the stage timer, then device
        time from the profiler over ``trace_seconds`` of solves.  The
        stage timer's barriers stay out of the profiled solves."""
        from dlaf_tpu.common import stagetimer

        tr = load_module(os.path.join(HERE, "trace_reduce.py"))

        stagetimer.start()
        try:
            first = self.solve()
        finally:
            stage_s = stagetimer.stop()
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(logdir, profiler_options=opts)
            try:
                with self.jax.profiler.TraceAnnotation(TRACE_WINDOW):
                    w = self.loop(float(self.cell.traffic.get("trace_seconds", 2.0)), 1)
            finally:
                self.jax.profiler.stop_trace()
            path = tr.find_xplane(logdir)
            if self.rehearse:  # CPU ops run on the host's XLA threads
                devices, host = tr.load(path, device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")
            else:
                devices, host = tr.load(path)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        lo_hi = tr.window_of(host, TRACE_WINDOW)
        if lo_hi is None:
            raise BenchError("the trace holds no traced-window span")
        w.kept.append(first)
        w.reduction = tr.reduce(devices, host, *lo_hi)
        w.stage_s = stage_s
        return w

    def peaks(self):
        kind = REHEARSE_KIND if self.rehearse else self.devices[0].device_kind
        if kind not in self.cell.peaks:
            raise BenchError(f"no peaks for device kind {kind!r} in benchmark/peaks.json")
        return self.cell.peaks[kind]

    def layer_metrics(self, w) -> dict:
        ctx = SimpleNamespace(
            stage_s=w.stage_s,
            trace=w.reduction,
            solves=w.attempted - w.failed,
            chips=len(self.devices),
            flops=self.cell.op.flops(self.n, self.complex),
            bytes=self.cell.op.bytes_moved(self.n, self.dtype.itemsize),
            peaks=self.peaks(),
        )
        out = {}
        for spec, reader in self.cell.per_layer:
            value = reader.read(ctx)
            if value is not None:
                out[spec["name"]] = {"value": value, "unit": spec["unit"]}
        return out

    # --- check -------------------------------------------------------------
    def check(self, kept) -> dict:
        """Gather the kept outputs, free the device, then compare each with
        the host reference.  Returns ``{name: (worst value over the kept
        outputs, limit)}``."""
        op = self.cell.op
        host = [op.gather(out) for out in kept]
        kept.clear()
        self.input = None
        limits = self.cell.config["limits"]
        worst = {}
        if host:
            ref = op.reference(self.reference_input())
            for out in host:
                for name, value in op.compare(ref, out).items():
                    prev = worst.get(name)
                    if prev is None or not value <= prev:  # NaN is the worst
                        worst[name] = value
        if not worst:
            return {name: (float("nan"), limit) for name, limit in limits.items()}
        return {name: (worst.get(name, float("nan")), limits[name]) for name in limits}


def device_report(devices, jax) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
    }


def run_cell(cell, args, rehearse: bool = False) -> dict:
    """The whole run; returns the result object (also for a rehearsal)."""
    t = time.time()
    import jax

    phases = {"start": t - T_START, "import_jax": time.time() - t}
    t = time.time()
    devices = jax.devices()
    phases["backend_init"] = time.time() - t
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise BenchError(f"no TPU: JAX found {platform!r} devices")
    if len(devices) < cell.chips:
        raise BenchError(f"the cell asks for {cell.chips} chips, JAX found {len(devices)}")
    t = time.time()
    run = Run(cell, args, jax, devices[:cell.chips], rehearse)
    phases["library"] = time.time() - t
    if not rehearse:
        run.peaks()  # an unknown device kind fails before any work
    run.setup(phases)
    setup_s = time.time() - T_START
    if args.trace:
        w = run.traced()
        metrics = run.layer_metrics(w)
    else:
        w = run.window()
        values = dict(w.values, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = device_report(run.devices, jax)
    if args.trace:
        device["busy_s"] = w.reduction.busy_s()
        device["window_s"] = w.reduction.window_s()
    compiles, loads = run.compiles.count, run.compiles.loads
    t = time.time()
    checks = run.check(w.kept)
    check_s = time.time() - t
    correct = w.failed == 0 and all(v <= lim for v, lim in checks.values())
    result = {
        "correct": bool(correct),
        "attempted": w.attempted,
        "failed": w.failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = {"device_ops": w.reduction.top_ops(10),
                               "idle_gaps": w.reduction.top_gaps(10)}
    result["setup_phases_s"] = phases
    result["programs_in_window"] = {"loaded": loads, "compiled": compiles - loads}
    result["check_s"] = check_s
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    if args.trace:
        for name, d in w.reduction.devices.items():
            print(f"benchmark: {name} busy {d.busy_ns / 1e9!r} s of {d.window_ns / 1e9!r} s, "
                  f"idle share {1 - d.busy_ns / d.window_ns!r}", file=sys.stderr)
    print(f"benchmark: set-up phases {json.dumps(phases)}; programs compiled or loaded "
          f"inside the measured solves: {compiles} ({loads} from the cache)", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} = {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    args = _parse(argv)
    cell = resolve(args.workload)
    _environment(args.rehearse, cell.chips)
    result = run_cell(cell, args, rehearse=args.rehearse)
    if args.rehearse:
        print("rehearsal (no chip, no result): " + json.dumps(result), file=sys.stderr)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
