"""Reduce a JAX profiler trace to device busy time, idle gaps, op times and
collective overlap.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, lines, and events with a
start and a duration in nanoseconds.  On a TPU host each chip is a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per executed
HLO op; the host's TraceMe and ``jax.profiler.TraceAnnotation`` spans sit
on the lines of the ``/host:CPU`` plane, on the same clock.

Everything below ``load`` works on plain ``(name, start_ns, end_ns)``
tuples, so the arithmetic is tested on synthetic intervals and on a trace
recorded on the CPU alike.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# XLA collectives (sync and async halves) and remote-DMA custom calls
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|^send|^recv|remote[-_]?dma|ragged-all-to-all",
    re.IGNORECASE,
)


@dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. ``fusion.12``
    start: int
    end: int
    scope: str = ""  # named-scope path or module, where the trace has one


@dataclass
class DeviceReduction:
    busy_ns: int
    window_ns: int
    collective_ns: int
    collective_exposed_ns: int
    gaps: list  # [(start, end)]
    op_ns: dict  # label -> self time in ns


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def instruction(text: str) -> str:
    """An op's name from the trace's HLO text (``%fusion.12 = f32[...]
    fusion(...)``): the instruction name, with a custom call's target."""
    head = text.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', text)
    if m and m.group(1) != "tpu_custom_call":
        head += f" ({m.group(1)})"
    return head


def load(path: str, device_plane: str = DEVICE_PLANE, op_line: str = OP_LINE):
    """Read ``path`` into ``({device: [Op]}, [host (name, start, end)])``.

    ``device_plane`` and ``op_line`` are regexes over plane names and the
    names of the lines of such a plane that hold the ops; a CPU rehearsal
    and a test point them at the host threads of a CPU trace.  Each op
    carries the program (``XLA Modules`` line) it ran in.  Zero-length
    host events (markers) are dropped.
    """
    import bisect

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    pat, line_pat = re.compile(device_plane), re.compile(op_line)
    for plane in pd.planes:
        if pat.search(plane.name):
            ops = devices.setdefault(plane.name, [])
            modules = sorted((int(ev.start_ns), int(ev.end_ns), ev.name.split("(")[0])
                             for line in plane.lines if line.name == MODULE_LINE
                             for ev in line.events)
            starts = [m[0] for m in modules]
            for line in plane.lines:
                if not line_pat.search(line.name):
                    continue
                for ev in line.events:
                    s0, s1 = int(ev.start_ns), int(ev.end_ns)
                    st = _stats(ev)
                    scope = st.get("tf_op") or st.get("name_scope") or ""
                    k = bisect.bisect_right(starts, s0) - 1
                    if not scope and k >= 0 and modules[k][1] >= s0:
                        scope = modules[k][2]
                    ops.append(Op(instruction(ev.name), s0, s1, str(scope)))
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return devices, host


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` covering the given intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_label(op: Op) -> str:
    """An op's name in the breakdown: its program or named scope, and its
    instruction name."""
    return f"{op.scope}/{op.name}" if op.scope else op.name


def self_times(ops) -> tuple:
    """Each op's time less the ops nested in it (a ``while`` holds its
    body's ops on the same line), and whether it holds any."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [o.end - o.start for o in ops]
    parent = [False] * len(ops)
    stack = []
    for i in order:
        o = ops[i]
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            own[stack[-1]] -= o.end - o.start
            parent[stack[-1]] = True
        stack.append(i)
    return own, parent


def reduce_device(ops, lo: int, hi: int, is_collective=COLLECTIVE.search) -> DeviceReduction:
    """For one device's ops inside ``[lo, hi)``: the busy union of all ops,
    the idle gaps, each op's self time, and the time in which a collective
    runs and no compute op does (innermost ops only: a loop that holds a
    collective is not compute)."""
    own, parent = self_times(ops)
    busy = union(clip([(o.start, o.end) for o in ops], lo, hi))
    leaves = [o for o, p in zip(ops, parent) if not p]
    coll = union(clip([(o.start, o.end) for o in leaves if is_collective(o.name)], lo, hi))
    comp = union(clip([(o.start, o.end) for o in leaves if not is_collective(o.name)], lo, hi))
    op_ns = {}
    for o, t in zip(ops, own):
        inside = min(o.end, hi) - max(o.start, lo)
        if inside > 0 and t > 0:
            k = op_label(o)
            op_ns[k] = op_ns.get(k, 0) + t * inside / (o.end - o.start)
    return DeviceReduction(
        busy_ns=length(busy),
        window_ns=hi - lo,
        collective_ns=length(coll),
        collective_exposed_ns=length(subtract(coll, comp)),
        gaps=subtract([(lo, hi)], busy),
        op_ns=op_ns,
    )


def label_gap(gap, host) -> str:
    """The innermost host span open over the middle of ``gap``."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, e in host:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "(no host span)"


@dataclass
class Reduction:
    devices: dict  # device name -> DeviceReduction
    host: list

    @property
    def n(self) -> int:
        return len(self.devices)

    def mean(self, attr: str) -> float:
        return sum(getattr(d, attr) for d in self.devices.values()) / self.n

    def busy_s(self) -> float:
        return self.mean("busy_ns") / 1e9

    def window_s(self) -> float:
        return self.mean("window_ns") / 1e9

    def idle_share(self) -> float:
        """1 - busy / window, as a mean over devices."""
        return sum(1 - d.busy_ns / d.window_ns for d in self.devices.values()) / self.n

    def collective_ns(self) -> int:
        return sum(d.collective_ns for d in self.devices.values())

    def collective_exposed_share(self) -> float:
        """Exposed collective time over busy time, as a mean over devices."""
        return sum(d.collective_exposed_ns / d.busy_ns for d in self.devices.values()
                   if d.busy_ns) / self.n

    def top_ops(self, k: int = 10) -> list:
        """``[[label, seconds per device]]``, the ``k`` longest."""
        tot = {}
        for d in self.devices.values():
            for name, ns in d.op_ns.items():
                tot[name] = tot.get(name, 0) + ns
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9 / self.n] for name, ns in rows]

    def top_gaps(self, k: int = 10) -> list:
        """``[[host label, seconds]]``: the ``k`` longest idle gaps of any
        device, each named by the host span open over it."""
        gaps = sorted((g for d in self.devices.values() for g in d.gaps),
                      key=lambda g: g[0] - g[1])[:k]
        return [[label_gap(g, self.host), (g[1] - g[0]) / 1e9] for g in gaps]


def reduce(devices: dict, host: list, lo: int, hi: int, is_collective=COLLECTIVE.search) -> Reduction:
    """Reduce every device's ops inside the window ``[lo, hi)``."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    return Reduction(
        {name: reduce_device(ops, lo, hi, is_collective) for name, ops in sorted(devices.items())},
        host,
    )


def window_of(host, name: str):
    """``(start, end)`` of the host span called ``name`` (the harness
    wraps the traced solves in one), or None."""
    spans = [(s, e) for n, s, e in host if n == name]
    return (min(s for s, _ in spans), max(e for _, e in spans)) if spans else None
