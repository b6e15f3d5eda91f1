"""From a profiler trace to numbers: the reduction kept with the benchmark.

``jax.profiler`` writes an ``.xplane.pb`` file; ``ProfileData`` reads it
with nothing but JAX.  On a TPU the planes of interest are
``/device:TPU:<n>`` (lines ``XLA Modules``: one event per run of a
compiled program; ``XLA Ops``: one event per operation, with container
operations such as ``%while`` spanning their bodies) and ``/host:CPU``
(one line per host thread; the benchmark's own ``TraceAnnotation`` spans,
named ``bench:...``, are among them).

Everything below works on plain ``Line`` records, so the tests reduce a
small recorded trace (``tests/perfbench/recorded_trace.json``) without a
profiler.
"""

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench:window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Line:
    names: list          # one str per event
    start: np.ndarray    # ns
    dur: np.ndarray      # ns

    def named(self, pred) -> "Line":
        keep = np.fromiter((pred(n) for n in self.names), bool,
                           len(self.names))
        return Line([n for n, k in zip(self.names, keep) if k],
                    self.start[keep], self.dur[keep])

    def clipped(self, t0: float, t1: float) -> "Line":
        """Events cut to [t0, t1]; those outside are dropped."""
        lo = np.maximum(self.start, t0)
        hi = np.minimum(self.start + self.dur, t1)
        keep = hi > lo
        return Line([n for n, k in zip(self.names, keep) if k],
                    lo[keep], (hi - lo)[keep])


def read_xplane(log_dir: str) -> dict:
    """``{(plane, line): Line}`` of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out, interned = {}, {}
    for plane in data.planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            names, start, dur = [], [], []
            for ev in line.events:
                name = ev.name
                names.append(interned.setdefault(name, name))
                start.append(ev.start_ns)
                dur.append(ev.duration_ns)
            if names:
                out[plane.name, line.name] = Line(
                    names, np.asarray(start, np.float64),
                    np.asarray(dur, np.float64))
    return out


def device_planes(lines: dict) -> list:
    return sorted({p for p, _ in lines if p.startswith(DEVICE_PREFIX)})


def traced_window(lines: dict) -> tuple[float, float]:
    """[start, end] in ns of the benchmark's ``bench:window`` span; where
    the trace holds none, the extent of the device's events."""
    for (plane, _), line in lines.items():
        if plane != HOST_PLANE:
            continue
        for n, s, d in zip(line.names, line.start, line.dur):
            if n == WINDOW_SPAN:
                return float(s), float(s + d)
    spans = [(l.start.min(), (l.start + l.dur).max())
             for (p, _), l in lines.items() if p.startswith(DEVICE_PREFIX)]
    if not spans:
        raise ValueError("the trace holds no device event")
    return float(min(s for s, _ in spans)), float(max(e for _, e in spans))


def union_ns(line: Line) -> float:
    """Length of the union of the line's intervals."""
    if not len(line.start):
        return 0.0
    order = np.argsort(line.start, kind="stable")
    s, e = line.start[order], (line.start + line.dur)[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new run where it starts past everything before
    opens = np.concatenate([[True], s[1:] > reach[:-1]])
    run_start = s[opens]
    run_end = reach[np.concatenate([opens[1:], [True]])]
    return float((run_end - run_start).sum())


def idle_gaps(line: Line, t0: float, t1: float) -> list:
    """``[(start, end)]`` inside [t0, t1] in which no event of the line
    runs."""
    gaps, cursor = [], t0
    order = np.argsort(line.start, kind="stable")
    for s, d in zip(line.start[order], line.dur[order]):
        if s > cursor:
            gaps.append((cursor, min(s, t1)))
        cursor = max(cursor, s + d)
        if cursor >= t1:
            break
    if cursor < t1:
        gaps.append((cursor, t1))
    return [(a, b) for a, b in gaps if b > a]


def _ops(lines: dict, plane: str) -> Line:
    for name in (OPS_LINE, MODULES_LINE):
        if (plane, name) in lines:
            return lines[plane, name]
    return Line([], np.zeros(0), np.zeros(0))


def busy(lines: dict) -> dict:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, and the length of the traced window."""
    t0, t1 = traced_window(lines)
    planes = device_planes(lines)
    if not planes:
        raise ValueError("the trace holds no device plane")
    per = [union_ns(_ops(lines, p).clipped(t0, t1)) for p in planes]
    return {"busy_s": float(np.mean(per)) * 1e-9,
            "window_s": (t1 - t0) * 1e-9, "chips": len(planes)}


def program_seconds(lines: dict, prefix: str) -> tuple[float, int]:
    """Device seconds and runs, inside the traced window and averaged over
    the device planes, of the compiled programs whose name starts with
    ``prefix`` (``jit_step_impl``, ``jit_prefill_impl``, ``jit_run``)."""
    t0, t1 = traced_window(lines)
    planes = device_planes(lines)
    total, runs = 0.0, 0
    for p in planes:
        line = lines.get((p, MODULES_LINE))
        if line is None:
            continue
        hit = line.named(lambda n: n.startswith(prefix)).clipped(t0, t1)
        total += hit.dur.sum()
        runs += len(hit.names)
    k = max(len(planes), 1)
    return total * 1e-9 / k, runs // k


def self_seconds(line: Line) -> dict:
    """``{name: seconds}`` of each operation's own time: a container's
    (``%while``, ``%conditional``, ``%call``) is what its body leaves."""
    order = np.argsort(line.start, kind="stable")
    own: dict = {}
    stack: list = []   # [end, name, self_ns]
    for i in order:
        s, d, n = line.start[i], line.dur[i], line.names[i]
        while stack and stack[-1][0] <= s:
            _, name, ns = stack.pop()
            own[name] = own.get(name, 0.0) + ns
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, n, d])
    for _, name, ns in stack:
        own[name] = own.get(name, 0.0) + ns
    return {k: v * 1e-9 for k, v in own.items()}


_HLO = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = \(?(\w+)\[([\d,]*)\]")


def short_name(hlo: str) -> str:
    """``copy_bf16_32_16_512_128`` for ``%copy.476 = bf16[32,16,512,128]{...}
    copy(...)``: the operation and its first result's type and shape, which
    are what XLA's names offer until the program names its own scopes."""
    m = _HLO.match(hlo)
    if not m:
        return re.sub(r"[^\w\-:.]+", "_", hlo)[:60]
    kind, dtype, shape = m.groups()
    if " custom-call(" in hlo:
        kind += ":custom-call"
    shape = "_".join(shape.split(",")) if shape else "scalar"
    return f"{kind}_{dtype}_{shape}"


def top_device_ops(lines: dict, k: int = 10) -> list:
    """The k operations (by short name) that took most of the device's own
    time in the traced window, ``[[name, seconds], ...]``."""
    t0, t1 = traced_window(lines)
    total: dict = {}
    planes = device_planes(lines)
    for p in planes:
        if (p, OPS_LINE) not in lines:
            continue
        for name, s in self_seconds(
                lines[p, OPS_LINE].clipped(t0, t1)).items():
            key = short_name(name)
            total[key] = total.get(key, 0.0) + s / len(planes)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s] for n, s in top]


def kernel_seconds(lines: dict, pred) -> tuple[float, int]:
    """Device seconds and calls, in the traced window and averaged over the
    planes, of the operations whose HLO text satisfies ``pred``."""
    t0, t1 = traced_window(lines)
    planes = device_planes(lines)
    total, calls = 0.0, 0
    for p in planes:
        if (p, OPS_LINE) not in lines:
            continue
        hit = lines[p, OPS_LINE].named(pred).clipped(t0, t1)
        total += hit.dur.sum()
        calls += len(hit.names)
    k = max(len(planes), 1)
    return total * 1e-9 / k, calls // k


def is_mosaic_call(hlo: str) -> bool:
    return " custom-call(" in hlo


def idle_by_host_activity(lines: dict, k: int = 10,
                          min_gap_ns: float = 20e3) -> list:
    """The device's idle time in the traced window, by what the host was
    doing: each gap of the first device plane goes to the shortest host
    event that covers at least half of it (``bench:`` spans included),
    gaps under 20 us to ``between_ops``."""
    t0, t1 = traced_window(lines)
    planes = device_planes(lines)
    gaps = idle_gaps(_ops(lines, planes[0]).clipped(t0, t1), t0, t1)
    host = [(l.start, l.start + l.dur, l.dur, l.names)
            for (p, _), l in lines.items() if p == HOST_PLANE]
    out: dict = {}
    for a, b in gaps:
        if b - a < min_gap_ns:
            out["between_ops"] = out.get("between_ops", 0.0) + (b - a)
            continue
        best, best_dur = "unattributed", np.inf
        for s, e, d, names in host:
            cover = np.minimum(e, b) - np.maximum(s, a)
            ok = np.nonzero((cover >= 0.5 * (b - a)) & (d < best_dur)
                            & (d > 0))[0]
            if len(ok):
                i = ok[np.argmin(d[ok])]
                if names[i] != WINDOW_SPAN:
                    best, best_dur = names[i], d[i]
        out[best] = out.get(best, 0.0) + (b - a)
    top = sorted(out.items(), key=lambda kv: -kv[1])[:k]
    return [[re.sub(r"[^\w\-:.]+", "_", n)[:60], s * 1e-9] for n, s in top]


def span_seconds(lines: dict, name: str) -> tuple[float, int, float]:
    """Total seconds and count of the host spans called ``name`` inside the
    traced window, and the device-idle seconds inside them."""
    t0, t1 = traced_window(lines)
    planes = device_planes(lines)
    dev = _ops(lines, planes[0]).clipped(t0, t1) if planes else None
    total, count, idle = 0.0, 0, 0.0
    for (p, _), l in lines.items():
        if p != HOST_PLANE:
            continue
        hit = l.named(lambda n: n == name).clipped(t0, t1)
        for s, d in zip(hit.start, hit.dur):
            total += d
            count += 1
            if dev is not None:
                idle += d - union_ns(dev.clipped(s, s + d))
    return total * 1e-9, count, idle * 1e-9
