"""From the profiler's `.xplane.pb` to plain event lists, and from those to
busy time, idle gaps and time by operation. The second half works on plain
tuples, so that tests can feed it a small recorded trace.

What one trace of this chip showed (PERF.md, Findings, PR 23): the device
planes are named `/device:TPU:<n>`; the line `XLA Ops` of each holds one
event per executed HLO operation, `XLA Modules` one per program run, named
`jit_<function>(<fingerprint>)`. An event's name on `XLA Ops` is the whole
HLO instruction, `%name.N = shape opcode(operands...)`, so the names of its
OPERANDS are in it too: a kernel is told by the instruction's own name (the
part before ` = `) and its opcode, never by a search over the whole text.
The benchmark's host spans (`bench.*`, `jax.profiler.TraceAnnotation`) sit on
the `/host:CPU` plane, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"

Event = tuple  # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    device_ops: dict            # plane name -> [Event], sorted by start
    host_spans: list            # [Event] of the benchmark's own spans
    window: tuple               # (start_ns, end_ns) of the traced window
    # plane name -> [Event], one per program run
    device_modules: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str) -> Trace:
    """Read the newest trace under `trace_dir` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    device_ops, device_modules, host_spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                into = {OPS_LINE: device_ops,
                        MODULES_LINE: device_modules}.get(line.name)
                if into is not None:
                    into[plane.name] = sorted(
                        ((ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for ev in line.events), key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.name, int(ev.start_ns),
                                           int(ev.duration_ns)))
    return from_events(device_ops, host_spans, device_modules)


def from_events(device_ops: dict, host_spans: list,
                device_modules: dict | None = None) -> Trace:
    """The traced window is the `bench.trace_window` span; where a recorded
    trace has none, the extent of the device events."""
    marks = [e for e in host_spans if e[0] == WINDOW_SPAN]
    if marks:
        window = (marks[0][1], marks[0][1] + marks[0][2])
    else:
        every = [e for evs in device_ops.values() for e in evs]
        window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    spans = sorted((e for e in host_spans if e[0] != WINDOW_SPAN),
                   key=lambda e: e[1])
    return Trace(device_ops, spans, window, device_modules or {})


def _clip(events, lo: int, hi: int):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events, lo: int, hi: int) -> list:
    """Union of the intervals in which an operation ran, inside [lo, hi)."""
    out = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    lo, hi = trace.window
    per_plane = [sum(b - a for a, b in busy_intervals(evs, lo, hi))
                 for evs in trace.device_ops.values()]
    return sum(per_plane) / len(per_plane) / 1e9 if per_plane else 0.0


def seconds_by_op(trace: Trace) -> dict:
    """Device seconds by operation name, summed over planes. Operations
    that contain others (a `while` and its body) both appear: this is a
    ranking, not a partition."""
    lo, hi = trace.window
    out: dict = {}
    for evs in trace.device_ops.values():
        for name, a, b in _clip(evs, lo, hi):
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def matching_ops(trace: Trace, pattern: str, opcode: str = "") -> list:
    """[(own name, seconds)] of every device event whose OWN name (the
    instruction's, before ` = `) matches `pattern` and, where `opcode` is
    given, whose instruction is of that kind (`custom-call` for a Pallas
    kernel). An instruction that only takes a kernel's result as an operand
    (`%slice-start.N = ... async-start(... %jit_flash_attention_.M)`) does
    not match."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    out = []
    for evs in trace.device_ops.values():
        for name, a, b in _clip(evs, lo, hi):
            own, _, rest = name.partition(" = ")
            if rx.search(own) and (not opcode or f" {opcode}(" in rest):
                out.append((own, (b - a) / 1e9))
    return out


def program_runs(trace: Trace, pattern: str) -> float:
    """How many runs of the programs whose name matches `pattern` lie in
    the traced window, averaged over the device planes. A run cut by an
    edge of the window counts by the share of it that lies inside, as the
    operations' time is clipped too."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    per_plane = []
    for evs in trace.device_modules.values():
        runs = 0.0
        for name, start, dur in evs:
            if dur > 0 and rx.search(name):
                inside = min(start + dur, hi) - max(start, lo)
                runs += max(0, inside) / dur
        per_plane.append(runs)
    return sum(per_plane) / len(per_plane) if per_plane else 0.0


def idle_by_host_span(trace: Trace) -> dict:
    """Idle seconds of the first device plane, by what the host was doing:
    each gap between busy intervals goes to the benchmark's host span that
    overlaps most of it (`(no bench span)` where none does)."""
    lo, hi = trace.window
    if not trace.device_ops:
        return {}
    evs = next(iter(trace.device_ops.values()))
    busy = busy_intervals(evs, lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out: dict = {}
    spans = trace.host_spans
    j = 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] + spans[j][2] <= a:
            j += 1
        best, best_ov = "(no bench span)", 0
        k = j
        while k < len(spans) and spans[k][1] < b:
            ov = min(b, spans[k][1] + spans[k][2]) - max(a, spans[k][1])
            if ov > best_ov:
                best, best_ov = spans[k][0], ov
            k += 1
        out[best] = out.get(best, 0.0) + (b - a) / 1e9
    return out


_AUTO_NAMED = re.compile(
    r"^%?(fusion|custom-call|copy|copy-start|copy-done|convolution|dot|"
    r"reduce|transpose|bitcast|broadcast|slice|dynamic-slice|"
    r"dynamic-update-slice|select|add|multiply|convert|while|"
    r"[a-z_\-]*fusion)$")


def short_name(event_name: str) -> str:
    """An event's name is the whole HLO instruction. Keep what tells
    operations apart: a kernel's or scope's own name without its `.N`
    suffix (so its calls add up), or, for XLA's automatic names, the name
    with the suffix and the start of the result's shape."""
    name, _, rest = event_name.partition(" = ")
    base = re.sub(r"(\.\d+)?(\.remat\d*)?$", "", name)
    if _AUTO_NAMED.match(base):
        return f"{name} {rest.split(' ')[0][:60]}".strip()[:100]
    return base[:100]


def breakdown(trace: Trace, top: int = 10) -> dict:
    by: dict = {}
    for name, secs in seconds_by_op(trace).items():
        key = short_name(name)
        by[key] = by.get(key, 0.0) + secs
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle_by_host_span(trace).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
