"""Device time of one run of a program, by the SCOPE its instructions were
traced under: from the profiler's `.xplane.pb` to `(name, start, duration,
op_name)` tuples of the first device plane's `XLA Ops` line, and from those
to a table scope x pass. Two halves like `xplane.py`: the second works on
plain tuples, so that a test can feed it a small recording.

What one trace of this chip showed (PERF.md, Findings, PR 36). The file
holds each instruction's `op_name`, the name stack JAX gave it at trace
time, e.g.
`jit(train_step)/transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/rematted_computation/h_0/gpt2.attn/c_attn/dot_general`:
the repo's `jax.named_scope`s (docs/observability.md, "scopes inside device
programs"), flax's module path beneath them, JAX's own wrappers around them.
It holds it ONCE per instruction, as the stat `tf_op` of the event's
METADATA (`XPlane.event_metadata`), not with each event:
`jax.profiler.ProfileData` hands out an event's own stats alone
(`device_offset_ps`, `device_duration_ps`), and the `/host:metadata` plane
has no line. So the first half reads that one table from the file's bytes
(`event_metadata_stat`: the protobuf wire format of
tsl/profiler/protobuf/xplane.proto, the lines skipped unread) and joins it
to ProfileData's events by their name, which is the whole HLO instruction
and so one instruction's alone inside a program.

- The SCOPE of an event is the innermost component of that path that lies in
  the repo's dotted vocabulary (`train.`, `gpt2.`, `lfm2.`, `moe.`, `mla.`,
  `ssm.`), wrappers (`jvp(..)`, `transpose(..)`, `jit(..)`) stripped; an
  event with none is UNNAMED. A fusion carries its root's `op_name`, so an
  elementwise operation fused across a scope's border counts on the root's
  side.
- The PASS is `rerun` where a component is `rematted_computation` (remat's
  re-run of the forward inside the backward), else `backward` where one
  starts with `transpose(`, else `forward`.
- SELF time: an event that contains other events of the line (`while`,
  `conditional`, `call`) counts for its own time less theirs, so the classes
  partition a run: classes + unnamed + the gaps in which no operation ran
  add up to the run (`xplane.seconds_by_op` is "a ranking, not a partition").
- Only WHOLE runs of a program matching `module_pattern` (`XLA Modules`, as
  `trace_module` tells them) inside the traced window count.

A trace whose events carry no `op_name` (another backend, a profiler that
drops it) reads as nothing, not as zero.
"""

from __future__ import annotations

import functools
import re

from . import xplane

OP_NAME_STAT = "tf_op"
VOCABULARY = ("train.", "gpt2.", "lfm2.", "moe.", "mla.", "ssm.")
PASSES = ("forward", "rerun", "backward")
REMAT_COMPONENT = "rematted_computation"

_WRAPPED = re.compile(r"^[A-Za-z_][\w.\-]*\((.*)\)$")


# ---------------------------------------------------------------------------
# first half: the trace file
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one protobuf message in buf[i:end]: a
    varint's value, or (start, end) of a length-delimited field, which is
    never copied nor looked into here; fixed-width fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _message(buf, span: tuple) -> dict:
    """{field number: last value} of a small message (a map entry, an
    XStatMetadata); repeated fields are not for this."""
    return dict(_fields(buf, *span))


def event_metadata_stat(buf, stat: str = OP_NAME_STAT,
                        plane_prefix: str = xplane.DEVICE_PLANE_PREFIX
                        ) -> dict:
    """{event name: the string the stat `stat` holds in that event's
    metadata} of the first plane whose name starts with `plane_prefix`, from
    the bytes of an XSpace. xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .lines = 3 (skipped), .event_metadata = 4 and .stat_metadata = 5 (maps:
    entries of key = 1, value = 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (the id of a stat metadata whose NAME is the value)."""
    buf = memoryview(buf)

    def text(span) -> str:
        return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")

    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *span):
            if f == 2:
                name = text(v)
            elif f == 4:
                events.append(_message(buf, v).get(2))
            elif f == 5:
                entry = _message(buf, v)
                stat_names[entry.get(1)] = text(
                    _message(buf, entry[2]).get(2, (0, 0)))
        if not name.startswith(plane_prefix):
            continue
        wanted = {i for i, n in stat_names.items() if n == stat}
        out = {}
        for ev in events:
            if ev is None:
                continue
            ev_name = value = None
            for f, v in _fields(buf, *ev):
                if f == 2:
                    ev_name = text(v)
                elif f == 5:
                    st = _message(buf, v)
                    if st.get(1) in wanted:
                        value = (text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7)))
            if ev_name is not None and value:
                out[ev_name] = value
        return out
    return {}


@functools.lru_cache(maxsize=2)
def _load_file(path: str) -> tuple:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    # `tf_op` is "<op_name>:<op type>", the type empty for a JAX program
    op_names = {name: tf_op.rpartition(":")[0] or tf_op
                for name, tf_op in event_metadata_stat(raw).items()}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if xplane.OPS_LINE not in lines:
            continue
        ops = sorted(((ev.name, int(ev.start_ns), int(ev.duration_ns),
                       op_names.get(ev.name, ""))
                      for ev in lines[xplane.OPS_LINE].events),
                     key=lambda e: (e[1], -e[2]))
        modules = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in getattr(lines.get(xplane.MODULES_LINE),
                                     "events", ())]
        return tuple(ops), tuple(modules)
    return (), ()


def load(trace_dir: str) -> tuple:
    """(operations with their `op_name`, sorted by start; program runs) of
    the first device plane, from the newest trace under `trace_dir`."""
    return _load_file(xplane.find_xplane(trace_dir))


# ---------------------------------------------------------------------------
# second half: plain tuples
# ---------------------------------------------------------------------------

def _unwrap(component: str) -> str:
    """`transpose(jvp(gpt2.attn))` -> `gpt2.attn`."""
    while True:
        m = _WRAPPED.match(component)
        if m is None:
            return component
        component = m.group(1)


@functools.lru_cache(maxsize=None)     # a program's instructions: thousands
def scope_of(op_name: str) -> str | None:
    """The innermost component in the repo's dotted vocabulary."""
    for component in reversed(op_name.split("/")):
        inner = _unwrap(component)
        if inner.startswith(VOCABULARY):
            return inner
    return None


@functools.lru_cache(maxsize=None)
def pass_of(op_name: str) -> str:
    components = op_name.split("/")
    if any(REMAT_COMPONENT in c for c in components):
        return "rerun"
    if any(c.startswith("transpose(") for c in components):
        return "backward"
    return "forward"


def whole_runs(modules, pattern: str, lo: int, hi: int) -> list:
    """[(start, end)] of the runs of a program matching `pattern` that lie
    whole inside [lo, hi], as `trace_module` tells them."""
    rx = re.compile(pattern)
    return sorted((start, start + dur) for name, start, dur in modules
                  if dur > 0 and start >= lo and start + dur <= hi
                  and rx.search(name))


def run_ms(runs) -> float:
    """A run's mean time on the device's clock, in milliseconds."""
    return sum(hi - lo for lo, hi in runs) / len(runs) / 1e6


def self_times(events) -> list:
    """[[event, self_ns]] of events sorted by start (the longer first where
    two start together): an event's duration less that of the events it
    contains directly. A child that outlasts its parent is clipped to it."""
    out, stack = [], []     # stack: [index into out, end_ns]
    for ev in events:
        start, end = ev[1], ev[1] + ev[2]
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            end = min(end, stack[-1][1])
            out[stack[-1][0]][1] -= end - start
        out.append([ev, end - start])
        stack.append((len(out) - 1, end))
    return out


def classify(ops, runs) -> list | None:
    """[(own name, op_name, scope or None, pass, self_ns)] of the
    operations that start inside a run (`ops` sorted by start), each run's
    self times taken apart from the others'. None where there is no run, or
    where no such operation carries an `op_name`."""
    out, i = [], 0
    for lo, hi in runs:
        while i < len(ops) and ops[i][1] < lo:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < hi:
            j += 1
        out += [(ev[0].partition(" = ")[0], ev[3], scope_of(ev[3]),
                 pass_of(ev[3]), ns) for ev, ns in self_times(ops[i:j])]
        i = j
    return tuple(out) if any(row[1] for row in out) else None


def table(runs, rows) -> dict | None:
    """{"runs", "run_ms", "gap_ms", "cells": {(scope or None, pass): ms},
    "unnamed": {(own name, op_name): ms}} of `classify`'s rows, every time
    the mean over the whole runs; None for its None."""
    if rows is None:
        return None
    per_ms = 1.0 / len(runs) / 1e6
    cells: dict = {}
    unnamed: dict = {}
    for own, op_name, scope, which, ns in rows:
        cells[scope, which] = cells.get((scope, which), 0.0) + ns * per_ms
        if scope is None:
            unnamed[own, op_name] = (unnamed.get((own, op_name), 0.0)
                                     + ns * per_ms)
    return {"runs": len(runs), "run_ms": run_ms(runs),
            "gap_ms": run_ms(runs) - sum(r[4] for r in rows) * per_ms,
            "cells": cells, "unnamed": unnamed}


def select(runs, rows, *, scope=None, passes=None, exclude_own=None,
           unnamed=False) -> float | None:
    """Mean milliseconds a run of the rows whose scope matches the regex
    `scope` whole (every named scope where it is None), whose pass is in
    `passes` and whose OWN instruction name does not match `exclude_own`;
    with `unnamed` the rows with no scope of the vocabulary instead. A
    program in which no instruction carries such a scope (the parent of the
    PR that named it) reads as nothing; no unnamed row is a share of 0."""
    if rows is None:
        return None
    rx = re.compile(scope) if scope else None
    rx_own = re.compile(exclude_own) if exclude_own else None
    found = [ns for own, _, sc, which, ns in rows
             if unnamed == (sc is None)
             and (rx is None or rx.fullmatch(sc))
             and (passes is None or which in passes)
             and (rx_own is None or not rx_own.search(own))]
    if not found and not unnamed:
        return None
    return sum(found) / len(runs) / 1e6


@functools.lru_cache(maxsize=4)
def _rows(trace_dir: str, module_pattern: str, lo: int, hi: int) -> tuple:
    """(whole runs, `classify`'s rows) of one traced run, made once for all
    the metrics that read it."""
    ops, modules = load(trace_dir)
    runs = whole_runs(modules, module_pattern, lo, hi)
    return runs, classify(ops, runs)


def _rows_of(rec, module_pattern: str) -> tuple:
    if rec.trace is None or not rec.run.trace_dir:
        return [], None
    return _rows(rec.run.trace_dir, module_pattern, *rec.trace.window)


def table_for(rec, *, module_pattern: str) -> dict | None:
    return table(*_rows_of(rec, module_pattern))


def format_table(tab: dict | None, top_unnamed: int = 20) -> str:
    """The whole table as text: scope x pass in ms a run and % of the run,
    then the largest unnamed instructions."""
    if tab is None:
        return "trace_scope: no whole run, or no event carries an op_name"
    run_ms = tab["run_ms"]
    lines = [f"{tab['runs']} whole runs, {run_ms:.3f} ms a run on the "
             f"device's clock; gaps inside a run {tab['gap_ms']:.3f} ms",
             f"{'scope':<18}" + "".join(f"{p:>11}" for p in PASSES)
             + f"{'all':>11}{'% of run':>10}"]
    scopes = sorted({s for s, _ in tab["cells"]},
                    key=lambda s: -sum(tab["cells"].get((s, p), 0.0)
                                       for p in PASSES))
    for s in scopes:
        row = [tab["cells"].get((s, p), 0.0) for p in PASSES]
        lines.append(f"{s or '(unnamed)':<18}"
                     + "".join(f"{v:>11.3f}" for v in row)
                     + f"{sum(row):>11.3f}{100.0 * sum(row) / run_ms:>10.2f}")
    col = [sum(v for (_, p), v in tab["cells"].items() if p == q)
           for q in PASSES]
    lines.append(f"{'(all)':<18}" + "".join(f"{v:>11.3f}" for v in col)
                 + f"{sum(col):>11.3f}{100.0 * sum(col) / run_ms:>10.2f}")
    lines.append(f"the {top_unnamed} largest unnamed instructions "
                 "(ms a run, own name, op_name):")
    for (own, op_name), ms in sorted(tab["unnamed"].items(),
                                     key=lambda kv: -kv[1])[:top_unnamed]:
        lines.append(f"  {ms:>8.3f}  {own}  {op_name or '(no op_name)'}")
    return "\n".join(lines)


def read(rec, *, module_pattern: str, scope: str | None = None,
         passes: list | None = None, exclude_own: str | None = None,
         unnamed: bool = False, as_pct: bool = False):
    runs, rows = _rows_of(rec, module_pattern)
    ms = select(runs, rows, scope=scope, passes=passes,
                exclude_own=exclude_own, unnamed=unnamed)
    if ms is None or not as_pct:
        return ms
    return 100.0 * ms / run_ms(runs)
