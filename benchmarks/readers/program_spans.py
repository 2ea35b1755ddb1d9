"""The device's idle time, split by what the PROGRAM was doing: the host
spans the program itself writes (`obs.phase` in
distributedtraining_tpu/utils/obs.py: `serve.step`, `serve.admit`,
`serve.decode.fetch`, ...) lie in the profiler's trace on the device's
clock, one line of the `/host:*` planes per thread.

Every instant of the traced window in which no operation ran on the first
device plane goes to the INNERMOST program span that covers it: of all spans
open at that instant, on any thread, the one that started last (the
shortest, where two started together). An instant no span covers goes to
nothing. So the idle time is partitioned exactly: the metrics that read this
add up to `trace_idle`'s reading.

`spans` names the spans whose idle time a metric sums; `outside` instead
asks for the idle time at instants that NO span of that name covers (the
caller's loop between two `serve.step`s). With `of="time"` a metric reads
the named spans' OWN time inside the traced window instead (the host's
wait in `serve.decode.fetch`), whatever the device did meanwhile. A trace without
any program span (a program from before they existed) reads as nothing, not
as zero.
"""

from __future__ import annotations

import functools

from . import xplane

# the serve engine's step is the one loop that writes phases; `obs.span`
# (`push.` / `avg.` / `val.`) is not on the profiler's clock
PROGRAM_PREFIXES = ("serve.",)

Span = tuple  # (name, start_ns, end_ns)


@functools.lru_cache(maxsize=2)
def _load_file(path: str) -> tuple:
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, int(ev.start_ns),
                      int(ev.start_ns) + int(ev.duration_ns))
                     for ev in line.events
                     if ev.name.startswith(PROGRAM_PREFIXES)]
            if spans:
                threads.append(tuple(spans))
    return tuple(threads)


def load(trace_dir: str) -> tuple:
    """The program's spans in the newest trace under `trace_dir`, one tuple
    of `(name, start_ns, end_ns)` per thread that wrote any."""
    return _load_file(xplane.find_xplane(trace_dir))


def timeline(threads, lo: int, hi: int) -> list:
    """[(t0, t1, innermost, covering)] over [lo, hi): consecutive pieces in
    each of which the set of open program spans does not change.
    `innermost` is the name of the open span that started last (None where
    none is open), `covering` the names of all open spans."""
    spans = [(n, max(a, lo), min(b, hi)) for th in threads for n, a, b in th
             if min(b, hi) > max(a, lo)]
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)})
    # open spans per piece by one sweep over the starts
    spans.sort(key=lambda s: s[1])
    out, open_, i = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][1] <= t0:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s[2] > t0]
        if open_:
            inner = max(open_, key=lambda s: (s[1], -s[2]))[0]
            out.append((t0, t1, inner, frozenset(s[0] for s in open_)))
        else:
            out.append((t0, t1, None, frozenset()))
    return out


def idle_gaps(trace) -> list:
    """[(a, b)]: where no operation ran on the first device plane, inside
    the traced window (all of it where the trace holds no device plane, as
    `trace_idle` reads that)."""
    lo, hi = trace.window
    first = next(iter(trace.device_ops.values()), ())
    busy = xplane.busy_intervals(first, lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_pieces(trace, threads) -> list:
    """[(idle_ns, innermost, covering)]: the idle gaps cut along the
    timeline's pieces (both are sorted: one merge)."""
    lo, hi = trace.window
    pieces, out, j = timeline(threads, lo, hi), [], 0
    for a, b in idle_gaps(trace):
        while pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            t0, t1, inner, cover = pieces[k]
            out.append((min(b, t1) - max(a, t0), inner, cover))
            k += 1
    return out


def idle_pct(trace, threads, *, spans=None, outside=None):
    """Idle time as % of the traced window: inside the spans named by
    `spans` (as the innermost), or outside every span named `outside`."""
    if (spans is None) == (outside is None):
        raise ValueError("give `spans` or `outside`, one of them")
    if not threads:
        return None
    if spans is not None:
        want = frozenset(spans)
        ns = sum(d for d, inner, _ in idle_pieces(trace, threads)
                 if inner in want)
    else:
        ns = sum(d for d, _, cover in idle_pieces(trace, threads)
                 if outside not in cover)
    return 100.0 * ns / (trace.window[1] - trace.window[0])


def time_pct(trace, threads, spans):
    """The named spans' own time, clipped to the traced window and summed
    over the threads, as % of the window."""
    if not threads:
        return None
    lo, hi = trace.window
    want = frozenset(spans)
    ns = sum(max(0, min(b, hi) - max(a, lo))
             for th in threads for n, a, b in th if n in want)
    return 100.0 * ns / (hi - lo)


def read(rec, *, spans=None, outside=None, of="idle"):
    if rec.trace is None or not rec.run.trace_dir:
        return None
    threads = load(rec.run.trace_dir)
    if of == "time":
        return time_pct(rec.trace, threads, spans)
    return idle_pct(rec.trace, threads, spans=spans, outside=outside)
