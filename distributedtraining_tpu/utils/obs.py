"""Round-trip observability: spans, a phase-timing registry, correlation
ids, and anomaly-triggered profiler capture.

The reference ships flat per-role scalar logging (utils/mlflow_utils.py);
after the validator's fetch/eval pipeline and the miner's background
publish worker, the hot paths are asynchronous and cross-thread — a
regression in push latency or fetch staleness is invisible in flat logs.
This module is the one home of the structured layer every role emits:

- ``span("push.upload")`` context managers record start/duration records
  through the process's configured :class:`MetricsSink` (the same JSONL
  file the scalar metrics land in) and feed a latency histogram per span
  name. Spans nest; each record carries its parent and depth.
- ``phase("serve.admit")`` is the hot-loop primitive beside ``span``: a
  host span on the profiler's clock (``jax.profiler.TraceAnnotation``)
  plus one histogram observation, no record. The serve engine's step
  and ``MinerLoop.run`` (``miner.*``) are timed with it; a ``span`` opens
  the same annotation, so both lie in one device trace.
- a process-wide :class:`Registry` of counters and latency histograms
  (p50/p95/p99 from bounded ring reservoirs) with name linting —
  ``[a-z0-9_.]`` only, and one name cannot be both a counter and a
  histogram. ``flush()`` snapshots it through the sink at each role's
  natural cadence (miner log boundary, validator/averager round end).
- a **correlation id** per published artifact: the miner stamps
  ``delta_id`` into the delta's meta rider (transport/base.py), the
  validator and averager read it back and tag their fetch/screen/eval/
  merge spans with it — one artifact's life (snapshot -> upload ->
  fetch -> screen -> cohort-eval -> merge) is then reconstructable from
  the per-role JSONL files by ``scripts/obs_report.py``.
- :class:`AnomalyMonitor`: a loss spike, a push-failure streak, or a
  step-time p99 blowout arms a ONE-SHOT ``TraceCapture``
  (utils/metrics.py) so the profiler evidence of the first anomaly is on
  disk before anyone is paged.

Everything here is off unless a sink is configured (``configure``): the
module-level ``count``/``observe`` helpers and ``span`` are single-branch
no-ops when disabled, so instrumentation costs nothing in tests and
tight loops that never opt in (one ``obs.phase``: 0.48 us off, 2.58 us
on, PERF.md §6, PR 25).

Thread discipline: the registry and the span emitter are lock-protected
(the publish worker spans from its background thread while the train
loop spans concurrently); the span STACK and current correlation id are
thread-local, so a worker thread must re-enter its artifact's id via
``correlate(cid)`` — DeltaPublisher does exactly that.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
import re
import threading
import time
from collections import deque
from typing import Any, Iterable

logger = logging.getLogger(__name__)

_NAME_RE = re.compile(r"^[a-z0-9_.]+$")

# cap on a correlation id read back from a PEER-CONTROLLED rider
_CID_MAX_LEN = 120


def check_metric_name(name: str) -> str:
    """Registry name lint: reject anything outside ``[a-z0-9_.]`` before
    it reaches a backend (MLflow key rules, grep-ability, and the
    flattened ``<name>.p99`` snapshot spelling all assume it)."""
    if not isinstance(name, str) or not _NAME_RE.match(name):
        raise ValueError(
            f"invalid metric name {name!r}: must match [a-z0-9_.]+")
    return name


# ---------------------------------------------------------------------------
# Instruments
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic float counter (thread-safe)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = check_metric_name(name)
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def merge_from(self, other: "Counter") -> None:
        """Fold another counter's total into this one (Registry.merge)."""
        self.inc(other.value)


class Gauge:
    """Last-value-wins gauge (thread-safe) — point-in-time levels the
    counter/histogram pair can't express: device memory watermarks, cache
    residency, fleet node counts. Snapshots as the bare name, like a
    counter."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = check_metric_name(name)
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        return self._value

    def merge_from(self, other: "Gauge") -> None:
        """Last-merged-wins, matching the instrument's own semantics: the
        most recently merged registry's level is the one that survives
        (Registry.merge documents the ordering contract)."""
        self.set(other.value)


def percentile(sorted_vals, q: float) -> float:
    """numpy's default ('linear') percentile on an already-sorted list —
    implemented locally so the hot observability path never imports
    numpy (tests pin this against ``np.percentile``)."""
    n = len(sorted_vals)
    if n == 0:
        return float("nan")
    if n == 1:
        return float(sorted_vals[0])
    pos = (n - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = math.ceil(pos)
    frac = pos - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


class Histogram:
    """Latency histogram over a bounded ring reservoir (thread-safe).

    The ring keeps the most recent ``capacity`` observations — percentiles
    reflect CURRENT behavior, which is what an anomaly check wants (a
    classic reservoir sample would dilute a fresh regression with hours
    of healthy history). ``count``/``total`` are lifetime."""

    __slots__ = ("name", "capacity", "_ring", "_count", "_total", "_lock")

    def __init__(self, name: str, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = check_metric_name(name)
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._count = 0
        self._total = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self._ring.append(float(value))
            self._count += 1
            self._total += float(value)

    @property
    def count(self) -> int:
        return self._count

    @property
    def total(self) -> float:
        return self._total

    def percentiles(self, qs: Iterable[float] = (50.0, 95.0, 99.0)
                    ) -> dict[str, float]:
        with self._lock:
            vals = sorted(self._ring)
        return {f"p{int(q)}": percentile(vals, q) for q in qs}

    def snapshot(self) -> dict[str, float]:
        out = {"count": float(self._count), "sum": self._total}
        if self._count:
            out.update(self.percentiles())
        return out

    def merge_from(self, other: "Histogram") -> None:
        """Fold another histogram in: lifetime count/sum add, and the
        other ring's observations extend this ring (still bounded by THIS
        ring's capacity — merging many actors keeps the newest tail, the
        same recency rule a single ring lives by)."""
        with other._lock:
            vals = list(other._ring)
            count, total = other._count, other._total
        with self._lock:
            self._ring.extend(vals)
            self._count += count
            self._total += total


class Registry:
    """Named counters + histograms; get-or-create, kind-checked.

    One name is ONE instrument: registering ``x`` as a counter after it
    exists as a histogram (or vice versa) raises — the duplicate-
    registration lint, so two call sites cannot silently split a metric
    into two series.

    ``max_names`` caps the metric-name CARDINALITY: once the registry
    holds that many distinct names, a request for a NEW name logs one
    warning, bumps ``dropped_names``, and returns a detached instrument
    (fully usable, never snapshotted) — callers keep working, the
    registry stays bounded. A 1000-actor fleet simulation
    (engine/fleetsim.py) hands every actor its own capped Registry, so
    one noisy actor cannot grow the process's metric vocabulary without
    bound. None (the default) keeps the historical unbounded behavior.

    ``merge(other)`` folds another registry in — counters add, gauges
    are last-merged-wins, histogram rings concatenate (bounded by the
    receiving ring's capacity) — which is how the fleet simulator
    assembles one scorecard registry from hundreds of per-actor ones.
    A kind mismatch between same-named instruments raises, the same
    duplicate-registration lint as ``_get``."""

    def __init__(self, *, max_names: int | None = None):
        if max_names is not None and max_names < 1:
            raise ValueError(f"max_names must be >= 1, got {max_names}")
        self._lock = threading.Lock()
        self._metrics: dict[str, Any] = {}
        self.max_names = max_names
        self.dropped_names = 0
        self._warned_cap = False

    def _get(self, name: str, kind) -> Any:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                if self.max_names is not None \
                        and len(self._metrics) >= self.max_names:
                    self.dropped_names += 1
                    if not self._warned_cap:
                        self._warned_cap = True
                        logger.warning(
                            "registry at its %d-name cardinality cap; "
                            "dropping new metric %r (and any further new "
                            "names, counted in dropped_names)",
                            self.max_names, name)
                    return kind(name)  # detached: usable, never snapshotted
                m = self._metrics[name] = kind(name)
            elif not isinstance(m, kind):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {kind.__name__}")
            return m

    def merge(self, other: "Registry") -> "Registry":
        """Fold ``other``'s instruments into this registry (see class
        docstring for per-kind semantics); returns self so scorecard
        assembly can chain ``reduce``-style. Names past this registry's
        cap are dropped-and-counted like any other new name."""
        with other._lock:
            items = list(other._metrics.items())
        for name, m in items:
            self._get(name, type(m)).merge_from(m)
        return self

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._metrics)

    def peek(self, name: str) -> Any | None:
        """The registered instrument under ``name`` (or None) WITHOUT
        creating one — consumers that render a specific instrument's
        richer view (the exporter's labeled quantile gauges) must never
        mint empty series as a side effect of looking."""
        with self._lock:
            return self._metrics.get(name)

    def digest(self) -> str:
        """Short stable digest of the registered metric VOCABULARY (names,
        not values). Rides in heartbeats (engine/health.py) so a fleet
        report can flag nodes running a different instrumentation version
        — after an auto-update that renames metrics, aggregating their
        snapshots with the rest of the fleet's would silently compare
        different quantities."""
        import hashlib
        return hashlib.sha256(
            ",".join(self.names()).encode()).hexdigest()[:12]

    def __len__(self) -> int:
        return len(self._metrics)

    def snapshot(self) -> dict[str, float]:
        """Flat numeric dict: counters as ``name``, histograms as
        ``name.count/.sum/.p50/.p95/.p99`` — MLflow's numeric filter
        keeps every key, JSONL keeps the record verbatim."""
        with self._lock:
            items = list(self._metrics.items())
        out: dict[str, float] = {}
        for name, m in items:
            if isinstance(m, (Counter, Gauge)):
                out[name] = m.value
            else:
                for k, v in m.snapshot().items():
                    out[f"{name}.{k}"] = v
        return out

    def flush_to(self, sink, *, step: int | None = None) -> dict[str, float]:
        snap = self.snapshot()
        if snap and sink is not None:
            sink.log(snap, step=step)
        return snap


# ---------------------------------------------------------------------------
# Process-wide state
# ---------------------------------------------------------------------------

class _ObsState:
    def __init__(self):
        self.registry = Registry()
        self.sink = None          # MetricsSink or None (None = disabled)
        self.role: str | None = None
        self.tl = threading.local()
        # attached FlightRecorder (utils/flight.py) or None: span closes,
        # registry flushes, and anomaly triggers mirror into its bounded
        # event ring. Held HERE (not imported) so obs stays import-light
        # and flight -> obs stays the only dependency direction.
        self.flight = None
        # attached device-observatory flush hook (utils/devprof.py) or
        # None: flush() mirrors the per-program device registry into the
        # same sink. Same held-not-imported rule as flight.
        self.devprof = None


_STATE = _ObsState()


def configure(sink, *, role: str | None = None) -> Registry:
    """Bind the process's span/metric emitter to ``sink`` (a MetricsSink).
    Called once per role boot (neurons/common.build); re-configuring
    replaces the sink/role and keeps the registry."""
    _STATE.sink = sink
    _STATE.role = role
    return _STATE.registry


def enabled() -> bool:
    return _STATE.sink is not None


def registry() -> Registry:
    return _STATE.registry


def current_sink():
    """The configured MetricsSink (or None) — the flight recorder logs
    frozen postmortem bundles through the same stream the spans ride."""
    return _STATE.sink


def attach_flight(recorder) -> None:
    """Attach (or detach, with None) a flight recorder (utils/flight.py):
    span closes, registry flushes, and anomaly triggers then mirror into
    its event ring. reset() drops the attachment with the rest of the
    process-wide state."""
    _STATE.flight = recorder


def attach_devprof(hook) -> None:
    """Attach (or detach, with None) the device observatory's flush hook
    (utils/devprof.on_flush): every flush() then mirrors the per-program
    device registry through the same sink as a ``{"devprof": ...}``
    record. devprof.enable() attaches itself; reset() drops it."""
    _STATE.devprof = hook


def reset() -> None:
    """Drop ALL global observability state (sink, role, registry, span
    stacks). Role entry points call this on exit so sequential in-process
    role runs (scripts/e2e_round.py, tests) never bleed metrics into each
    other; the tests/conftest.py guard asserts every test module leaves
    this state clean."""
    global _STATE
    _STATE = _ObsState()


def dirty() -> bool:
    """True when a sink is configured or the registry holds metrics —
    what the conftest hygiene guard checks after each test module."""
    return _STATE.sink is not None or len(_STATE.registry) > 0


def count(name: str, n: float = 1.0) -> None:
    """Increment a registry counter — single-branch no-op when disabled,
    so hot paths may call this unconditionally."""
    if _STATE.sink is None:
        return
    _STATE.registry.counter(name).inc(n)


def observe(name: str, value: float) -> None:
    """Record into a registry histogram — no-op when disabled."""
    if _STATE.sink is None:
        return
    _STATE.registry.histogram(name).observe(value)


def gauge(name: str, value: float) -> None:
    """Set a registry gauge — no-op when disabled."""
    if _STATE.sink is None:
        return
    _STATE.registry.gauge(name).set(value)


def registry_digest() -> str:
    return _STATE.registry.digest()


def flush(sink=None, *, step: int | None = None) -> dict[str, float]:
    """Snapshot the registry through ``sink`` (default: the configured
    one). The periodic-flush primitive each role calls at its natural
    cadence. Flush records carry an ``obs_registry`` role marker so
    offline joins (scripts/fleet_report.py) can attribute a snapshot to
    its emitting role without relying on file names."""
    if sink is None:
        sink = _STATE.sink
    if sink is None:
        return {}
    snap = _STATE.registry.snapshot()
    if snap:
        sink.log({"obs_registry": _STATE.role or "unknown", **snap},
                 step=step)
    fl = _STATE.flight
    if fl is not None:
        try:
            fl.on_flush(snap)
        except Exception:
            logger.exception("flight flush hook failed")
    dp = _STATE.devprof
    if dp is not None:
        try:
            dp(sink, _STATE.role)
        except Exception:
            logger.exception("devprof flush hook failed")
    return snap


# ---------------------------------------------------------------------------
# Correlation ids
# ---------------------------------------------------------------------------

def new_delta_id(miner_id: str, seq: int) -> str:
    """Deterministic per-push correlation id. Greppable, sortable, and
    collision-free per miner per process run; the push SEQUENCE (not a
    content hash) so superseded pushes stay distinguishable."""
    return f"{miner_id}-{seq:06d}"


def _tl():
    tl = _STATE.tl
    if not hasattr(tl, "stack"):
        tl.stack = []
        tl.cid = None
    return tl


def current_cid() -> str | None:
    return getattr(_STATE.tl, "cid", None)


@contextlib.contextmanager
def correlate(cid: str | None):
    """Set the CURRENT thread's correlation id for the duration — spans
    opened inside inherit it. The publish worker re-enters its job's id
    through this (thread-local state does not cross threads)."""
    tl = _tl()
    prev = tl.cid
    tl.cid = cid
    try:
        yield
    finally:
        tl.cid = prev


def capture_context() -> tuple:
    """Snapshot THIS thread's span context (open-span stack + current
    correlation id) for hand-off to a worker thread. Span state is
    thread-local by design (the publish worker re-enters its id via
    ``correlate``); a worker POOL that fans one caller's work across
    threads instead captures the submitting thread's context here and
    installs it per job via ``use_context`` — concurrent ``avg.fetch``
    spans then keep their parent nesting and inherited cid exactly as if
    they had run inline (engine/ingest.py's pool does this)."""
    tl = _tl()
    return (tuple(tl.stack), tl.cid)


@contextlib.contextmanager
def use_context(ctx: tuple | None):
    """Install a ``capture_context()`` snapshot on the CURRENT thread for
    the duration. The worker gets a private COPY of the captured stack:
    its spans nest under the submitter's open span without mutating the
    submitter's own (still live) stack across threads."""
    tl = _tl()
    prev_stack, prev_cid = tl.stack, tl.cid
    tl.stack = list(ctx[0]) if ctx else []
    tl.cid = ctx[1] if ctx else None
    try:
        yield
    finally:
        tl.stack, tl.cid = prev_stack, prev_cid


def rider_delta_id(meta: dict | None) -> str | None:
    """Defensive read of ``delta_id`` from a PEER-CONTROLLED meta rider:
    a short string or nothing (a hostile rider must not be able to
    inject junk into span records or report joins)."""
    if not isinstance(meta, dict):
        return None
    v = meta.get("delta_id")
    if isinstance(v, str) and 0 < len(v) <= _CID_MAX_LEN:
        return v
    return None


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1024)
def _phase_hist(name: str, hist: str | None) -> str:
    """The histogram a phase feeds. Both names are linted here, once per
    (name, hist) pair, not at every entry of a hot loop."""
    check_metric_name(name)
    return check_metric_name(hist or f"{name}_ms")


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at the first enabled
    phase; None in a process without jax (the histogram still fills)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def _annotation(name: str, args: dict):
    """The host span ``phase`` and ``span`` both open while a sink is on:
    a ``TraceAnnotation(name, **args)``, or None without jax."""
    annot = _trace_annotation()
    return None if annot is None else annot(name, **args)


class _NoPhase:
    """What ``phase`` returns while obs is off: one object for every call
    site, so a disabled phase is one branch and no allocation."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_PHASE = _NoPhase()


class _Phase:
    __slots__ = ("_hist", "_annot", "_t0", "dur_ms")

    def __init__(self, registry: Registry | None, name: str,
                 hist: str | None, args: dict):
        if registry is None:  # obs off, ``timed``: the clock pair alone
            self._hist = self._annot = None
            return
        # the histogram of the registry that was live at entry: a phase
        # that straddles reset() must not dirty the fresh state
        self._hist = registry.histogram(_phase_hist(name, hist))
        self._annot = _annotation(name, args)

    def __enter__(self) -> "_Phase":
        if self._annot is not None:
            self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ms = (time.perf_counter() - self._t0) * 1e3
        if self._annot is not None:
            self._annot.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.dur_ms)
        return False


def phase(name: str, hist: str | None = None, *, timed: bool = False,
          **args):
    """Time one phase of a hot loop (``with obs.phase("serve.admit"):``).

    Enabled, the phase is a ``jax.profiler.TraceAnnotation(name, **args)``
    — a host span on the profiler's own clock, so a device trace can put
    an idle gap down to it; start, end and nesting are kept per thread by
    the profiler, not here — and one observation of the histogram
    ``hist`` (default ``<name>_ms``). ``args`` are what ties the span to a
    request or a shape (a request id, a bucket): pass values the site
    already holds, they are formatted only inside a running profiler.
    Nothing reaches the sink per close: the registry holds the histogram
    and ``flush()`` writes it at the role's cadence. Disabled (no sink),
    every call returns the same no-op object; a site that needs the time
    with obs off too (``with obs.phase(..., timed=True) as ph`` and then
    ``ph.dur_ms``) gets the clock pair alone, so no site times one
    interval twice. For a round-scale phase that needs a record, a
    correlation id and the flight mirror, use ``span``."""
    st = _STATE
    if st.sink is None:
        return _Phase(None, name, hist, args) if timed else _NO_PHASE
    return _Phase(st.registry, name, hist, args)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def span(name: str, *, cid: str | None = None, **attrs):
    """Time a phase; on exit emit one record through the configured sink
    and feed the ``span.<name>_ms`` histogram. Nesting is tracked per
    thread (records carry ``parent`` and ``depth``). Zero-cost no-op when
    no sink is configured. ``attrs`` ride verbatim in the record (keep
    them JSON-able and small). While it is open the span is also the
    host span a ``phase`` is (``TraceAnnotation(name, cid=...)`` on the
    opening thread's line), so a device trace shows ``push.snapshot`` on
    the loop's thread and ``push.upload`` on the publisher's."""
    st = _STATE
    if st.sink is None:
        yield
        return
    check_metric_name(name)
    tl = _tl()
    parent = tl.stack[-1] if tl.stack else None
    prev_cid = tl.cid
    if cid is not None:
        tl.cid = cid
    tl.stack.append(name)
    annot = _annotation(name, {} if tl.cid is None else {"cid": tl.cid})
    if annot is not None:
        annot.__enter__()
    t0_wall = time.time()
    t0 = time.perf_counter()
    ok = True
    try:
        yield
    except BaseException:
        ok = False
        raise
    finally:
        dur_ms = (time.perf_counter() - t0) * 1e3
        if annot is not None:
            annot.__exit__(None, None, None)
        tl.stack.pop()
        ccid = tl.cid
        tl.cid = prev_cid
        st.registry.histogram(f"span.{name}_ms").observe(dur_ms)
        rec = {"span": name, "dur_ms": round(dur_ms, 3), "t0": t0_wall,
               "depth": len(tl.stack)}
        if st.role is not None:
            rec["role"] = st.role
        if parent is not None:
            rec["parent"] = parent
        if ccid is not None:
            rec["cid"] = ccid
        if not ok:
            rec["error"] = True
        rec.update(attrs)
        try:
            st.sink.log(rec)
        except Exception:  # a broken sink must never break the traced phase
            logger.exception("span sink emit failed")
        fl = st.flight
        if fl is not None:
            try:
                fl.on_span(name, dur_ms, ccid, ok)
            except Exception:  # forensics must degrade, never break a phase
                logger.exception("flight span hook failed")


# ---------------------------------------------------------------------------
# Anomaly-triggered profiler capture
# ---------------------------------------------------------------------------

class AnomalyMonitor:
    """Arms a one-shot TraceCapture (utils/metrics.py) on the FIRST of:

    - loss spike: loss exceeds ``loss_spike_factor`` x its EMA (after
      ``loss_warmup`` observations), or goes non-finite;
    - push failure streak: ``push_failure_streak`` consecutive failed
      pushes with no success in between;
    - step-time p99 blowout: the recent-step p99 exceeds
      ``step_p99_factor`` x p50 (after ``step_warmup`` steps; checked
      every ``check_every`` observations so the per-step cost is one
      deque append).

    Exactly ONE arming per monitor lifetime, whatever fires afterwards —
    a capture window is expensive evidence, and the first anomaly is the
    one worth profiling. ``capture`` may be None (detection + counters
    only). The miner loop feeds observations and forwards ``tick()``."""

    def __init__(self, capture=None, *, loss_spike_factor: float = 2.0,
                 loss_warmup: int = 8, push_failure_streak: int = 3,
                 step_p99_factor: float = 8.0, step_warmup: int = 64,
                 check_every: int = 32, step_capacity: int = 256):
        if loss_spike_factor <= 1.0 or step_p99_factor <= 1.0:
            raise ValueError("anomaly factors must be > 1.0")
        if push_failure_streak < 1:
            raise ValueError("push_failure_streak must be >= 1")
        self.capture = capture
        self.loss_spike_factor = loss_spike_factor
        self.loss_warmup = loss_warmup
        self.push_failure_streak = push_failure_streak
        self.step_p99_factor = step_p99_factor
        self.step_warmup = step_warmup
        self.check_every = check_every
        self.triggered: str | None = None
        self._loss_ema: float | None = None
        self._loss_seen = 0
        self._fail_streak = 0
        self._last_pushes = 0
        self._last_failed = 0
        self._steps = Histogram("anomaly.step_ms", capacity=step_capacity)

    # -- observations -------------------------------------------------------
    def observe_loss(self, loss: float) -> None:
        loss = float(loss)
        if not math.isfinite(loss):
            self._trigger("loss_nonfinite", value=loss)
            return
        self._loss_seen += 1
        if self._loss_ema is None:
            self._loss_ema = loss
            return
        if (self._loss_seen > self.loss_warmup and self._loss_ema > 0
                and loss > self.loss_spike_factor * self._loss_ema):
            self._trigger("loss_spike", value=loss, ema=self._loss_ema)
        self._loss_ema += 0.1 * (loss - self._loss_ema)

    def observe_step_ms(self, ms: float) -> None:
        self._steps.observe(ms)
        n = self._steps.count
        if n < self.step_warmup or n % self.check_every:
            return
        p = self._steps.percentiles((50.0, 99.0))
        if p["p50"] > 0 and p["p99"] > self.step_p99_factor * p["p50"]:
            self._trigger("step_time_p99", p50=p["p50"], p99=p["p99"])

    def observe_push_counters(self, pushes: int, failed: int) -> None:
        """Feed the loop's cumulative MinerReport counters; deltas since
        the last call drive the streak (a success resets it)."""
        d_push = pushes - self._last_pushes
        d_fail = failed - self._last_failed
        self._last_pushes, self._last_failed = pushes, failed
        if d_push > 0:
            self._fail_streak = 0
        if d_fail > 0:
            self._fail_streak += d_fail
            if self._fail_streak >= self.push_failure_streak:
                self._trigger("push_failure_streak",
                              streak=self._fail_streak)

    def trigger_external(self, reason: str, **details) -> None:
        """Arm on an externally-detected anomaly — the fleet health
        plane's SLO breaches (engine/health.py) route through here so a
        stale miner or a fleet-wide loss divergence arms the SAME
        one-shot capture budget as the local detectors (first anomaly of
        any origin wins, forever)."""
        self._trigger(check_metric_name(reason), **details)

    # -- capture plumbing ---------------------------------------------------
    def tick(self) -> None:
        """Forward one step tick to the (possibly armed) capture."""
        if self.capture is not None:
            self.capture.tick()

    def close(self) -> None:
        if self.capture is not None:
            self.capture.close()

    def _trigger(self, reason: str, **details) -> None:
        if self.triggered is not None:
            return  # one-shot: first anomaly wins, forever
        self.triggered = reason
        count(f"obs.anomaly.{reason}")
        logger.warning("anomaly detected (%s%s)%s", reason,
                       "".join(f" {k}={v:.4g}" if isinstance(v, float)
                               else f" {k}={v}"
                               for k, v in details.items()),
                       "" if self.capture is None
                       else " — arming one-shot profiler capture")
        if _STATE.sink is not None:
            try:
                _STATE.sink.log({"anomaly": reason, **details})
            except Exception:
                logger.exception("anomaly sink emit failed")
        fl = _STATE.flight
        if fl is not None:
            try:
                fl.record("anomaly", reason=reason,
                          armed=self.capture is not None)
            except Exception:
                logger.exception("flight anomaly hook failed")
        if self.capture is not None:
            self.capture.arm()
