"""Device performance observatory: XLA cost attribution, per-program
execution histograms, and a hardware roofline model.

The fleet is thoroughly observed (spans, heartbeats, flight bundles,
fleetsim scorecards) but the *device* was a black box: ``compile.ms``
and wall-clock said how long things took, never where a step's FLOPs
and bytes actually went. This module is the one home of per-program
device accounting — every hot path registers its cached jitted
programs here:

- :func:`wrap` wraps a jitted callable under a **closed program
  vocabulary** (:data:`PROGRAMS`, the same producer-side lint
  discipline as ``flight.EVENT_KINDS``: an unknown name raises at the
  producer, so a new hot path cannot ship unobserved under an ad-hoc
  name). Per (program, bucket) the observatory records:

  * lowered ``cost_analysis()`` FLOPs / bytes-accessed, probed once on
    the first dispatch (skip-not-fail: backends without a cost model
    leave the fields None, everything else keeps working);
  * compile time — first-dispatch wall, the same convention as the
    shared ``compile.ms`` histogram (trace + compile + one dispatch);
  * an execution-time histogram. On CPU the wrapper BLOCKS on the
    result (``jax.block_until_ready``) so the histogram is real device
    time; on TPU it never blocks — the dispatch runs under a
    ``jax.profiler.TraceAnnotation("dt.<prog>[<bucket>]")`` so an
    on-demand device trace (``flight.capture_profile``, the
    ``/debug/profile`` endpoint) attributes device time to the same
    names this registry reports, and the histogram records host
    dispatch time (still the pipeline-stall truth the host sees).

- :func:`track` is the host-phase sibling for hot paths that are NOT
  device programs (the packed-wire densify): same records, no cost
  probe, ``host: true`` in exports.

- a **roofline model** (:data:`ROOFLINES`): a small per-chip peak
  bf16 FLOP/s + HBM bandwidth table keyed on
  ``jax.devices()[0].device_kind`` with an explicit unknown fallback,
  yielding achieved-fraction and arithmetic-intensity gauges per
  program — the quantity TPU systems papers reason with across
  hardware generations, and the yardstick the Pallas-kernel PR will
  be judged against.

Everything is off until :func:`enable` runs (the utils/obs.py
contract): a wrapped program costs ONE module-flag branch when
disabled; the enabled cost is not measured on the chip, and
tests/test_planes.py holds that it attributes every dispatch of the
train step and changes no result. Exposure: ``obs.flush`` mirrors :func:`snapshot`
into the role's JSONL sink as a ``{"devprof": ...}`` record
(scripts/perf_report.py joins those into the where-the-time-goes
table), utils/obs_http.py renders :func:`prom_lines`
(``dt_prog_*{prog,bucket}`` + ``dt_compile_ms{prog,bucket}``), and
:func:`anatomy` derives the step-time anatomy fields heartbeats and
fleet_report carry (host-blocked vs device vs data-wait).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable

from . import obs

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Closed program vocabulary (the flight.EVENT_KINDS discipline)
# ---------------------------------------------------------------------------

# name -> description. wrap()/track() REJECT names outside this table, so
# every observed device program is registered here first — the tier-1
# lint test (tests/test_devprof.py) additionally asserts every jax.jit
# site in the five hot-path modules is wrapped or explicitly exempted.
PROGRAMS: dict[str, str] = {
    "train.step": "miner fwd+bwd+optimizer train step (engine/train.py)",
    "train.eval": "token-weighted eval step (engine/train.py)",
    "push.snapshot": "delta snapshot / wire-v2 pack program "
                     "(engine/train.py)",
    "eval.cohort": "bucketed K-candidate cohort eval "
                   "(engine/batched_eval.py)",
    "eval.stack": "cohort stack+pad assembly (engine/batched_eval.py)",
    "eval.pad": "stacked-cohort pad-up (engine/batched_eval.py)",
    "merge.sharded": "cached shard_map cohort merge "
                     "(parallel/collectives.py)",
    "delta.finite": "fused tree-finiteness guard (delta.py)",
    "delta.merge": "stacked weighted merge (delta.py)",
    "delta.screen": "fused dense cohort screen (delta.py)",
    "delta.screen_packed": "fused packed-wire cohort screen (delta.py)",
    "delta.accumulate": "scatter-add delta accumulation (delta.py)",
    "delta.dequant_scatter": "fused dequant->scatter-add packed "
                             "accumulate via the Pallas kernel "
                             "(delta.py / ops/dequant_scatter.py)",
    "delta.densify": "host densify of packed wire entries (delta.py)",
    "serve.prefill": "per-T-bucket prefill program (engine/serve.py)",
    "serve.decode": "per-(slot,page)-bucket decode step "
                    "(engine/serve.py)",
    "serve.decode_sample": "sampled (temperature/top-p, seeded PRNG) "
                           "twin of serve.decode — same forward, "
                           "scatter, and (slot,page) buckets "
                           "(engine/serve.py)",
    "serve.prefill_ctx": "suffix prefill over shared prefix-cache "
                         "pages, per (T,page)-bucket (engine/serve.py)",
    "serve.sample_tok": "single-row seeded sampler for the first "
                        "token after prefill (engine/serve.py)",
    "serve.page_copy": "whole-page KV copy — the copy-on-write "
                       "primitive behind prefix sharing "
                       "(engine/serve.py)",
    "serve.state_restore": "prefix-cache hit of a family with per-slot "
                           "state: one snapshot row copied over the "
                           "admitted slot's row (engine/serve.py)",
    "serve.state_snapshot": "registration of such a family's prompt: "
                            "the slot's row copied over a snapshot row "
                            "(engine/serve.py)",
    "serve.kv_adopt": "adopted-KV page write on a decode worker — "
                      "scatter one fetched [L,P,Hkv,D] page pair into "
                      "the pool (disaggregated serving; "
                      "engine/kv_transfer.py)",
    "serve.draft": "draft-model propose step / context prefill over "
                   "the drafter's own paged KV pool "
                   "(engine/speculative.py)",
    "serve.verify": "speculative K+1-position batched verify pass — "
                    "the multi-token twin of serve.decode on the same "
                    "(slot,page) buckets (engine/serve.py)",
}


# ---------------------------------------------------------------------------
# Roofline table
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Roofline:
    """Per-DEVICE peaks (what one ``jax.devices()`` entry can do):
    dense bf16 FLOP/s and HBM bytes/s, from public spec sheets.
    ``known=False`` is the explicit unknown-chip fallback — achieved
    fractions are then omitted, never fabricated."""
    device_kind: str
    peak_flops: float | None
    hbm_bytes_per_s: float | None
    known: bool = True

    @property
    def ridge_intensity(self) -> float | None:
        """FLOPs/byte at the compute/memory-bound ridge point."""
        if not self.peak_flops or not self.hbm_bytes_per_s:
            return None
        return self.peak_flops / self.hbm_bytes_per_s


# substring of the (lowercased) device_kind -> (peak bf16 FLOP/s,
# HBM bytes/s) PER JAX DEVICE. v2/v3 expose one device per CORE (half a
# chip); v4 onward are megacore (one device per chip). The e-generations
# report themselves as "v5 lite"/"v6 lite"; the ladder checks most
# specific first so "v5p" never matches a bare "v5" entry.
_ROOFLINE_LADDER: tuple[tuple[tuple[str, ...], float, float], ...] = (
    (("v6e", "v6 lite"), 918e12, 1640e9),
    (("v5p",), 459e12, 2765e9),
    (("v5e", "v5 lite"), 197e12, 819e9),
    (("v4",), 275e12, 1228e9),
    (("v3",), 61.5e12, 450e9),
    (("v2",), 22.5e12, 350e9),
)

# exported for docs/tests: device-kind spellings the ladder recognizes
ROOFLINES: dict[str, Roofline] = {
    keys[0]: Roofline(keys[0], fl, bw)
    for keys, fl, bw in _ROOFLINE_LADDER
}


def roofline_for(device_kind: str) -> Roofline:
    """Roofline for a device-kind string; unknown chips (and CPU hosts)
    get the explicit ``known=False`` fallback."""
    text = (device_kind or "").lower()
    for keys, fl, bw in _ROOFLINE_LADDER:
        if any(k in text for k in keys):
            return Roofline(device_kind, fl, bw)
    return Roofline(device_kind or "unknown", None, None, known=False)


def current_roofline() -> Roofline:
    """Roofline of this process's first device (cached per enable)."""
    st = _STATE
    if st.roofline is None:
        try:
            import jax
            st.roofline = roofline_for(jax.devices()[0].device_kind)
        except Exception:  # backend init failure degrades, never raises
            st.roofline = Roofline("unknown", None, None, known=False)
    return st.roofline


def cost_analysis_available() -> bool:
    """Probe whether this backend's lowered programs expose a cost model
    with flops/bytes (the CPU backend does; exotic plugins may not).
    Used by tests to skip-not-fail attribution assertions."""
    try:
        import jax
        import jax.numpy as jnp
        ca = jax.jit(lambda x: x * 2.0).lower(
            jnp.ones((4,), jnp.float32)).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        return isinstance(ca, dict) and "flops" in ca
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Per-program records
# ---------------------------------------------------------------------------

class ProgramStats:
    """One (program, bucket) cell of the registry."""

    __slots__ = ("prog", "bucket", "host", "calls", "compile_ms",
                 "flops", "bytes_accessed", "cost_probed", "exec_ms")

    def __init__(self, prog: str, bucket: str, *, host: bool = False):
        self.prog = prog
        self.bucket = bucket
        self.host = host
        self.calls = 0
        self.compile_ms: float | None = None
        self.flops: float | None = None
        self.bytes_accessed: float | None = None
        self.cost_probed = False
        self.exec_ms = obs.Histogram("devprof.exec_ms", capacity=512)

    # -- derived gauges ------------------------------------------------------
    def arithmetic_intensity(self) -> float | None:
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def achieved(self, roofline: Roofline) -> tuple[float | None,
                                                    float | None]:
        """(achieved peak-FLOPs fraction, achieved HBM-bandwidth
        fraction) at the exec p50 — None wherever the cost model or the
        roofline has no number (unknown chip, host phase)."""
        if self.host or not self.exec_ms.count:
            return None, None
        p50_s = self.exec_ms.percentiles((50.0,))["p50"] / 1e3
        if not p50_s or p50_s <= 0:
            return None, None
        ff = bf = None
        if self.flops and roofline.peak_flops:
            ff = (self.flops / p50_s) / roofline.peak_flops
        if self.bytes_accessed and roofline.hbm_bytes_per_s:
            bf = (self.bytes_accessed / p50_s) / roofline.hbm_bytes_per_s
        return ff, bf

    def as_record(self, roofline: Roofline) -> dict:
        ff, bf = self.achieved(roofline)
        rec: dict[str, Any] = {
            "prog": self.prog, "bucket": self.bucket, "calls": self.calls,
            "host": self.host, "compile_ms": self.compile_ms,
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "exec_ms": self.exec_ms.snapshot(),
        }
        ai = self.arithmetic_intensity()
        if ai is not None:
            rec["arith_intensity"] = round(ai, 4)
        if ff is not None:
            rec["achieved_flops_frac"] = round(ff, 6)
        if bf is not None:
            rec["achieved_bw_frac"] = round(bf, 6)
        return rec


class _DevprofState:
    def __init__(self, *, max_programs: int = 64):
        self.lock = threading.Lock()
        self.records: dict[tuple[str, str], ProgramStats] = {}
        self.max_programs = max_programs
        self.dropped = 0
        self.roofline: Roofline | None = None
        # resolved lazily on the first observed call (enable() must not
        # force backend init inside a role that probes the backend with
        # its own discipline)
        self.block: bool | None = None
        self.annotate: bool | None = None
        self.probe_costs = True


_STATE = _DevprofState()
_ON = False


def enable(*, block: bool | None = None, annotate: bool | None = None,
           max_programs: int = 64, probe_costs: bool = True) -> None:
    """Turn the observatory on. ``block``/``annotate`` override the
    per-backend defaults (block on non-TPU so exec histograms are real
    device time; annotate on TPU so device traces carry program names);
    ``max_programs`` caps (program, bucket) cardinality — past it, new
    cells are dropped-and-counted, the obs ``Registry(max_names=)``
    discipline."""
    global _ON
    st = _STATE
    with st.lock:
        st.max_programs = max(1, int(max_programs))
        st.block = block
        st.annotate = annotate
        st.probe_costs = probe_costs
    _ON = True
    # flush mirroring rides the obs sink: every obs.flush() then logs a
    # {"devprof": ...} record next to the registry snapshot
    obs.attach_devprof(on_flush)


def disable() -> None:
    global _ON
    _ON = False
    obs.attach_devprof(None)


def enabled() -> bool:
    return _ON


def reset() -> None:
    """Drop ALL observatory state (records, roofline cache, the enabled
    flag) — the obs.reset()/flight.reset() teardown contract; the
    tests/conftest.py hygiene guard asserts every test module leaves
    this clean."""
    global _STATE, _ON
    _STATE = _DevprofState()
    _ON = False
    obs.attach_devprof(None)


def dirty() -> bool:
    return _ON or bool(_STATE.records) or _STATE.dropped > 0


def _resolve_backend() -> None:
    st = _STATE
    if st.block is not None and st.annotate is not None:
        return
    platform = "cpu"
    try:
        import jax
        platform = jax.default_backend()
    except Exception:
        pass
    if st.block is None:
        st.block = platform != "tpu"
    if st.annotate is None:
        st.annotate = platform == "tpu"


def _get_record(prog: str, bucket: str, *,
                host: bool = False) -> ProgramStats | None:
    st = _STATE
    key = (prog, bucket)
    with st.lock:
        rec = st.records.get(key)
        if rec is None:
            if len(st.records) >= st.max_programs:
                st.dropped += 1
                return None
            rec = st.records[key] = ProgramStats(prog, bucket, host=host)
        return rec


def _bucket_of(bucket, args, kwargs) -> str:
    if bucket is None:
        return "-"
    if callable(bucket):
        try:
            bucket = bucket(args, kwargs)
        except Exception:
            return "-"
    return str(bucket)


def _probe_cost(rec: ProgramStats, fn, args, kwargs) -> None:
    """One-time FLOPs/bytes probe: lower the jitted callable against the
    first call's (still-live — this runs BEFORE the dispatch that may
    donate them) arguments and read the XLA cost analysis. Lowering is
    abstract (shapes only) and happens once per (program, bucket);
    backends without a cost model just leave the fields None."""
    rec.cost_probed = True
    lower = getattr(fn, "lower", None)
    if lower is None:
        return
    try:
        ca = lower(*args, **kwargs).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            fl = ca.get("flops")
            by = ca.get("bytes accessed")
            if isinstance(fl, (int, float)) and fl >= 0:
                rec.flops = float(fl)
            if isinstance(by, (int, float)) and by >= 0:
                rec.bytes_accessed = float(by)
    except Exception:
        logger.debug("devprof: cost probe failed for %s[%s]",
                     rec.prog, rec.bucket, exc_info=True)


def _observed_call(prog: str, bucket, fn, args, kwargs):
    _resolve_backend()
    rec = _get_record(prog, _bucket_of(bucket, args, kwargs))
    first = rec is not None and rec.calls == 0
    import jax
    # the one-time cost probe (an abstract trace+lower) runs INSIDE the
    # timed window: its wall time lands in compile_ms with the rest of
    # the first dispatch, so attributed time accounts for everything
    # the observatory itself adds to the step
    t0 = time.perf_counter()
    if first and _STATE.probe_costs:
        _probe_cost(rec, fn, args, kwargs)
    if _STATE.annotate:
        with jax.profiler.TraceAnnotation(
                f"dt.{prog}[{rec.bucket if rec else '-'}]"):
            out = fn(*args, **kwargs)
    else:
        out = fn(*args, **kwargs)
    if _STATE.block:
        try:
            jax.block_until_ready(out)
        except Exception:
            pass  # non-array outputs: dispatch time is the record
    dur_ms = (time.perf_counter() - t0) * 1e3
    if rec is not None:
        with _STATE.lock:
            rec.calls += 1
            if first:
                # first-dispatch wall = trace + compile (+ dispatch/exec),
                # the _timed_compile convention — and it stays OUT of the
                # exec histogram so percentiles describe the steady state
                rec.compile_ms = round(dur_ms, 3)
            else:
                rec.exec_ms.observe(dur_ms)
    return out


def wrap(name: str, fn: Callable, *, bucket=None) -> Callable:
    """Register a jitted program under ``name`` (closed vocabulary —
    unknown names raise, the producer-side lint). ``bucket`` labels the
    program's compiled-variant family: a static value, or a callable
    ``(args, kwargs) -> value`` evaluated per call (bucket ladders where
    one wrapped callable serves many compiled shapes). Returns a wrapper
    that is a single-branch pass-through until :func:`enable`."""
    if name not in PROGRAMS:
        raise ValueError(
            f"unknown devprof program {name!r}; register it in "
            f"devprof.PROGRAMS (closed vocabulary: {sorted(PROGRAMS)})")

    def wrapped(*args, **kwargs):
        if not _ON:
            return fn(*args, **kwargs)
        return _observed_call(name, bucket, fn, args, kwargs)

    wrapped.__wrapped__ = fn
    wrapped._devprof_name = name  # type: ignore[attr-defined]
    lower = getattr(fn, "lower", None)
    if lower is not None:
        # AOT/introspection users (scripts/scale_aot.py, HLO-pinning
        # tests) keep the jitted callable's lower() through the wrapper
        wrapped.lower = lower  # type: ignore[attr-defined]
    return wrapped


@contextmanager
def track(name: str, *, bucket=None):
    """Host-phase sibling of :func:`wrap` for hot paths that are not
    device programs (the packed-wire densify): records wall time into
    the same per-(program, bucket) histograms, no cost probe."""
    if name not in PROGRAMS:
        raise ValueError(
            f"unknown devprof program {name!r}; register it in "
            f"devprof.PROGRAMS (closed vocabulary: {sorted(PROGRAMS)})")
    if not _ON:
        yield
        return
    rec = _get_record(name, _bucket_of(bucket, (), {}), host=True)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if rec is not None:
            with _STATE.lock:
                rec.calls += 1
                rec.exec_ms.observe((time.perf_counter() - t0) * 1e3)


# ---------------------------------------------------------------------------
# Exposure
# ---------------------------------------------------------------------------

def records() -> list[ProgramStats]:
    with _STATE.lock:
        return list(_STATE.records.values())


def snapshot() -> dict:
    """JSON-able registry dump: per-program records + the roofline +
    cardinality accounting — the ``{"devprof": ...}`` record obs.flush
    mirrors into the role's JSONL sink and perf_report joins."""
    rl = current_roofline()
    recs = records()
    return {
        "roofline": {"device_kind": rl.device_kind,
                     "peak_flops": rl.peak_flops,
                     "hbm_bytes_per_s": rl.hbm_bytes_per_s,
                     "known": rl.known},
        "programs": sorted((r.as_record(rl) for r in recs),
                           key=lambda r: (r["prog"], r["bucket"])),
        "dropped_programs": _STATE.dropped,
    }


def on_flush(sink, role: str | None = None) -> None:
    """obs.flush hook: mirror the registry snapshot through the role's
    sink (one record per flush; perf_report keeps the last per role)."""
    if not _ON or sink is None or not _STATE.records:
        return
    sink.log({"devprof": snapshot(), "role": role or "unknown"})


# step histogram -> (device programs attributed to it, data-wait
# histogram): the step-time anatomy join. Sums are averages over the
# step count so the parts are additive (host-blocked = step - device).
_ANATOMY = (
    ("miner.step_ms", ("train.step",), "miner.data_wait_ms"),
    ("serve.step_ms", ("serve.decode", "serve.prefill"), None),
)


def anatomy() -> dict[str, float]:
    """Step-time anatomy fields (``anat.*``, heartbeat-lintable names):
    average step wall-clock, the device-program share attributed by this
    registry, the host-blocked remainder, and data wait. Empty when the
    observatory is off or no step histogram has samples."""
    if not _ON:
        return {}
    reg = obs.registry()
    for step_name, progs, wait_name in _ANATOMY:
        h = reg.peek(step_name)
        if h is None or not getattr(h, "count", 0):
            continue
        steps = h.count
        step_avg = h.total / steps
        device_total = sum(
            r.exec_ms.total + (r.compile_ms or 0.0)
            for r in records() if r.prog in progs and not r.host)
        device_avg = device_total / steps
        out = {
            "anat.step_ms": round(step_avg, 3),
            "anat.device_ms": round(device_avg, 3),
            "anat.host_ms": round(max(0.0, step_avg - device_avg), 3),
            "anat.device_frac": round(
                min(1.0, device_avg / step_avg) if step_avg > 0 else 0.0,
                4),
        }
        if wait_name is not None:
            w = reg.peek(wait_name)
            if w is not None and getattr(w, "count", 0):
                out["anat.data_wait_ms"] = round(w.total / w.count, 3)
        return out
    return {}


def _esc(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"') \
                 .replace("\n", r"\n")


def prom_lines() -> list[str]:
    """Prometheus exposition lines for utils/obs_http.py:
    ``dt_prog_*{prog,bucket}`` labeled series per registered program
    (calls, flops, bytes, exec-time quantiles, achieved fractions,
    arithmetic intensity) plus ``dt_compile_ms{prog,bucket}`` — the
    labeled per-program compile series riding next to the unlabeled
    ``compile.ms`` registry aggregate. Empty when disabled."""
    if not _ON:
        return []
    rl = current_roofline()
    recs = records()
    if not recs:
        return []
    lines: list[str] = []
    series: dict[str, list[str]] = {}

    def emit(pn: str, labels: str, v) -> None:
        series.setdefault(pn, []).append(f"{pn}{{{labels}}} {float(v)!r}")

    for r in sorted(recs, key=lambda r: (r.prog, r.bucket)):
        lab = f'prog="{_esc(r.prog)}",bucket="{_esc(r.bucket)}"'
        emit("dt_prog_calls", lab, r.calls)
        if r.compile_ms is not None:
            emit("dt_compile_ms", lab, r.compile_ms)
        if r.flops is not None:
            emit("dt_prog_flops", lab, r.flops)
        if r.bytes_accessed is not None:
            emit("dt_prog_bytes_accessed", lab, r.bytes_accessed)
        if r.exec_ms.count:
            ps = r.exec_ms.percentiles((50.0, 95.0, 99.0))
            for q, qv in (("0.5", ps["p50"]), ("0.95", ps["p95"]),
                          ("0.99", ps["p99"])):
                emit("dt_prog_exec_ms", lab + f',q="{q}"', qv)
        ai = r.arithmetic_intensity()
        if ai is not None:
            emit("dt_prog_arith_intensity", lab, ai)
        ff, bf = r.achieved(rl)
        if ff is not None:
            emit("dt_prog_achieved_flops_frac", lab, ff)
        if bf is not None:
            emit("dt_prog_achieved_bw_frac", lab, bf)
    for pn in sorted(series):
        lines.append(f"# TYPE {pn} gauge")
        lines.extend(series[pn])
    if _STATE.dropped:
        lines.append("# TYPE dt_prog_dropped gauge")
        lines.append(f"dt_prog_dropped {float(_STATE.dropped)!r}")
    return lines
