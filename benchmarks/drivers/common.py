"""What every driver shares: the run's context, the device gate, the compile
listener, host spans on the profiler's clock, and the traced slice."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(ROOT, ".bench_work")     # listed in .gitignore
MOSAIC_CALL = "tpu_custom_call"


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def require_device(chips: int) -> dict:
    """The one backend touch that decides: a TPU with the cell's chips, or
    exit 2 with the device named. There is no other platform anywhere."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"bench: device platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} (cell asks {chips})", flush=True)
    if info["platform"] != "tpu" or info["count"] < chips:
        raise SystemExit(
            f"bench: FAIL: the cell needs {chips} TPU chip(s); jax.devices() "
            f"gives {info['count']} x {info['platform']}:{info['kind']}")
    info["count"] = chips
    return info


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise SystemExit(f"bench: FAIL: device_kind {kind!r} is not in "
                         f"benchmarks/peaks.json; add it with its source")
    return table[kind]


class CompileListener:
    """Backend compiles as JAX's own monitoring reports them: seconds
    (cache retrievals included), how many, and persistent-cache hits and
    misses. `mark()` starts the count that a timed window must keep at 0."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0
        self._mark = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.events += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> None:
        self._mark = self.events

    def since_mark(self) -> int:
        return self.events - self._mark


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit."""
    name: str
    value: float
    limit: float
    ok: bool
    note: str = ""

    def line(self) -> str:
        return (f"bench: check {self.name}: {self.value!r} (limit "
                f"{self.limit!r}) {'ok' if self.ok else 'FAIL'}"
                + (f"  [{self.note}]" if self.note else ""))


def check_le(name: str, value: float, limit: float, note: str = "") -> Check:
    value = float(value)
    return Check(name, value, float(limit), value <= limit, note)


@dataclasses.dataclass
class Run:
    """What a driver hands back."""
    setup_s: float
    end_to_end: dict            # name -> value, everything the driver times
    attempted: int
    failed: int
    checks: list                # [Check]
    stats: dict                 # host-side numbers for the readers
    memory_peak_bytes: int
    window_s: float
    trace_dir: str | None = None


@dataclasses.dataclass
class Ctx:
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float            # perf_counter at process start
    compiles: CompileListener
    device: dict

    def model_cfg(self) -> dict:
        """The sizes the reference needs, from the configuration file."""
        c, a = self.config, self.config.get("assumed", {})
        return {"n_embd": c["n_embd"], "n_layer": c["n_layer"],
                "n_head": c["n_head"], "vocab_size": c["vocab_size"],
                "n_positions": c["n_positions"],
                "layer_norm_epsilon": c["layer_norm_epsilon"],
                "padded_vocab": a.get("padded_vocab", c["vocab_size"])}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def free_device_memory() -> None:
    """After the caller dropped its references: collect, so that the
    reference finds the chip empty."""
    gc.collect()


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


class NullSink:
    """Turns the program's obs registry on without writing anywhere."""

    def log(self, *_a, **_k):
        pass

    def close(self):
        pass


def obs_snapshot(obs) -> dict:
    """{histogram name: {count, sum, ring}} of the program's registry."""
    reg = obs.registry()
    out = {}
    for name in reg.names():
        h = reg.peek(name)
        if hasattr(h, "percentiles"):
            out[name] = {"count": h.count, "sum": h.total,
                         **h.percentiles((50.0, 95.0))}
    return out


# ---------------------------------------------------------------------------
# host spans and the traced slice
# ---------------------------------------------------------------------------

class Spans:
    """`with spans("bench.engine_step"):` puts a host span into the
    profiler's own trace while a slice is being traced, and costs one
    branch otherwise."""

    def __init__(self):
        self.on = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield


class TraceSlice:
    """Traces the last part of the window: its last quarter, at most
    `max_s` seconds. `poll(t)` is called by the driver's loop with the time
    since the window opened and starts the profiler when the slice is due;
    the driver calls `stop()` once the window has closed, so that writing
    the trace out (seconds) is not inside it. With `--trace 0` both do
    nothing. The profiler's Python tracer is off: it would hook every
    Python call of the host loop that is being measured."""

    WINDOW_SPAN = "bench.trace_window"

    def __init__(self, ctx: Ctx, spans: Spans, max_s: float = 4.0):
        self.enabled = ctx.trace
        self.spans = spans
        self.start_at = ctx.seconds - min(max_s, ctx.seconds * 0.25)
        self.dir = os.path.join(WORK_DIR, "trace", ctx.cell["name"])
        self.state = "before" if self.enabled else "done"
        self._annot = None
        self.overhead_s = 0.0       # spent starting the profiler

    def poll(self, t: float) -> None:
        if self.state != "before" or t < self.start_at:
            return
        import jax
        t_in = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.spans.on = True
        self._annot = jax.profiler.TraceAnnotation(self.WINDOW_SPAN)
        self._annot.__enter__()
        self.state = "on"
        self.overhead_s = time.perf_counter() - t_in

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax
        self._annot.__exit__(None, None, None)
        self.spans.on = False
        jax.profiler.stop_trace()
        self.state = "stopped"

    def result_dir(self) -> str | None:
        return self.dir if self.state == "stopped" else None
