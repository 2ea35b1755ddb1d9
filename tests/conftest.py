"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip behavior (dp/fsdp/tp shardings, psum merges, ring attention) is
tested without TPU hardware by splitting the host CPU into 8 XLA devices.
Must run before any JAX backend initialization. The tier-1 command sets
``JAX_PLATFORMS=cpu``; the config update below makes a bare ``pytest
tests/`` land on the CPU too.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributedtraining_tpu.utils.platform import (  # noqa: E402
    enable_compile_cache, ensure_virtual_devices)

ensure_virtual_devices(8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The persistent compilation cache is the tier-1 budget lever: dozens of
# test modules compile IDENTICAL tiny-model programs, and the cache keys
# on the HLO, so every repeat compile across modules — and across runs —
# deserializes instead of re-lowering. Subprocess tests (the
# multi-OS-process round, supervise) reach the same directory through
# the same helper in their entry points.
enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_nondaemon_threads():
    """Every background worker this framework spawns — data prefetch,
    stage_cohorts staging, the miner publication pipeline, async
    checkpoint saves — must be a DAEMON thread that its owner drains via
    flush()/close(): a leaked non-daemon worker blocks interpreter
    shutdown (CI hangs at 100% green). This guard asserts no test module
    leaves a NEW non-daemon thread running; threads that predate the
    module (pytest/jax internals) are exempt, and joiners get a grace
    window."""
    import threading
    import time as _time

    before = {t.ident for t in threading.enumerate()}
    yield
    deadline = _time.monotonic() + 5.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t.is_alive() and not t.daemon
                  and t.ident not in before]
        if not leaked:
            return
        if _time.monotonic() > deadline:
            raise AssertionError(
                f"test module leaked non-daemon threads: {leaked}")
        _time.sleep(0.05)


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_ingest_pool():
    """Ingest-pool hygiene (the concurrent delta ingest, engine/ingest.py):
    its workers are DAEMON threads — invisible to the non-daemon guard
    above — named ``ingest-*`` and designed to idle out within ~2 s of
    their last job (or immediately on DeltaIngestor.close()). A worker
    still alive well past that means a wedged transport call or a pool
    whose owner never drained it; either way the module leaked live
    machinery into its successors. Daemon or not, fail the module."""
    import threading
    import time as _time

    yield
    deadline = _time.monotonic() + 6.0   # > IngestPool's 2 s idle timeout
    while True:
        leaked = [t for t in threading.enumerate()
                  if t.is_alive() and t.name.startswith("ingest-")]
        if not leaked:
            return
        if _time.monotonic() > deadline:
            raise AssertionError(
                f"test module left ingest pool threads alive: {leaked}; "
                "close() the DeltaIngestor (or its owning loop) in teardown")
        _time.sleep(0.05)


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_health_plane():
    """Fleet-health-plane hygiene (engine/health.py + utils/obs_http.py):
    a HeartbeatPublisher's timer thread (named ``heartbeat-*``) and an
    ObsHTTPExporter's listening socket are long-lived background
    machinery that their owners must close() — a leaked timer keeps
    publishing into whatever transport the next module builds, and a
    leaked socket holds the port (and a serve thread) for the rest of
    the process. Force-clean so one offender cannot cascade, then fail
    the module."""
    import threading
    import time as _time

    yield
    from distributedtraining_tpu.utils import obs_http

    live = obs_http.live_exporters()
    for exp in live:
        exp.close()
    deadline = _time.monotonic() + 6.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t.is_alive() and t.name.startswith("heartbeat-")]
        if not leaked:
            break
        if _time.monotonic() > deadline:
            raise AssertionError(
                f"test module left heartbeat publisher threads alive: "
                f"{leaked}; close() the HeartbeatPublisher (or the loop "
                "that owns it) in teardown")
        _time.sleep(0.05)
    assert not live, (
        f"test module left HTTP exporters serving: {live}; call "
        "ObsHTTPExporter.close() in teardown")


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_localfs_tmp():
    """Shard-publish hygiene (the wire-v2 shard container rides the
    localfs transport's publish_raw): every localfs artifact write —
    deltas, bases, SHARDS, manifests, ``__agg__.*`` partial aggregates —
    must follow the tmp + fsync + rename discipline, so a ``*.tmp`` file
    still present after a module means a publish path died between the
    two steps (torn-publish debris) or bypassed the atomic write
    altogether. A leaked tmp from a mid-publish kill is exactly the
    artifact a reader must never decode; fail the module that produced
    it — and name aggregate debris separately, because a torn aggregate
    poisons a whole SUBTREE's contribution, not one miner's. Scans
    every transport root this process constructed (localfs.live_roots)."""
    yield
    import glob as _glob

    from distributedtraining_tpu.transport import localfs

    leaked = []
    for root in localfs.live_roots():
        for sub in ("deltas", "base"):
            leaked += _glob.glob(os.path.join(root, sub, "*.tmp"))
    agg_leaked = [p for p in leaked
                  if os.path.basename(p).startswith("__agg__")]
    for path in leaked:   # force-clean so one offender cannot cascade
        try:
            os.unlink(path)
        except OSError:
            pass
    assert not agg_leaked, (
        f"test module leaked partially-published AGGREGATE artifacts: "
        f"{agg_leaked}; a sub-averager publish (engine/hier_average.py) "
        "died between tmp write and rename")
    assert not leaked, (
        f"test module leaked partially-published artifact temp files: "
        f"{leaked}; localfs writes must go through the atomic "
        "tmp+fsync+rename path (serialization.save_file / _write_atomic)")


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_subaverager_threads():
    """Hierarchy hygiene (engine/hier_average.py): a SubAverager owns an
    ingest pool (covered by the ingest guard above) AND a DeltaPublisher
    worker named ``publish-__agg__.*`` that blocks on its queue until
    close() — a leaked one keeps publishing aggregates into whatever
    transport the next module builds. Fail the module that left one
    alive; the owning test must call SubAverager.close() in teardown."""
    import threading
    import time as _time

    yield
    deadline = _time.monotonic() + 6.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t.is_alive() and (t.name.startswith("publish-__agg__")
                                       or t.name.startswith("subavg-"))]
        if not leaked:
            return
        if _time.monotonic() > deadline:
            raise AssertionError(
                f"test module left sub-averager threads alive: {leaked}; "
                "close() the SubAverager in teardown")
        _time.sleep(0.05)


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_serving_plane():
    """Serving-plane hygiene (engine/serve.py): a GenerationEngine may
    own a base-revision watcher thread (``serve-watch``), a ServeLoop
    scheduler thread (``serve-loop``), and a ServeHTTPFrontend listening
    socket (``serve-http-*`` thread) — same long-lived background
    machinery as the heartbeat/exporter pair, same rule: the owning test
    must close() them. A leaked watcher keeps fetching bases from
    whatever transport the next module builds; a leaked frontend holds
    the port AND a reference to a dead engine. Force-clean the sockets
    so one offender cannot cascade, then fail the module."""
    import threading
    import time as _time

    yield
    from distributedtraining_tpu.engine import serve as serve_mod

    live = serve_mod.live_frontends()
    for fe in live:
        fe.close()
    deadline = _time.monotonic() + 6.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t.is_alive() and (t.name.startswith("serve-watch")
                                       or t.name.startswith("serve-loop"))]
        if not leaked:
            break
        if _time.monotonic() > deadline:
            raise AssertionError(
                f"test module left serving threads alive: {leaked}; "
                "close() the GenerationEngine/ServeLoop (the engine "
                "closes its watcher) in teardown")
        _time.sleep(0.05)
    assert not live, (
        f"test module left generation frontends serving: {live}; call "
        "ServeHTTPFrontend.close() in teardown")


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_flight_state():
    """Postmortem-plane hygiene (utils/flight.py): a configured flight
    recorder is PROCESS-WIDE state (same rule as the obs guard below), a
    leaked crash hook rewrites sys.excepthook/threading.excepthook for
    every later module, and a /debug/profile session whose jax profiler
    is still running poisons every later capture in the process (the
    profiler is a process global). Debug-endpoint SOCKETS ride the
    exporter and are covered by the health-plane guard above. Force-clean
    so one offender cannot cascade, then fail the module."""
    yield
    from distributedtraining_tpu.utils import flight

    live = flight.live_profile_sessions()
    for sess in live:
        try:
            sess.stop()
        except Exception:
            pass
    was_dirty = flight.dirty()
    had_hooks = flight.hooks_installed()
    flight.reset()
    assert not live, (
        f"test module left a /debug/profile session running: {live}; "
        "flight.capture_profile must stop its own trace")
    assert not was_dirty, (
        "test module left a configured flight recorder behind; call "
        "flight.reset() in teardown")
    assert not had_hooks, (
        "test module left flight crash hooks installed "
        "(sys.excepthook/threading.excepthook/atexit); call "
        "flight.uninstall_crash_hooks() or flight.reset() in teardown")


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_fleetsim():
    """Fleet-simulator hygiene (engine/fleetsim.py): a FleetSim owns
    FleetMonitors (ingest pools + ledgers) for every validator and
    averager actor — process machinery the owning test must release via
    FleetSim.close() (fleetsim.simulate() does it for you). The
    simulator is deliberately thread-free (workers=1 pools run inline),
    so the check is the live-instance registry plus a sweep for any
    stray ``fleetsim-`` thread a future refactor might introduce.
    Force-clean so one offender cannot cascade, then fail the module."""
    import threading

    yield
    from distributedtraining_tpu.engine import fleetsim

    live = fleetsim.live_sims()
    for sim in live:
        sim.close()
    leaked_threads = [t for t in threading.enumerate()
                      if t.is_alive() and t.name.startswith("fleetsim")]
    assert not live, (
        f"test module left fleet simulators open: {live}; call "
        "FleetSim.close() (or use fleetsim.simulate()) in teardown")
    assert not leaked_threads, (
        f"test module left fleetsim threads alive: {leaked_threads}")


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_obs_state():
    """Observability hygiene (mirrors the thread-leak guard above): the
    span/metric layer (utils/obs.py) is PROCESS-WIDE state — a test that
    configures a sink or populates the global registry and walks away
    silently pollutes every later module's metrics, and a TraceCapture
    whose jax profiler is still running poisons every later capture in
    the process. Each test module must leave both clean (obs.reset(), and
    drained/closed captures); this guard asserts it and force-cleans so
    one offender cannot cascade."""
    yield
    from distributedtraining_tpu.utils import metrics as metrics_mod
    from distributedtraining_tpu.utils import obs

    live = metrics_mod.live_captures()
    for cap in live:
        cap.close()
    was_dirty = obs.dirty()
    leftover = obs.registry().names() if was_dirty else []
    obs.reset()
    # the device observatory (utils/devprof.py) is the same kind of
    # process-wide state: an enabled registry left behind would keep
    # wrapping every later module's hot paths with blocking timings
    from distributedtraining_tpu.utils import devprof
    devprof_dirty = devprof.dirty()
    devprof_left = ([f"{r.prog}[{r.bucket}]" for r in devprof.records()]
                    if devprof_dirty else [])
    devprof.reset()
    assert not live, f"test module left a running TraceCapture: {live}"
    assert not was_dirty, (
        "test module left global obs state behind (configured sink or "
        f"registry metrics {leftover}); call obs.reset() in teardown")
    assert not devprof_dirty, (
        "test module left the device observatory enabled or populated "
        f"(programs {devprof_left}); call devprof.reset() in teardown")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
