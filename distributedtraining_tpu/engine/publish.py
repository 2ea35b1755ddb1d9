"""Asynchronous miner publication pipeline.

The miner's push path used to stall the training loop for its entire
duration every ``send_interval``: a host sync for the NaN screen, a
device->host transfer of the full delta, msgpack serialization, a temp-file
write, and a blocking upload (the reference pays the same tax at its upload
cadence, training_manager.py:345-433). At TPU scale the standard lever is
to hide host/network I/O behind accelerator compute — this module is the
miner-side twin of the validator's fetch/eval pipeline
(engine/batched_eval.stage_cohorts).

Division of labor:

- the TRAINING thread runs ONE jitted snapshot program (delta + wire
  layout + compression + finite flag, non-donated outputs — built by
  MinerLoop) and hands the device arrays to a :class:`SupersedeQueue`;
  dispatch is asynchronous, so the step cadence never waits on transport
- the PUBLISHER worker does everything with host cost off-thread: the
  finite-flag fetch, device->host transfer, serialization,
  ``transport.publish_delta``, and the base-revision rider — with bounded
  jittered-backoff retries (transport/retry.py)
- a push still in flight when the next interval fires is SUPERSEDED,
  never queued behind: each artifact is the whole cumulative delta, so
  only the newest matters (the same replace-don't-accumulate rule as the
  wire formats themselves, delta.py)

Pod rule (multi-host SPMD): the snapshot program, the flag fetch, and the
host materialization of cross-process-sharded arrays are collectives or
synced decisions — they stay on the training thread at the loop barrier
(MinerLoop hands this queue an already-host tree); only the coordinator's
upload itself runs here. ``flush()`` drains in-flight work so shutdown and
e2e round semantics are unchanged from the sequential path.

The same worker machinery drives async checkpoint saves
(checkpoint.CheckpointStore.save_async).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from ..utils import flight, obs

logger = logging.getLogger(__name__)

Params = Any

_CLOSED = object()


class SupersedeQueue:
    """Bounded single-producer/single-consumer handoff where NEWEST wins.

    ``offer`` never blocks: when ``depth`` items are already pending, the
    OLDEST pending item is dropped (superseded). An item the consumer has
    already taken is never superseded — it completes. ``wait_drained``
    blocks until nothing is pending AND nothing is in flight (the flush
    primitive)."""

    def __init__(self, depth: int = 1):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._depth = depth
        self._items: deque = deque()
        self._cv = threading.Condition()
        self._in_flight = 0
        self._closed = False

    def offer(self, item) -> int:
        """Enqueue ``item``; returns how many pending items it superseded
        (0 or 1 at depth 1). No-op (returns 0) after close."""
        with self._cv:
            if self._closed:
                return 0
            dropped = 0
            while len(self._items) >= self._depth:
                self._items.popleft()
                dropped += 1
            self._items.append(item)
            depth = len(self._items)
            self._cv.notify_all()
        # outside the cv: observability must never extend the handoff's
        # critical section (no-ops unless a sink is configured)
        obs.observe("publish.queue_depth", depth)
        if dropped:
            obs.count("publish.superseded", dropped)
        return dropped

    def take(self, timeout: float | None = None):
        """Next item (marks it in flight — pair with ``task_done``), or
        ``_CLOSED`` once closed and empty, or None on timeout."""
        with self._cv:
            while not self._items:
                if self._closed:
                    return _CLOSED
                if not self._cv.wait(timeout=timeout):
                    return None
            self._in_flight += 1
            return self._items.popleft()

    def task_done(self) -> None:
        with self._cv:
            self._in_flight -= 1
            self._cv.notify_all()

    def wait_drained(self, timeout: float | None = None) -> bool:
        with self._cv:
            return self._cv.wait_for(
                lambda: not self._items and self._in_flight == 0,
                timeout=timeout)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class PublishWorker:
    """One DAEMON thread draining a SupersedeQueue of zero-arg jobs.

    A job exception is logged and reported to ``on_error``, never
    propagated — a failed upload must not kill training (the reference's
    rule, training_manager.py:410-431), and a poisoned job must not wedge
    the queue. Daemon: a worker blocked in a hung upload at interpreter
    exit must not block shutdown (the run loop's flush() is the orderly
    path; see the leaked-thread guard in tests/conftest.py)."""

    def __init__(self, name: str = "publisher", *, depth: int = 1,
                 on_error: Optional[Callable[[BaseException], None]] = None,
                 counter_prefix: str = "publish"):
        self._q = SupersedeQueue(depth)
        self._on_error = on_error
        self._name = name
        # registry namespace of the worker occupancy counters: the delta
        # lane reads as publish.worker_*, while other users of this
        # machinery (the heartbeat publisher, engine/health.py) report
        # under their own prefix instead of polluting the push pipeline's
        # occupancy numbers
        self._counter_prefix = counter_prefix
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        self.jobs_run = 0
        self.jobs_failed = 0
        self.jobs_superseded = 0

    def submit(self, job: Callable[[], None]) -> int:
        """Queue ``job``; returns how many pending jobs it superseded.
        The worker thread starts lazily on first submit, so loops that
        never go async never own a thread."""
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(target=self._run,
                                                name=self._name, daemon=True)
                self._thread.start()
        dropped = self._q.offer(job)
        self.jobs_superseded += dropped
        return dropped

    def _run(self) -> None:
        while True:
            # idle = worker waiting for work (training fully overlapped);
            # busy = host cost actually hidden behind accelerator compute.
            # publish.worker_idle_ms / publish.worker_busy_ms together
            # read as the pipeline's occupancy: busy/(busy+idle) near 1.0
            # means the worker is the bottleneck and pushes will start
            # superseding each other.
            t0 = time.perf_counter()
            job = self._q.take()
            obs.count(f"{self._counter_prefix}.worker_idle_ms",
                      (time.perf_counter() - t0) * 1e3)
            if job is _CLOSED:
                return
            if job is None:
                continue
            t1 = time.perf_counter()
            try:
                job()
                self.jobs_run += 1
            except BaseException as e:  # noqa: BLE001 - worker must survive
                self.jobs_failed += 1
                logger.exception("%s: background job failed", self._name)
                if self._on_error is not None:
                    try:
                        self._on_error(e)
                    except Exception:
                        pass
            finally:
                obs.count(f"{self._counter_prefix}.worker_busy_ms",
                          (time.perf_counter() - t1) * 1e3)
                self._q.task_done()

    def flush(self, timeout: float | None = None) -> bool:
        """Block until every pending AND in-flight job has completed
        (failed jobs count as completed — they were logged/counted)."""
        return self._q.wait_drained(timeout=timeout)

    def close(self, timeout: float = 5.0) -> None:
        """Drain, then stop the worker thread. Idempotent."""
        self._q.wait_drained(timeout=timeout)
        self._q.close()
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout)


def host_materialize(tree: Params) -> Params:
    """Host-complete numpy copy of a (possibly device, possibly
    cross-process-sharded) pytree. On leaves sharded across processes this
    runs a process_allgather — a COLLECTIVE: on a pod it must execute on
    every process at the loop barrier, which is why MinerLoop calls it
    on-thread before handing a pod push to the background worker (the
    single-host fast path is a plain device_get and may run anywhere)."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    if not all(getattr(l, "is_fully_addressable", True) for l in leaves):
        from jax.experimental import multihost_utils
        tree = multihost_utils.process_allgather(tree, tiled=True)
    return jax.device_get(tree)


class DeltaPublisher:
    """The miner's publication lane: one implementation of the
    screen -> transfer -> publish -> rider sequence, runnable either
    inline (``publish_now``, the --push-async-off sequential path and the
    warm-up spelling) or on the background worker (``submit``). Both
    spellings execute the identical code on the identical arrays, so the
    published artifacts are byte-identical by construction.

    Counters land in the loop's :class:`MinerReport` (single logical
    writer: either the training thread in sync mode or the worker in
    async mode — never both concurrently for the same field)."""

    def __init__(self, transport, miner_id: str, *, report,
                 nan_guard: bool = True, queue_depth: int = 1,
                 sleep: Callable[[float], None] | None = None,
                 publish_retry=None, meta_retry=None,
                 wire_spec: dict | None = None):
        from ..transport.retry import (DEFAULT_META_RETRY,
                                       DEFAULT_PUBLISH_RETRY)
        self.transport = transport
        self.miner_id = miner_id
        self.report = report
        self.nan_guard = nan_guard
        self.publish_retry = publish_retry or DEFAULT_PUBLISH_RETRY
        self.meta_retry = meta_retry or DEFAULT_META_RETRY
        self._sleep = sleep
        # wire-v2 declaration for the meta rider (format/density/quant):
        # how receivers learn this miner's artifact is a shard manifest
        # BEFORE fetching it (engine/ingest.py negotiates the v1 decode
        # fallback off its absence). Set by MinerLoop when --wire-v2.
        self.wire_spec = wire_spec
        # layer_key -> sha256 of the last shard set the FLEET can see
        # (updated only after the manifest lands): the publisher-side
        # half of shard dedupe — an unchanged layer's shard is never
        # re-uploaded, the exact mirror of ingest never re-fetching it.
        self._last_shards: dict[str, str] = {}
        self._worker = PublishWorker(name=f"publish-{miner_id}",
                                     depth=queue_depth)

    # -- the one publish procedure ------------------------------------------
    def publish_now(self, payload: Params, finite, base_revision,
                    cid: str | None = None, *,
                    extra_meta: dict | None = None) -> bool:
        """Screen + transfer + publish + rider ON the calling thread.
        ``finite`` is the snapshot program's device flag (None skips the
        screen); ``payload`` may be device arrays or an already-host tree
        (the pod path materializes at the loop barrier). ``cid`` is the
        push's correlation id (utils/obs.py): it tags every span below,
        rides the meta rider as ``delta_id``, and is what lets
        scripts/obs_report.py join this push to the validator's fetch and
        the averager's merge across processes. ``extra_meta`` merges
        additional rider keys (the sub-averager's ``"agg"`` weight-sum
        declaration, engine/hier_average.py) — protocol keys win on
        collision."""
        import jax

        from ..transport.retry import call_with_retry

        with obs.correlate(cid):
            if self.nan_guard and finite is not None:
                with obs.span("push.screen"):
                    finite_ok = bool(jax.device_get(finite))
                if not finite_ok:
                    logger.warning("miner %s: delta has non-finite values, "
                                   "not pushing", self.miner_id)
                    return False
            # plain device_get on a single host / an already-host tree; an
            # allgather COLLECTIVE for cross-process shards — which is why
            # the pod's sync path runs publish_now at the loop barrier on
            # every process, and its async path materializes first
            with obs.span("push.materialize"):
                host = host_materialize(payload)
            from .. import delta as delta_lib
            sleep = self._sleep
            wire_v2 = delta_lib.is_packed_v2(host)
            try:
                with obs.span("push.upload", miner=self.miner_id):
                    if wire_v2:
                        self._publish_v2(host)
                    else:
                        call_with_retry(
                            lambda: self.transport.publish_delta(
                                self.miner_id, host),
                            policy=self.publish_retry,
                            describe=f"miner {self.miner_id} delta publish",
                            **({"sleep": sleep} if sleep is not None else {}))
            except Exception:
                self.report.pushes_failed += 1
                obs.count("publish.failed")
                # flight ring: the failed push — with its correlation id
                # — is the first thing a postmortem of this miner's death
                # should name (utils/flight.py)
                flight.record("publish", outcome="failed",
                              hotkey=self.miner_id, cid=cid or "",
                              wire="v2" if wire_v2 else "v1")
                logger.exception("miner %s: delta push failed", self.miner_id)
                return False
            self._publish_meta(base_revision, cid,
                               wire=self.wire_spec if wire_v2 else None,
                               extra=extra_meta)
            self.report.pushes += 1
            obs.count("publish.pushes")
            flight.record("publish", outcome="ok", hotkey=self.miner_id,
                          cid=cid or "", wire="v2" if wire_v2 else "v1")
            logger.info("miner %s: pushed delta #%d", self.miner_id,
                        self.report.pushes)
            return True

    # -- wire v2: changed shards, then the manifest --------------------------
    def _publish_v2(self, packed: Params) -> None:
        """Shard-addressed publish of one packed v2 tree: serialize +
        hash every layer, upload ONLY the shards whose content hash
        changed since the last round this publisher landed, then publish
        the manifest. MANIFEST-LAST is the torn-set invariant: until the
        manifest commits, readers hold the previous manifest, and any
        already-overwritten shard fails its hash check instead of
        decoding half-new (engine/ingest.py treats that as a transient
        miss, exactly like a mid-rename publish race). ``_last_shards``
        advances only after the manifest lands, so a failed publish
        re-uploads everything unconfirmed next interval."""
        from .. import delta as delta_lib
        from .. import serialization as ser
        from ..transport import base as tbase
        from ..transport.retry import call_with_retry

        sleep = self._sleep
        kw = {"sleep": sleep} if sleep is not None else {}
        t0 = time.perf_counter()
        entries = delta_lib.packed_layer_entries(packed)
        shards = {key: ser.pack_shard(e) for key, e in entries.items()}
        layers = {key: (ser.shard_digest(data), len(data))
                  for key, data in shards.items()}
        manifest = ser.build_wire_manifest(
            layers,
            density=(self.wire_spec or {}).get("density", 0.0),
            quant=(self.wire_spec or {}).get("quant", "int8"))
        obs.observe("wire.encode_ms", (time.perf_counter() - t0) * 1e3)
        changed = [key for key, (digest, _) in layers.items()
                   if self._last_shards.get(key) != digest]
        shards_done = 0
        try:
            for key in changed:
                data = shards[key]
                call_with_retry(
                    lambda key=key, data=data: tbase.publish_shard(
                        self.transport, self.miner_id, key, data),
                    policy=self.publish_retry,
                    describe=f"miner {self.miner_id} shard {key}", **kw)
                obs.count("wire.bytes_published", len(data))
                shards_done += 1
            obs.count("wire.shards_uploaded", len(changed))
            obs.count("wire.shards_skipped", len(shards) - len(changed))
            pdr = getattr(self.transport, "publish_delta_raw", None)
            publish_manifest = (pdr if pdr is not None
                                else self.transport.publish_raw)
            call_with_retry(
                lambda: publish_manifest(self.miner_id, manifest),
                policy=self.publish_retry,
                describe=f"miner {self.miner_id} wire manifest publish",
                **kw)
        except Exception:
            # torn shard set: some shards landed, the manifest (or a
            # later shard) did not. Readers are safe (manifest-last), but
            # the flight ring must NAME the tear — which push, how far it
            # got — because this is precisely the state a mid-publish
            # kill leaves behind and the postmortem timeline
            # (scripts/postmortem.py) reconstructs.
            flight.record("publish", outcome="torn",
                          hotkey=self.miner_id,
                          cid=obs.current_cid() or "",
                          shards_done=shards_done,
                          shards_total=len(changed), manifest=False)
            raise
        obs.count("wire.bytes_published", len(manifest))
        obs.count("wire.manifest_publishes")
        self._last_shards = {key: digest
                             for key, (digest, _) in layers.items()}

    def _publish_meta(self, base_revision, cid: str | None = None,
                      wire: dict | None = None,
                      extra: dict | None = None) -> None:
        """Base-revision (+ correlation-id, + wire-format declaration)
        rider next to the delta (see transport/base.publish_delta_meta
        for the staleness protocol). The delta-THEN-rider order makes the
        only inconsistent window false-STALE, never false-fresh — and for
        wire v2, never false-v2: a receiver that reads the old rider
        simply decodes the (self-describing) manifest by its magic
        instead. Best-effort: a rider that fails its whole retry budget
        heals at the next push cadence, so it is logged, not counted as
        a failed push."""
        from ..transport.retry import call_with_retry

        pm = getattr(self.transport, "publish_delta_meta", None)
        if pm is None or (base_revision is None and cid is None
                          and wire is None and not extra):
            return
        meta: dict = dict(extra) if extra else {}
        if base_revision is not None:
            meta["base_revision"] = base_revision
        if cid is not None:
            meta["delta_id"] = cid
        if wire is not None:
            meta["wire"] = wire
        sleep = self._sleep
        try:
            with obs.span("push.meta"):
                call_with_retry(
                    lambda: pm(self.miner_id, meta),
                    policy=self.meta_retry,
                    describe=f"miner {self.miner_id} delta meta publish",
                    **({"sleep": sleep} if sleep is not None else {}))
        except Exception:
            logger.warning(
                "miner %s: delta meta publish failed after retries; "
                "skip-policy receivers may treat this push as stale "
                "until the next one", self.miner_id, exc_info=True)

    # -- async lane ---------------------------------------------------------
    def submit(self, payload: Params, finite, base_revision,
               cid: str | None = None, *,
               extra_meta: dict | None = None) -> int:
        """Hand a snapshot to the background worker; returns how many
        pending pushes it superseded. The caller must pass NON-DONATED
        buffers (the jitted snapshot program's outputs) — the worker reads
        them while later train steps donate the live state.

        ``publish.submit_ms`` is the TRAINING THREAD's whole cost of a
        push in async mode — the number the pipeline exists to keep near
        zero."""
        t0 = time.perf_counter()
        dropped = self._worker.submit(
            lambda: self.publish_now(payload, finite, base_revision, cid,
                                     extra_meta=extra_meta))
        obs.observe("publish.submit_ms", (time.perf_counter() - t0) * 1e3)
        if dropped:
            self.report.pushes_superseded += dropped
            logger.debug("miner %s: superseded %d pending push(es)",
                         self.miner_id, dropped)
        return dropped

    def flush(self, timeout: float | None = None) -> bool:
        """Drain pending + in-flight publishes (shutdown/e2e semantics:
        the final push is on the wire before flush returns)."""
        return self._worker.flush(timeout=timeout)

    def close(self, timeout: float = 5.0) -> None:
        self._worker.close(timeout=timeout)
