"""Async miner publication pipeline (engine/publish.py).

Contracts pinned here, mirroring tests/test_batched_eval.py's pipeline
discipline:

1. PARITY — the async path publishes byte-identical artifacts (and the
   identical rider) to the sequential path, and --push-async off IS the
   sequential path (no worker thread ever starts).
2. SUPERSEDE — a push still pending when the next interval fires is
   replaced, never queued behind; counters record it.
3. FLUSH — flush() drains pending AND in-flight publishes before
   returning (shutdown/e2e semantics unchanged).
4. ISOLATION — publisher-worker exceptions (and retry-exhausted
   publishes) never kill training; failures land in
   MinerReport.pushes_failed.
5. POD RULE — on a cross-process mesh the snapshot + host
   materialization happen on the TRAINING thread; only the upload runs
   on the worker.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu import delta as delta_lib
from distributedtraining_tpu.engine import (
    FakeClock, MinerLoop, PublishWorker, SupersedeQueue, TrainEngine)
from distributedtraining_tpu.engine.publish import host_materialize
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.transport.retry import (RetryPolicy,
                                                     call_with_retry)

SEQ = 32
BATCH = 4


@pytest.fixture(scope="module")
def setup():
    model, cfg = gpt2.make_model("tiny")
    rng = np.random.default_rng(0)
    batch = {"input_ids": np.asarray(
        rng.integers(0, cfg.vocab_size, (BATCH, SEQ)), np.int32)}
    return model, cfg, batch


def _run_miner(model, batch, *, push_async, transport=None, steps=12,
               send_interval=5.0, delta_dtype=None, **kw):
    engine = TrainEngine(model, seq_len=SEQ)
    transport = transport if transport is not None else InMemoryTransport()
    loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                     send_interval=send_interval,
                     check_update_interval=1e9, log_every=10**9,
                     push_async=push_async, delta_dtype=delta_dtype, **kw)
    loop.bootstrap(jax.random.PRNGKey(0))

    def batches():
        while True:
            loop.clock.sleep(1.0)
            yield batch

    loop.run(batches(), max_steps=steps)
    loop.flush()
    return transport, loop


# ---------------------------------------------------------------------------
# the queue + worker primitives
# ---------------------------------------------------------------------------

def test_supersede_queue_newest_wins():
    q = SupersedeQueue(depth=1)
    assert q.offer("a") == 0
    assert q.offer("b") == 1     # a superseded before anyone took it
    assert q.offer("c") == 1
    assert q.take() == "c"
    q.task_done()
    with pytest.raises(ValueError):
        SupersedeQueue(depth=0)


def test_supersede_queue_in_flight_never_superseded():
    """An item the consumer already took completes; only PENDING items
    are replaced."""
    q = SupersedeQueue(depth=1)
    q.offer("a")
    assert q.take() == "a"       # in flight now
    assert q.offer("b") == 0     # nothing pending to supersede
    assert q.offer("c") == 1     # b was pending
    q.task_done()
    assert q.take() == "c"
    q.task_done()
    assert q.wait_drained(timeout=1.0)


def test_publish_worker_supersedes_while_blocked():
    """Jobs submitted while the worker is stuck in an upload coalesce to
    the newest; the blocked job still completes."""
    gate = threading.Event()
    started = threading.Event()
    ran = []

    def make(tag, block=False):
        def job():
            ran.append(tag)
            if block:
                started.set()
                gate.wait(5.0)
        return job

    w = PublishWorker(name="t", depth=1)
    w.submit(make("slow", block=True))
    assert started.wait(5.0)
    # worker is in flight on "slow"; these three coalesce to the newest
    w.submit(make("a"))
    w.submit(make("b"))
    w.submit(make("c"))
    gate.set()
    assert w.flush(timeout=5.0)
    assert ran == ["slow", "c"]
    assert w.jobs_superseded == 2
    w.close()


def test_publish_worker_survives_job_exceptions():
    errors = []
    w = PublishWorker(name="t", on_error=errors.append)
    w.submit(lambda: 1 / 0)
    assert w.flush(timeout=5.0)
    w.submit(lambda: None)       # worker still alive and draining
    assert w.flush(timeout=5.0)
    assert w.jobs_failed == 1 and w.jobs_run == 1
    assert isinstance(errors[0], ZeroDivisionError)
    w.close()


def test_publish_worker_thread_is_lazy_and_daemon():
    w = PublishWorker(name="t")
    assert w._thread is None     # sync-only loops never own a thread
    w.submit(lambda: None)
    assert w._thread is not None and w._thread.daemon
    w.close()
    assert w._thread is None


# ---------------------------------------------------------------------------
# retry (transport/retry.py)
# ---------------------------------------------------------------------------

def test_retry_backoff_bounded_and_jittered():
    import random
    policy = RetryPolicy(attempts=5, base_delay=1.0, max_delay=4.0,
                         jitter=0.5)
    rng = random.Random(0)
    for attempt, cap in ((1, 1.0), (2, 2.0), (3, 4.0), (4, 4.0)):
        for _ in range(20):
            d = policy.delay(attempt, rng)
            assert 0.5 * cap <= d <= 1.5 * cap
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)


def test_call_with_retry_recovers_then_gives_up():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("hub hiccup")
        return "ok"

    assert call_with_retry(flaky, policy=RetryPolicy(attempts=3),
                           sleep=sleeps.append) == "ok"
    assert calls["n"] == 3 and len(sleeps) == 2

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        call_with_retry(always, policy=RetryPolicy(attempts=2),
                        sleep=sleeps.append)


# ---------------------------------------------------------------------------
# parity: async == sync, byte for byte
# ---------------------------------------------------------------------------

def test_async_artifacts_byte_identical_to_sync(setup):
    model, cfg, batch = setup
    t_sync, l_sync = _run_miner(model, batch, push_async=False)
    # room for every push to wait its turn: at depth 1 a fast train loop
    # (warm compile cache) supersedes a pending push by design, and the
    # push COUNT then depends on machine speed — parity is the subject
    t_async, l_async = _run_miner(model, batch, push_async=True,
                                  push_queue_depth=4)
    assert l_sync.report.pushes == l_async.report.pushes >= 2
    assert t_sync._deltas["m0"] == t_async._deltas["m0"]


def test_async_parity_sparse8_wire(setup):
    """The fused snapshot program (delta + wire layout + sparse8 + finite
    flag in ONE jit) produces the identical artifact either way."""
    model, cfg, batch = setup
    t_sync, _ = _run_miner(model, batch, push_async=False,
                           delta_dtype="sparse8")
    t_async, _ = _run_miner(model, batch, push_async=True,
                            delta_dtype="sparse8")
    assert t_sync._deltas["m0"] == t_async._deltas["m0"]


def test_push_async_off_never_starts_a_worker(setup):
    model, cfg, batch = setup
    _, loop = _run_miner(model, batch, push_async=False)
    assert loop._publisher._worker._thread is None


def test_meta_rider_published_from_worker(setup):
    """With a published base, the async path uploads the base-revision
    rider after the artifact, same as sync."""
    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    transport = InMemoryTransport()
    rev = transport.publish_base(engine.init_state(
        jax.random.PRNGKey(1)).params)
    t, loop = _run_miner(model, batch, push_async=True, transport=transport)
    assert loop.report.base_pulls == 0  # bootstrap pulled it, not run()
    meta = t.fetch_delta_meta("m0")
    assert meta["base_revision"] == rev
    # the rider also carries the push's correlation id (utils/obs.py)
    assert meta["delta_id"].startswith("m0-")


# ---------------------------------------------------------------------------
# supersede + flush semantics on the real loop
# ---------------------------------------------------------------------------

class _GatedTransport(InMemoryTransport):
    """publish_delta blocks until released — deterministic in-flight
    control (the _SlowTransport discipline of test_batched_eval)."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.publishes = 0

    def publish_delta(self, miner_id, delta):
        self.entered.set()
        assert self.gate.wait(10.0), "test forgot to release the gate"
        self.publishes += 1
        return super().publish_delta(miner_id, delta)


def test_pushes_supersede_while_upload_in_flight(setup):
    """Three pushes land while the first is stuck in the transport: the
    middle ones coalesce, the flush() artifact is the NEWEST state."""
    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    transport = _GatedTransport()
    loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                     send_interval=1.0, check_update_interval=1e9,
                     log_every=10**9, push_async=True)
    loop.bootstrap(jax.random.PRNGKey(0))

    def batches():
        while True:
            loop.clock.sleep(1.0)
            yield batch

    worker = threading.Thread(
        target=lambda: (loop.run(batches(), max_steps=6)), daemon=True)
    worker.start()
    assert transport.entered.wait(30.0)   # first push is in flight
    worker.join(30.0)                     # training finished meanwhile
    assert not worker.is_alive(), "training stalled behind the upload"
    transport.gate.set()
    loop.flush()
    # every push interval fired, but blocked uploads coalesced
    assert loop.report.pushes == transport.publishes
    assert loop.report.pushes + loop.report.pushes_superseded >= 3
    assert loop.report.pushes_superseded >= 1
    # the final artifact equals a fresh snapshot of the final state
    payload, _ = loop._push_snapshot()
    from distributedtraining_tpu import serialization as ser
    assert transport._deltas["m0"] == ser.to_msgpack(
        jax.device_get(payload))


def test_flush_drains_in_flight_publish(setup):
    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    transport = _GatedTransport()
    loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                     send_interval=1e9, check_update_interval=1e9,
                     log_every=10**9, push_async=True)
    loop.bootstrap(jax.random.PRNGKey(0))
    loop._push_delta()
    assert transport.entered.wait(30.0)
    assert "m0" not in transport._deltas    # still in flight
    done = threading.Event()
    t = threading.Thread(target=lambda: (loop.flush(), done.set()),
                         daemon=True)
    t.start()
    assert not done.wait(0.2), "flush returned with the publish in flight"
    transport.gate.set()
    assert done.wait(30.0)
    assert "m0" in transport._deltas
    assert loop.report.pushes >= 1


def test_worker_publish_failure_counted_not_fatal(setup):
    """A transport that dies (even past its retry budget) costs the report
    a pushes_failed tick; training and later pushes continue."""
    model, cfg, batch = setup

    class Dying(InMemoryTransport):
        def __init__(self):
            super().__init__()
            self.calls = 0

        def publish_delta(self, miner_id, delta):
            self.calls += 1
            if self.calls <= 4:   # eats the first push's whole retry budget
                raise OSError("hub down")
            return super().publish_delta(miner_id, delta)

    transport = Dying()
    t, loop = _run_miner(model, batch, push_async=True, transport=transport,
                         steps=12)
    assert loop.report.pushes_failed >= 1
    assert loop.report.pushes >= 1          # a later push recovered
    assert loop.report.steps == 12          # training never died
    assert "m0" in transport._deltas


def test_nonfinite_delta_screened_off_thread(setup):
    """The fused finite flag still blocks poisoned publishes when fetched
    on the worker."""
    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    transport = InMemoryTransport()
    loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                     send_interval=1e9, check_update_interval=1e9,
                     log_every=10**9, push_async=True)
    loop.bootstrap(jax.random.PRNGKey(0))
    loop.state = loop.state.replace(params=jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), loop.state.params))
    loop._push_delta()
    loop.flush()
    assert loop.report.pushes == 0
    assert "m0" not in transport._deltas


# ---------------------------------------------------------------------------
# pod rule: snapshot + materialization on-thread, upload-only background
# ---------------------------------------------------------------------------

def test_pod_mode_materializes_on_training_thread(setup):
    """With _multi() true, the worker must receive an already-HOST tree
    (the allgather is a collective — it may only run at the loop barrier)
    and the transport still sees exactly one publish."""
    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)

    submitted = {}

    class Spy(InMemoryTransport):
        def publish_delta(self, miner_id, delta):
            submitted["thread"] = threading.current_thread().name
            submitted["host"] = all(
                isinstance(l, np.ndarray)
                for l in jax.tree_util.tree_leaves(delta))
            return super().publish_delta(miner_id, delta)

    transport = Spy()
    loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                     send_interval=1e9, check_update_interval=1e9,
                     log_every=10**9, push_async=True)
    loop.bootstrap(jax.random.PRNGKey(0))
    loop._multi = lambda: True    # single-process stand-in for a pod mesh
    loop._push_delta()
    loop._publisher.flush()       # drain WITHOUT forcing a second push
    assert loop.report.pushes == 1
    # upload ran on the background worker...
    assert submitted["thread"].startswith("publish-")
    # ...but the tree it saw was materialized host-side on THIS thread
    assert submitted["host"]


def test_host_materialize_is_device_get_on_single_host(setup):
    model, cfg, batch = setup
    tree = {"a": jnp.ones((4, 4)), "b": np.zeros((2,))}
    out = host_materialize(tree)
    assert all(isinstance(l, np.ndarray)
               for l in jax.tree_util.tree_leaves(out))
    np.testing.assert_array_equal(out["a"], np.ones((4, 4)))


# ---------------------------------------------------------------------------
# async checkpoint lane (checkpoint.save_async)
# ---------------------------------------------------------------------------

def test_async_checkpoint_supersede_and_flush(setup, tmp_path):
    from distributedtraining_tpu.checkpoint import CheckpointStore, Snapshot

    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    state = engine.init_state(jax.random.PRNGKey(0))
    with CheckpointStore(str(tmp_path)) as store:
        # burst of saves: pending ones supersede, the store ends on the
        # NEWEST revision with a contiguous step sequence
        for i in range(4):
            store.save_async(Snapshot(state=state, base_params=None,
                                      base_revision=f"r{i}",
                                      lifetime_steps=i))
        assert store.flush(timeout=60)
        steps = store.all_steps()
        assert steps == sorted(steps) and len(steps) <= 4
        assert store.read_meta()["base_revision"] == "r3"

    # precondition=False vetoes the write on the worker
    with CheckpointStore(str(tmp_path / "veto")) as store:
        store.save_async(Snapshot(state=state, base_params=None,
                                  base_revision="bad"),
                         precondition=lambda: False)
        assert store.flush(timeout=60)
        assert store.latest_step() is None


def test_miner_async_checkpoint_roundtrip(setup, tmp_path):
    """MinerLoop + push_async + a real store: the background save persists
    a state a fresh loop resumes from."""
    from distributedtraining_tpu.checkpoint import CheckpointStore

    model, cfg, batch = setup
    engine = TrainEngine(model, seq_len=SEQ)
    transport = InMemoryTransport()
    with CheckpointStore(str(tmp_path)) as store:
        loop = MinerLoop(engine, transport, "m0", clock=FakeClock(),
                         send_interval=1e9, check_update_interval=1e9,
                         log_every=10**9, push_async=True,
                         checkpoint_store=store, checkpoint_interval=1e9)
        loop.bootstrap(jax.random.PRNGKey(0))

        def batches():
            while True:
                yield batch

        loop.run(batches(), max_steps=3)
        loop.flush()
        assert store.latest_step() is not None

    with CheckpointStore(str(tmp_path)) as store:
        engine2 = TrainEngine(model, seq_len=SEQ)
        loop2 = MinerLoop(engine2, transport, "m0", clock=FakeClock(),
                          send_interval=1e9, check_update_interval=1e9,
                          log_every=10**9, checkpoint_store=store,
                          checkpoint_interval=1e9)
        loop2.bootstrap(jax.random.PRNGKey(1))
        assert loop2.report.steps == 3      # resumed, not re-initialized
