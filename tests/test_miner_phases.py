"""`MinerLoop.run` says what the host is doing: four `obs.phase`s on the
profiler's clock under the histogram names heartbeats and reports already
read, and with neither a sink nor an anomaly monitor the loop reads no clock.
"""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import MinerLoop, TrainEngine
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.utils import obs
from distributedtraining_tpu.utils.metrics import InMemorySink

STEPS = 4


@pytest.fixture(scope="module")
def engine():
    cfg = gpt2.GPT2Config(vocab_size=64, n_positions=16, n_embd=16,
                          n_layer=1, n_head=2)
    model = gpt2.GPT2(cfg)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)}
    return TrainEngine(model, seq_len=16), batch


def _loop(engine, **kw) -> MinerLoop:
    eng, _ = engine
    loop = MinerLoop(eng, InMemoryTransport(), "phases",
                     send_interval=kw.pop("send_interval", 1e9),
                     check_update_interval=1e9, **kw)
    loop.bootstrap(jax.random.PRNGKey(0))
    return loop


class _Annotation:
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        _Annotation.log.append((threading.get_ident(), "enter", self.name))

    def __exit__(self, *exc):
        _Annotation.log.append((threading.get_ident(), "exit", self.name))


@pytest.fixture()
def annotations(monkeypatch):
    _Annotation.log = []
    monkeypatch.setattr(obs, "_trace_annotation", lambda: _Annotation)
    return _Annotation.log


def test_run_with_a_sink_writes_each_phase_once_a_step_or_fetch(engine):
    sink = InMemorySink()
    reg = obs.configure(sink, role="miner")
    try:
        loop = _loop(engine, metrics=sink, log_every=2)
        loop.run([engine[1]] * STEPS)
        count = {n: reg.peek(n).count for n in reg.names()
                 if n.startswith("miner.")}
    finally:
        obs.reset()
    assert count == {
        "miner.step_ms": STEPS, "miner.actions_ms": STEPS,
        # one a `next`: the one that found the feed exhausted is a wait too
        "miner.data_wait_ms": STEPS + 1,
        # at the log cadence (steps 2 and 4) and when run() returns
        "miner.fetch_loss_ms": STEPS // 2 + 1}
    assert loop.report.steps == STEPS


def test_phases_lie_on_the_profilers_clock_in_the_loops_order(
        engine, annotations):
    """Every phase is a host span of the train thread; a push that falls
    due opens `push.snapshot` inside `miner.actions`."""
    obs.configure(InMemorySink(), role="miner")
    try:
        loop = _loop(engine, send_interval=0.0)
        del annotations[:]                  # the bootstrap's own spans
        loop.run([engine[1]] * 2)
        loop.flush()
    finally:
        obs.reset()
    me = threading.get_ident()
    mine = [(kind, name) for ident, kind, name in annotations
            if ident == me and name.startswith(("miner.", "push.snap"))]
    one_step = [("enter", "miner.data_wait"), ("exit", "miner.data_wait"),
                ("enter", "miner.step"), ("exit", "miner.step"),
                ("enter", "miner.actions"),
                ("enter", "push.snapshot"), ("exit", "push.snapshot"),
                ("exit", "miner.actions")]
    assert mine[:16] == one_step * 2
    assert mine[16:20] == [
        ("enter", "miner.data_wait"), ("exit", "miner.data_wait"),
        ("enter", "miner.fetch_loss"), ("exit", "miner.fetch_loss")]


class _Monitor:
    """An anomaly monitor that only keeps what it was told."""

    def __init__(self):
        self.step_ms = []

    def observe_step_ms(self, ms):
        self.step_ms.append(ms)

    def tick(self):
        pass

    def close(self):
        pass


@pytest.mark.parametrize("monitor", [False, True])
def test_run_without_a_sink_reads_the_clock_only_for_a_monitor(
        engine, monkeypatch, monitor):
    """Tracing off and no monitor: not one `perf_counter` read from the
    loop or from obs. With a monitor, `miner.step`'s pair alone."""
    anomaly = _Monitor() if monitor else None
    loop = _loop(engine, anomaly=anomaly)
    loop.run([engine[1]])                   # compiled, warm
    ours = ("engine/train.py", "utils/obs.py")
    reads: list = []
    real = time.perf_counter

    def counted():
        caller = sys._getframe(1).f_code.co_filename
        if caller.endswith(ours):
            reads.append(caller)
        return real()

    monkeypatch.setattr(time, "perf_counter", counted)
    assert not obs.enabled()
    loop.run([engine[1]] * STEPS)
    monkeypatch.undo()
    assert len(reads) == (2 * STEPS if monitor else 0), reads
    if monitor:
        assert len(anomaly.step_ms) == STEPS + 1
        assert all(ms >= 0.0 for ms in anomaly.step_ms)
