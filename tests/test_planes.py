"""The five observation planes, held to what tier-1 CAN say of them.

A plane (heartbeat, flight recorder, device observatory on the miner
side; lineage, remediation on the averager / validator side) rides
beside a production loop. What it COSTS a step is the chip's to say
(PERF.md §3: not measured). What holds on any backend, without a clock:

- it sees every step / round of the loop it rides on, and
- it changes no result: from one seed, the loop's final parameters and
  losses (the published base, the round's scores) are bit-identical
  with the plane on and off.

One fixture per side builds the production loop the way the role mains
do; each plane is one more input to it.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.engine.average import (AveragerLoop,
                                                    WeightedAverage)
from distributedtraining_tpu.engine.health import (FleetMonitor,
                                                   HeartbeatPublisher,
                                                   build_heartbeat,
                                                   report_vitals)
from distributedtraining_tpu.engine.lineage import LineagePlane
from distributedtraining_tpu.engine.remediate import RemediationEngine
from distributedtraining_tpu.engine.train import (MinerLoop,
                                                  host_wire_template)
from distributedtraining_tpu.engine.validate import Validator
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.transport.base import heartbeat_id
from distributedtraining_tpu.utils import devprof, flight, obs
from distributedtraining_tpu.utils.metrics import InMemorySink

STEPS = (2, 30)        # two run() calls: a role re-enters run()
ROUNDS = 3
MINERS = [f"m{i}" for i in range(4)]


def _host(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_bit_identical(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Miner side: heartbeat, flight, devprof beside MinerLoop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def miner_runs():
    """``run(plane)`` -> what one MinerLoop run from seed 0 left behind
    (2 + 30 steps over one fixed batch, pushes at a 50 ms cadence,
    the obs layer configured as a role main configures it), with
    ``plane`` attached; ``None`` is the loop alone. Each run is made
    once and shared by the tests of this module."""
    model, cfg = gpt2.make_model("tiny")
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 64)).astype(np.int32)}
    runs: dict = {}

    def batches():
        while True:
            yield batch

    def run(plane):
        if plane in runs:
            return runs[plane]
        sink = InMemorySink()
        transport = InMemoryTransport()
        hb = rec = None
        seen: dict = {}
        try:
            obs.configure(sink, role="miner")
            if plane == "devprof":
                devprof.enable()
            if plane == "flight":
                rec = flight.configure("miner", "planes", transport=transport,
                                       capacity=512)
            loop = MinerLoop(TrainEngine(model, seq_len=64), transport,
                             "planes", send_interval=0.05,
                             check_update_interval=1e9, log_every=4,
                             metrics=sink)
            if plane == "heartbeat":
                hb = HeartbeatPublisher(transport, "miner", "planes",
                                        interval=0.02,
                                        vitals=report_vitals(loop.report))
                loop.heartbeat = hb
            loop.bootstrap(jax.random.PRNGKey(0))
            for n in STEPS:
                loop.run(batches(), max_steps=n)
            loop.flush()
            if hb is not None:
                seen = {"sent": hb.sent, "failed": hb.failed}
            if rec is not None:
                seen = {"recorded": rec.recorded,
                        "bundle": rec.freeze("test")}
            if plane == "devprof":
                seen = {"records": {r.prog: r for r in devprof.records()}}
            runs[plane] = SimpleNamespace(
                steps=loop.report.steps,
                params=_host(loop.state.params),
                losses=[r["train_loss"] for r in sink.records
                        if "train_loss" in r] + [loop.report.last_loss],
                seen=seen)
            return runs[plane]
        finally:
            if hb is not None:
                hb.close()
            flight.reset()
            devprof.reset()
            obs.reset()

    return run


@pytest.mark.parametrize("plane", ["heartbeat", "flight", "devprof"])
def test_plane_sees_every_step(miner_runs, plane):
    got = miner_runs(plane)
    assert got.steps == sum(STEPS)
    if plane == "heartbeat":
        # the timer beat beside the loop and the final beat of flush()
        assert got.seen["sent"] >= 2 and got.seen["failed"] == 0
    elif plane == "flight":
        # span closes, publish outcomes, registry snapshots: recorded,
        # and present in a frozen bundle
        assert got.seen["recorded"] > 0
        kinds = {e["kind"] for e in got.seen["bundle"]["events"]}
        assert kinds and kinds <= set(flight.EVENT_KINDS)
        assert "publish" in kinds
    else:
        step = got.seen["records"]["train.step"]
        assert step.calls == sum(STEPS)     # every dispatch
        if devprof.cost_analysis_available():
            assert step.flops > 0 and step.bytes_accessed > 0


@pytest.mark.parametrize("plane", ["heartbeat", "flight", "devprof"])
def test_plane_changes_no_result(miner_runs, plane):
    off, on = miner_runs(None), miner_runs(plane)
    assert len(off.losses) == sum(STEPS) // 4 + 1   # log_every=4, + last
    assert on.losses == off.losses
    _assert_bit_identical(on.params, off.params)


# ---------------------------------------------------------------------------
# Averager / validator side: lineage beside AveragerLoop, remediation
# beside Validator
# ---------------------------------------------------------------------------

class _CountingRemediation(RemediationEngine):
    """The production engine, counting the rounds it was handed."""
    rounds = 0

    def observe_round(self, breaches):
        self.rounds += 1
        return super().observe_round(breaches)


@pytest.fixture(scope="module")
def round_runs():
    """``run(plane)`` -> what ROUNDS production rounds over a healthy
    four-miner fleet left behind: every round each miner
    publishes a fresh seeded delta and a clean heartbeat. ``lineage``
    rides an AveragerLoop (off: ``averager``), ``remediation`` a
    Validator that has the fleet monitor either way (off:
    ``validator``)."""
    model, cfg = gpt2.make_model("tiny")
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 32)).astype(np.int32)}
    runs: dict = {}

    class Chain:
        my_hotkey = "planes-node"

        def sync(self):
            return SimpleNamespace(hotkeys=MINERS + [self.my_hotkey])

        def consensus_scores(self):
            return {h: float(i + 1) for i, h in enumerate(MINERS)}

        def should_set_weights(self):
            return False

    def eval_batches():
        yield batch

    def publish_round(transport, template, r):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        key = jax.random.PRNGKey(r)
        for hk in MINERS:
            key, k = jax.random.split(key)
            ks = jax.random.split(k, len(leaves))
            transport.publish_delta(hk, jax.tree_util.tree_unflatten(
                treedef, [1e-3 * np.asarray(jax.random.normal(s, l.shape),
                                            l.dtype)
                          for s, l in zip(ks, leaves)]))
            transport.publish_delta_meta(
                heartbeat_id("miner", hk),
                build_heartbeat("miner", hk, r + 1, now=float(r + 1),
                                steps=float(r + 1), loss_ema=2.0,
                                pushes=float(r + 1)))

    def run(plane):
        if plane in runs:
            return runs[plane]
        engine = TrainEngine(model, seq_len=32)
        transport = InMemoryTransport()
        template = host_wire_template(engine)
        seen = None
        if plane in ("averager", "lineage"):
            if plane == "lineage":
                seen = LineagePlane(transport, node="planes-node")
            loop = AveragerLoop(engine, transport, Chain(), WeightedAverage(),
                                val_batches=eval_batches,
                                publish_policy="always", ingest_workers=1,
                                lineage=seen)
            one_round = loop.run_round
        else:
            fleet = FleetMonitor(transport)
            if plane == "remediation":
                seen = _CountingRemediation(fleet)
            loop = Validator(engine, transport, Chain(),
                             eval_batches=eval_batches, cohort_size=8,
                             fleet=fleet, remediation=seen)
            one_round = loop.validate_and_score
        try:
            loop.bootstrap(rng=jax.random.PRNGKey(0))
            results = []
            for r in range(ROUNDS):
                publish_round(transport, template, r)
                results.append(one_round())
            base = transport.fetch_base(template)
            runs[plane] = SimpleNamespace(
                results=results, seen=seen,
                base=None if base is None else _host(base[0]),
                revision=None if base is None else base[1])
            return runs[plane]
        finally:
            loop.close()

    return run


@pytest.mark.parametrize("plane", ["lineage", "remediation"])
def test_plane_sees_every_round(round_runs, plane):
    got = round_runs(plane)
    if plane == "lineage":
        assert got.results == [True] * ROUNDS       # every round merged
        # one record a merged round, after the genesis base's own
        assert got.seen.records == 1 + ROUNDS
    else:
        assert got.seen.rounds == ROUNDS
        assert got.seen.quarantines == 0                # a healthy fleet
        assert all(len(scores) == len(MINERS) for scores in got.results)


@pytest.mark.parametrize("plane,alone", [("lineage", "averager"),
                                         ("remediation", "validator")])
def test_plane_changes_no_result_round(round_runs, plane, alone):
    off, on = round_runs(alone), round_runs(plane)
    if plane == "lineage":
        assert on.revision == off.revision
        _assert_bit_identical(on.base, off.base)
    else:
        def table(results):
            return [[(s.hotkey, s.score, s.loss, s.reason) for s in scores]
                    for scores in results]
        assert table(on.results) == table(off.results)
        assert all(s.loss is not None for s in on.results[-1])
