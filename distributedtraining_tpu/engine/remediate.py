"""Remediation: act on SLO breaches — quarantine, elastic cohorts,
averager failover.

PR 5 built the detection half of the fleet health plane
(engine/health.py): heartbeats, a per-miner contribution ledger, and
declarative SLO rules whose breaches armed a profiler one-shot and
nothing else. This module is the actuator half — at fleet scale node
failure is the steady state, not the exception, so a breach must change
what the next round *does*:

- **Quarantine** (:class:`RemediationEngine`): a miner breaching a
  configured rule (default: push-failure streak, loss divergence, stale
  node) is dropped from the ingest hotkey set — the delta-consuming
  loops pass :meth:`RemediationEngine.is_excluded` as the staging
  exclude hook (engine/ingest.py), so a quarantined submission is
  refused *before* any transport bytes move and the refusal lands in the
  contribution ledger as ``reason="quarantined"``. Scores decay
  (:meth:`decay_scores`) instead of freezing at their pre-breach value.
  Heartbeats keep being polled: after ``probation_beats`` FRESH beats
  that evaluate clean against the quarantining rule, the node re-admits
  into **probation** (staged again, watched for ``probation_rounds``
  rounds; the fired-breach memory is re-armed via
  ``FleetMonitor.clear_fired`` so a relapse re-quarantines immediately),
  then back to healthy.

- **Elastic cohort sizing** (:func:`elastic_cohort` +
  ``BatchedCohortEvaluator.prefer_compiled``): when quarantine/pruning
  shrinks the healthy-miner count below the configured cohort, the
  effective cohort steps down the PRE-COMPILED bucket ladder
  (engine/batched_eval.py BUCKETS) instead of tracking the raw count —
  and the evaluator, when asked, pads up to an already-compiled bucket
  rather than compiling the exact-fit one. A fleet wobbling between 3
  and 8 healthy miners therefore hits one compiled program per phase,
  never a per-round compile storm (the failure mode the ``compile.ms``
  histogram was built to expose).

- **Averager failover** (:class:`LeaseManager` + :class:`StandbyAverager`):
  base publication is single-writer, so a standby cannot simply start
  publishing when the primary looks dead — looks-dead is a one-sided
  observation. The arbitration token is a transport-published **lease**
  (transport/base.lease_id, riding the same rider channel as
  heartbeats): ``{"epoch": N, "holder": hotkey, "t": ..}``. The holder
  re-reads and renews it immediately before every base publish; the
  standby follows the live signals (lease renewals, ``__hb__.averager.*``
  heartbeat sequence, base revision) and, once nothing has changed for
  ``deadline_s``, acquires the lease at ``epoch N+1`` and becomes
  active. A revived old primary re-reads the lease before its next
  publish, sees the higher epoch, and stands down — so every published
  base is stamped with a monotonically increasing epoch and exactly one
  averager publishes per round, across the failover. (The guarantee is
  epoch arbitration through the shared store, not a distributed-consensus
  proof: a transport that serves stale reads to exactly one side can
  delay — never reorder — a handover.)

Everything here is driven at the round cadence by the loops that already
own a FleetMonitor; remediation failures are isolated the same way the
health plane's are — they degrade remediation, never a round.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Iterable, Sequence

from ..transport.base import heartbeat_id, lease_id
from ..utils import flight, obs
from .batched_eval import BUCKETS
from .health import FleetMonitor, parse_heartbeat

logger = logging.getLogger(__name__)

Params = Any


# ---------------------------------------------------------------------------
# Elastic cohort sizing
# ---------------------------------------------------------------------------

def elastic_cohort(configured: int, healthy: int, *,
                   compiled: Iterable[int] = (),
                   buckets: Sequence[int] = BUCKETS) -> int:
    """Effective cohort size for ``healthy`` stageable miners under a
    ``configured`` cohort: unchanged while the fleet covers it, else the
    smallest ladder bucket covering the healthy count — preferring an
    ALREADY-COMPILED bucket so the shrink reuses a cached program instead
    of compiling the exact-fit one. Never exceeds ``configured``."""
    if configured <= 1 or healthy >= configured:
        return configured
    healthy = max(1, int(healthy))
    comp = sorted(b for b in set(compiled) if healthy <= b <= configured)
    if comp:
        return comp[0]
    ladder = [b for b in buckets if b >= healthy]
    target = ladder[0] if ladder else buckets[-1]
    return max(1, min(configured, target))


# ---------------------------------------------------------------------------
# Quarantine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RemediationPolicy:
    """Declarative knobs (docs/resilience.md documents each).

    ``quarantine_rules``: SLO rule NAMES whose breach quarantines a miner
    (names, not kinds — deployments rename/duplicate rules with custom
    thresholds). ``probation_beats``: fresh clean heartbeats required to
    re-admit. ``probation_rounds``: rounds a re-admitted node stays on
    probation (a breach there re-quarantines at once). ``score_decay``:
    multiplier applied to a quarantined miner's score each round — decay,
    not zeroing, so a recovered node re-enters weight-setting from a
    discounted history rather than from nothing."""
    quarantine_rules: tuple = ("push_failure_streak", "loss_divergence",
                               "stale_node")
    probation_beats: int = 3
    probation_rounds: int = 2
    score_decay: float = 0.25

    def __post_init__(self):
        if self.probation_beats < 1:
            raise ValueError(f"probation_beats must be >= 1, "
                             f"got {self.probation_beats}")
        if self.probation_rounds < 0:
            raise ValueError(f"probation_rounds must be >= 0, "
                             f"got {self.probation_rounds}")
        if not 0.0 <= self.score_decay <= 1.0:
            raise ValueError(f"score_decay must be in [0, 1], "
                             f"got {self.score_decay}")


@dataclasses.dataclass
class _Case:
    """One miner's remediation case file."""
    hotkey: str
    rule: str                       # the rule that quarantined it
    state: str                      # "quarantined" | "probation"
    opened_round: int
    beats_seen: int                 # node.beats at the last observation
    clean_beats: int = 0
    probation_until: int | None = None


class RemediationEngine:
    """Subscribe a :class:`~.health.FleetMonitor`'s breaches to actions.

    Drive it at the round cadence from the loop that owns the monitor:
    ``observe_round(breaches)`` right after ``fleet.evaluate_slos()``.
    The staging exclude hook (:meth:`is_excluded`) and score decay
    (:meth:`decay_scores`) read the current case files; both are cheap
    dict lookups — the filter-hook cost per round is O(hotkeys).
    """

    def __init__(self, fleet: FleetMonitor, *,
                 policy: RemediationPolicy | None = None,
                 metrics=None, role: str = "miner"):
        self.fleet = fleet
        self.policy = policy or RemediationPolicy()
        self.metrics = metrics
        self.role = role            # the role this engine quarantines
        self.cases: dict[str, _Case] = {}
        self._ever: set[str] = set()  # hotkeys ever quarantined (relapse tag)
        self.quarantines = 0        # lifetime counters (reports/tests)
        self.readmissions = 0

    # -- the filter hook -----------------------------------------------------
    def is_excluded(self, hotkey: str) -> bool:
        """True while ``hotkey`` is quarantined (the ingest exclude hook:
        probation nodes are NOT excluded — re-admission means staging)."""
        case = self.cases.get(hotkey)
        return case is not None and case.state == "quarantined"

    def quarantined(self) -> set[str]:
        return {h for h, c in self.cases.items()
                if c.state == "quarantined"}

    def filter_hotkeys(self, hotkeys: Iterable[str]) -> list[str]:
        """The stageable subset of ``hotkeys`` (order preserved)."""
        return [h for h in hotkeys if not self.is_excluded(h)]

    def decay_scores(self, scores: dict[str, float]) -> dict[str, float]:
        """Quarantined miners' scores decay by ``score_decay`` per round
        (applied to whatever the validator computed — usually 0 for a
        quarantined miner, but the decayed value is what feeds the chain
        EMA, pulling the on-chain weight down each round it stays out)."""
        if not self.cases:
            return scores
        return {h: (s * self.policy.score_decay
                    if self.is_excluded(h) else s)
                for h, s in scores.items()}

    def cohort_size(self, configured: int, healthy: int,
                    compiled: Iterable[int] = ()) -> int:
        return elastic_cohort(configured, healthy, compiled=compiled)

    # -- transitions ---------------------------------------------------------
    def _emit(self, action: str, case: _Case, detail: str = "",
              pm_ref: str | None = None) -> dict:
        # postmortem attachment (utils/flight.py): every quarantine and
        # probation flip carries a bundle reference — the TRIGGERING
        # breach's bundle when the monitor froze one, else a fresh
        # freeze of this role's ring at the moment of the action — and
        # the reference lands on the node's ledger entry, so
        # fleet_report/postmortem joins go straight from decision to
        # evidence.
        flight.record("remediation", action=action, hotkey=case.hotkey,
                      rule=case.rule, round=self.fleet.round)
        if pm_ref is None:
            pm_ref = flight.freeze_and_publish(f"remediation_{action}")
        rec = {"remediation": action, "hotkey": case.hotkey,
               "rule": case.rule, "round": self.fleet.round,
               "detail": detail}
        if pm_ref:
            rec["pm_ref"] = pm_ref
            node = self.fleet.nodes.get((self.role, case.hotkey))
            if node is not None:
                node.pm_ref = pm_ref
        obs.count(f"remediate.{action}")
        logger.warning("remediation: %s %s/%s (%s) %s", action, self.role,
                       case.hotkey, case.rule, detail)
        if self.metrics is not None:
            try:
                self.metrics.log(rec)
            except Exception:
                logger.exception("remediation: sink emit failed")
        return rec

    def _quarantine(self, hotkey: str, rule: str, detail: str,
                    pm_ref: str | None = None) -> dict:
        node = self.fleet.node(self.role, hotkey)
        node.quarantined, node.probation = True, False
        relapse = hotkey in self._ever
        self._ever.add(hotkey)
        self.cases[hotkey] = case = _Case(
            hotkey=hotkey, rule=rule, state="quarantined",
            opened_round=self.fleet.round, beats_seen=node.beats)
        self.quarantines += 1
        return self._emit("requarantined" if relapse else "quarantined",
                          case, detail, pm_ref)

    def _rule(self, name: str):
        for r in self.fleet.rules:
            if r.name == name:
                return r
        return None

    def observe_round(self, breaches: Iterable[dict] | None) -> list[dict]:
        """One remediation round: fold this round's NEW breaches, then
        advance every open case (clean-beat counting, probation expiry).
        Returns the action records it emitted. Never raises — the caller
        is a training round."""
        try:
            return self._observe_round(list(breaches or ()))
        except Exception:
            logger.exception("remediation: round observation failed")
            return []

    def _observe_round(self, breaches: list[dict]) -> list[dict]:
        actions = []
        for b in breaches:
            if b.get("role") != self.role:
                continue
            rule = b.get("slo_breach")
            if rule not in self.policy.quarantine_rules:
                continue
            hotkey = b.get("hotkey")
            case = self.cases.get(hotkey)
            if case is not None and case.state == "quarantined":
                continue        # already out; nothing more to do
            actions.append(self._quarantine(hotkey, rule,
                                            b.get("detail", ""),
                                            b.get("pm_ref")))
        median = self.fleet.fleet_median_loss()
        for case in list(self.cases.values()):
            node = self.fleet.nodes.get((self.role, case.hotkey))
            if node is None:    # pruned from the registry: case closed
                del self.cases[case.hotkey]
                continue
            if case.state == "quarantined":
                fresh = node.beats - case.beats_seen
                case.beats_seen = node.beats
                if fresh <= 0:
                    continue
                rule = self._rule(case.rule)
                clean = rule is None or rule.evaluate(
                    node, round_num=self.fleet.round,
                    fleet_median_loss=median) is None
                if not clean:
                    case.clean_beats = 0
                    continue
                case.clean_beats += fresh
                if case.clean_beats >= self.policy.probation_beats:
                    case.state = "probation"
                    case.probation_until = (self.fleet.round
                                            + self.policy.probation_rounds)
                    node.quarantined, node.probation = False, True
                    # re-arm the breach so a relapse can fire (and
                    # re-quarantine) instead of being one-shot-swallowed
                    self.fleet.clear_fired(self.role, case.hotkey,
                                           case.rule)
                    self.readmissions += 1
                    actions.append(self._emit(
                        "readmitted", case,
                        f"{case.clean_beats} clean heartbeats"))
            elif case.state == "probation":
                if self.fleet.round >= (case.probation_until or 0):
                    node.probation = False
                    del self.cases[case.hotkey]
                    actions.append(self._emit("healthy", case))
        obs.gauge("remediate.active_quarantines",
                  float(len(self.quarantined())))
        return actions


# ---------------------------------------------------------------------------
# The publication lease
# ---------------------------------------------------------------------------

LEASE_VERSION = 1
_MAX_STR = 200


def parse_lease(meta) -> dict | None:
    """Defensive read of the (peer-visible) lease token; None when absent
    or malformed — the same trust posture as parse_heartbeat."""
    if not isinstance(meta, dict):
        return None
    v = meta.get("lease")
    if not isinstance(v, (int, float)) or int(v) < 1:
        return None
    epoch = meta.get("epoch")
    holder = meta.get("holder")
    if not isinstance(epoch, (int, float)) or int(epoch) < 1:
        return None
    if not (isinstance(holder, str) and 0 < len(holder) <= _MAX_STR):
        return None
    out = {"lease": int(v), "epoch": int(epoch), "holder": holder,
           "t": float(meta["t"]) if isinstance(meta.get("t"),
                                               (int, float)) else 0.0}
    rev = meta.get("base_revision")
    if isinstance(rev, str) and 0 < len(rev) <= _MAX_STR:
        out["base_revision"] = rev
    return out


class LeaseManager:
    """The failover arbitration token for one single-writer role.

    ``epoch`` is this node's HELD epoch (0 = not holding). ``acquire``
    bumps past the highest epoch ever observed and verifies its own
    write; ``renew`` re-reads before the caller publishes and stands
    down the moment a higher epoch appears; ``stamp`` annotates the
    token with the revision just published, which is how "the
    publication carries the epoch" is readable from the store."""

    def __init__(self, transport, hotkey: str, *, role: str = "averager",
                 clock=None):
        from .scheduler import RealClock
        self.transport = transport
        self.hotkey = hotkey
        self.role = role
        self.id = lease_id(role)
        self.clock = clock or RealClock()
        self.epoch = 0
        self.seen = 0               # highest epoch ever observed

    # -- raw I/O -------------------------------------------------------------
    def read(self) -> dict | None:
        """Current token, or None (absent/unreadable — callers that need
        the distinction use :meth:`read_strict`)."""
        try:
            return self.read_strict()
        except Exception:
            obs.count("lease.read_errors")
            logger.warning("lease %s: read failed", self.id, exc_info=True)
            return None

    def read_strict(self) -> dict | None:
        fm = getattr(self.transport, "fetch_delta_meta", None)
        if fm is None:
            return None
        cur = parse_lease(fm(self.id))
        if cur is not None:
            self.seen = max(self.seen, cur["epoch"])
        return cur

    def _publish(self, epoch: int, base_revision: str | None) -> None:
        pm = getattr(self.transport, "publish_delta_meta", None)
        if pm is None:
            raise OSError(f"transport has no rider channel; lease "
                          f"{self.id} cannot be published")
        body = {"lease": LEASE_VERSION, "epoch": epoch,
                "holder": self.hotkey, "t": self.clock.now()}
        if base_revision:
            body["base_revision"] = base_revision
        pm(self.id, body)

    # -- protocol ------------------------------------------------------------
    def holds(self) -> bool:
        return self.epoch > 0

    def acquire(self) -> bool:
        """Claim the lease at (highest observed epoch) + 1 and verify the
        claim landed. Transport errors raise — acquiring blind against a
        store you cannot read is how two holders happen."""
        cur = self.read_strict()
        nxt = max(self.seen, cur["epoch"] if cur else 0) + 1
        self._publish(nxt, None)
        check = self.read_strict()
        if check and check["holder"] == self.hotkey \
                and check["epoch"] == nxt:
            self.epoch = nxt
            obs.count("lease.acquired")
            obs.gauge(f"{self.role}.lease_epoch", float(nxt))
            flight.record("lease", action="acquired", epoch=nxt,
                          holder=self.hotkey, role=self.role)
            logger.info("lease %s: acquired epoch %d as %s", self.id, nxt,
                        self.hotkey)
            return True
        # lost the write race: remember the winner's epoch, stay passive
        return False

    def renew(self) -> bool:
        """Confirm ownership immediately before a publish. Fail-SAFE: any
        doubt (unreadable token, higher epoch, different holder) answers
        False and the caller must not publish."""
        if self.epoch == 0:
            try:
                return self.acquire()   # lazy first acquisition (primary)
            except Exception:
                logger.warning("lease %s: lazy acquire failed", self.id,
                               exc_info=True)
                return False
        try:
            cur = self.read_strict()
        except Exception:
            obs.count("lease.read_errors")
            flight.record("lease", action="renew_failed", epoch=self.epoch,
                          holder=self.hotkey, role=self.role)
            logger.warning("lease %s: renew read failed; standing down "
                           "this round", self.id, exc_info=True)
            return False
        if cur is None:
            # token vanished (storage reset): reclaim at a fresh epoch so
            # the sequence stays monotone past whatever was seen
            try:
                return self.acquire()
            except Exception:
                return False
        if cur["epoch"] > self.epoch or (cur["epoch"] == self.epoch
                                         and cur["holder"] != self.hotkey):
            obs.count("lease.lost")
            logger.warning(
                "lease %s: superseded (held epoch %d, current epoch %d "
                "holder %s) — standing down", self.id, self.epoch,
                cur["epoch"], cur["holder"])
            # losing the lease IS the failover's forensic moment on the
            # deposed side: record + freeze, so the old primary's bundle
            # shows what it was doing when the standby took over
            flight.record("lease", action="lost", epoch=cur["epoch"],
                          holder=cur["holder"], role=self.role)
            flight.freeze_and_publish("lease_lost")
            self.epoch = 0
            return False
        try:
            self._publish(self.epoch, cur.get("base_revision"))
        except Exception:
            # the renewal write failing is survivable — ownership was
            # confirmed; the publish that follows uses the same transport
            # and will surface a real outage itself
            logger.warning("lease %s: renewal write failed", self.id,
                           exc_info=True)
        return True

    def stamp(self, base_revision: str | None) -> None:
        """Annotate the held token with the revision just published (the
        epoch the publication 'carries'). Best-effort."""
        if self.epoch == 0:
            return
        try:
            self._publish(self.epoch, base_revision)
        except Exception:
            logger.warning("lease %s: stamp failed", self.id, exc_info=True)


# ---------------------------------------------------------------------------
# The standby averager
# ---------------------------------------------------------------------------

class StandbyAverager:
    """A passive averager that takes over publication when the primary
    goes quiet.

    Follows three live signals through the transport it already has: the
    lease token (epoch + renewal timestamp), the primary's
    ``__hb__.averager.<holder>`` heartbeat sequence, and the base
    revision. POSITIVE evidence of change (a signal read successfully,
    with a new value) resets the stall clock — a read fault is "no
    evidence", never "activity", so a flaky transport cannot starve the
    takeover; ``deadline_s`` without such evidence triggers takeover — acquire the lease at the successor
    epoch, bootstrap the wrapped loop from the CURRENT published base
    (and, through the PR-5 ledger in its FleetMonitor, the fleet state),
    and run rounds actively. ``poll_once`` is the unit of progress so
    tests drive the whole lifecycle on a fake clock; :meth:`run` is the
    production loop around it."""

    def __init__(self, loop, lease: LeaseManager, *,
                 deadline_s: float = 90.0, poll_s: float = 5.0,
                 clock=None):
        from .scheduler import RealClock
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.loop = loop
        self.lease = lease
        self.deadline_s = deadline_s
        self.poll_s = poll_s
        self.clock = clock or RealClock()
        self.active = False
        self.takeovers = 0
        # last successfully-read value PER SIGNAL (None until first
        # read); _progressed mutates elements in place
        self._last_sig: list | None = None
        self._last_change: float | None = None

    # -- observation ---------------------------------------------------------
    def _signature(self) -> tuple:
        """Fingerprint of everything a LIVE primary would be advancing.
        Per-signal isolation: a partitioned read contributes a constant,
        it never aborts the watch."""
        transport = self.loop.transport
        sig = []
        lease = self.lease.read()
        sig.append((lease["epoch"], lease["t"], lease["holder"])
                   if lease else None)
        try:
            sig.append(transport.base_revision())
        except Exception:
            sig.append(None)
        holder = lease["holder"] if lease else None
        if holder and holder != self.lease.hotkey:
            try:
                hb = parse_heartbeat(transport.fetch_delta_meta(
                    heartbeat_id("averager", holder)))
                sig.append((hb["seq"], hb["t"]) if hb else None)
            except Exception:
                sig.append(None)
        else:
            sig.append(None)
        return tuple(sig)

    def stalled_for(self) -> float:
        if self._last_change is None:
            return 0.0
        return self.clock.now() - self._last_change

    def _progressed(self, sig: tuple) -> bool:
        """True when ``sig`` carries POSITIVE evidence the primary moved:
        some element read successfully AND differs from its last
        successfully-read value. A per-signal read fault degrades that
        element to None — which is "no evidence", not "activity" — so a
        flaky transport cannot keep resetting the stall clock and delay
        a needed takeover indefinitely (the fleetsim chaos runs caught
        exactly this: failover latency scaled with fetch error rate)."""
        if self._last_sig is None:
            self._last_sig = list(sig)
            return True
        moved = False
        for i, v in enumerate(sig):
            if v is not None and v != self._last_sig[i]:
                self._last_sig[i] = v
                moved = True
        return moved

    # -- the state machine ---------------------------------------------------
    def poll_once(self) -> str:
        """One watch step; returns "active" | "following" | "takeover"."""
        if self.active:
            return "active"
        now = self.clock.now()
        if self._progressed(self._signature()) \
                or self._last_change is None:
            self._last_change = now
            return "following"
        if now - self._last_change < self.deadline_s:
            return "following"
        obs.count("standby.deadline_missed")
        logger.warning(
            "standby %s: no primary activity for %.0fs (deadline %.0fs); "
            "attempting takeover", self.lease.hotkey, now - self._last_change,
            self.deadline_s)
        try:
            acquired = self.lease.acquire()
        except Exception:
            logger.warning("standby %s: takeover acquire failed; will "
                           "retry", self.lease.hotkey, exc_info=True)
            return "following"
        if not acquired:
            # someone else moved the epoch between our reads: they are the
            # new primary — restart the stall clock on their activity
            self._last_sig = None
            self._last_change = None
            return "following"
        self.takeovers += 1
        obs.count("standby.takeovers")
        logger.warning("standby %s: took over publication at epoch %d",
                       self.lease.hotkey, self.lease.epoch)
        # takeover forensics: freeze the standby's ring (what it watched
        # the primary do before the silence) and attach the bundle
        # reference to its own ledger entry, same as quarantine does
        flight.record("lease", action="takeover", epoch=self.lease.epoch,
                      holder=self.lease.hotkey, role=self.lease.role)
        ref = flight.freeze_and_publish("takeover")
        fleet = getattr(self.loop, "fleet", None)
        if ref and fleet is not None:
            try:
                fleet.node("averager", self.lease.hotkey).pm_ref = ref
            except Exception:
                logger.exception("standby: ledger pm_ref attach failed")
        # bootstrap AFTER winning the lease: pulls the current published
        # base (never a local guess), so the first active round merges
        # against exactly what the fleet last saw
        self.loop.bootstrap()
        self.active = True
        return "takeover"

    def run(self, *, interval: float = 1200.0,
            rounds: int | None = None) -> int:
        """Watch until takeover, then run the wrapped loop's rounds.
        Returns the merged-round count (0 if never activated)."""
        while not self.active:
            self.poll_once()
            if not self.active:
                self.clock.sleep(self.poll_s)
        return self.loop.run_periodic(interval=interval, rounds=rounds)
