#!/usr/bin/env python
"""Join the fleet health plane's JSONL records into one fleet table.

The monitor roles (validator/averager with ``--heartbeat-interval``) log
three kinds of records through their ``--metrics-path`` sinks
(engine/health.py):

- ``{"heartbeat": {...}}`` — every FRESH heartbeat the FleetMonitor
  observed (role, hotkey, seq, step rate, loss EMA, push counters,
  registry digest, device memory watermark);
- ``{"fleet_ledger": {...}}`` — the per-round contribution-ledger
  snapshot (deltas published/accepted/declined, staleness in rounds,
  score, SLO breaches) — the LAST one per file wins;
- ``{"slo_breach": ...}`` — one record per breach, with detail.

plus the span/registry records every role already writes; registry
flushes are tagged ``obs_registry: <role>`` (utils/obs.py) and the last
snapshot per role lands in the report's ``registry`` section (step
timing, compile.ms, cache counters — the intra-process half of the
story). Rotated sinks (JSONLSink ``--metrics-rotate-mb``) read
transparently via obs_report.expand_segments.

Usage:
    python scripts/fleet_report.py averager.jsonl validator.jsonl
    python scripts/fleet_report.py --work-dir ./run      # globs *.jsonl
    python scripts/fleet_report.py ... --json            # machine-readable
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import obs_report  # noqa: E402 — same directory; shares record loading

COLUMNS = ("role", "tier", "hotkey", "beats", "age_s", "step_rate",
           "loss_ema", "rev", "phase", "tok_s", "ttft95", "tpot95",
           "q_age95", "slo_burn", "shed", "kv_exp", "kv_adp",
           "pfx_hit", "acc_rate", "published", "accepted", "declined",
           "stale_rounds",
           "wire_b", "base_b", "mirror_hit", "score", "credit", "quar",
           "slo")


def _human_bytes(v) -> str:
    for unit, div in (("G", 1 << 30), ("M", 1 << 20), ("k", 1 << 10)):
        if v >= div:
            return f"{v / div:.1f}{unit}"
    return str(int(v))


def build_report(paths: list[str]) -> dict:
    records = obs_report.load_records(paths)
    nodes: dict[str, dict] = {}
    registry: dict[str, dict] = {}
    devprof: dict[str, dict] = {}
    breaches: list[dict] = []
    remediations: list[dict] = []
    pruned: list[dict] = []
    heartbeats = 0
    for rec in records:
        hb = rec.get("heartbeat")
        if isinstance(hb, dict) and isinstance(hb.get("hotkey"), str):
            heartbeats += 1
            key = f"{hb.get('role', '?')}/{hb['hotkey']}"
            node = nodes.setdefault(key, {"role": hb.get("role"),
                                          "hotkey": hb["hotkey"]})
            # heartbeats arrive in file order; later seq wins
            if hb.get("seq", -1) >= node.get("seq", -1):
                node.update({k: v for k, v in hb.items() if k != "hb"})
                if isinstance(rec.get("ts"), (int, float)):
                    node["observed_ts"] = rec["ts"]
            continue
        led = rec.get("fleet_ledger")
        if isinstance(led, dict):
            for key, entry in led.items():
                if isinstance(entry, dict):
                    nodes.setdefault(key, {}).update(entry)
            continue
        if isinstance(rec.get("slo_breach"), str):
            breaches.append({k: rec.get(k) for k in
                             ("slo_breach", "role", "hotkey", "detail",
                              "round", "ts", "pm_ref")})
            continue
        if isinstance(rec.get("remediation"), str):
            # quarantine / readmission / failover actions
            # (engine/remediate.py) — the what-was-DONE half of the
            # breach records above; pm_ref points at the postmortem
            # bundle the action attached (scripts/postmortem.py)
            remediations.append({k: rec.get(k) for k in
                                 ("remediation", "hotkey", "rule",
                                  "round", "detail", "ts", "pm_ref")})
            continue
        pr = rec.get("fleet_pruned")
        if isinstance(pr, dict):
            # the node's final ledger state before it left the registry
            pruned.append(pr)
            continue
        role = rec.get("obs_registry")
        if isinstance(role, str):
            registry[role] = {k: v for k, v in rec.items()
                              if isinstance(v, (int, float))
                              and k not in ("ts", "step")}
            continue
        dp = rec.get("devprof")
        if isinstance(dp, dict) and isinstance(rec.get("role"), str):
            # device observatory snapshot (utils/devprof.py), mirrored
            # through obs.flush — the LAST one per role wins, like the
            # registry section
            devprof[rec["role"]] = dp
    # registry-digest drift: nodes whose instrumentation vocabulary
    # differs from the fleet majority (usually a version skew)
    digests = {}
    for node in nodes.values():
        d = node.get("registry_digest")
        if isinstance(d, str):
            digests[d] = digests.get(d, 0) + 1
    majority = max(digests, key=digests.get) if digests else None
    for node in nodes.values():
        d = node.get("registry_digest")
        if majority and isinstance(d, str) and d != majority:
            node["registry_drift"] = True
    return {
        "files": paths,
        "records": len(records),
        "heartbeats": heartbeats,
        "nodes": dict(sorted(nodes.items())),
        "breaches": breaches,
        "remediations": remediations,
        "pruned": pruned,
        "registry": registry,
        "devprof": devprof,
        "registry_digest_majority": majority,
    }


def _cell(node: dict, col: str) -> str:
    if col == "tier":
        # "agg" rows are sub-averager partial aggregates (__agg__.*,
        # engine/hier_average.py) — their wire_b/accepted counts describe
        # subtree aggregates, not individual miner submissions; older
        # ledgers without the field read as plain miners
        return node.get("tier") or "miner"
    if col == "age_s":
        v = node.get("last_seen_age_s")
        return "-" if v is None else f"{v:.1f}"
    if col == "rev":
        # the base revision the node is tracking — miners' train base,
        # the averager's published base, the SERVER's served revision
        # (engine/serve.py heartbeats): one column reads the
        # train -> merge -> serve lag across the fleet
        v = node.get("base_revision")
        return "-" if not isinstance(v, str) or not v else v[:10]
    if col == "phase":
        # disaggregated worker class (engine/serve.py healthz/heartbeat
        # "phase" extra): prefill | decode; unified workers and
        # non-serving roles read "-" so the column only lights up on a
        # split fleet
        v = node.get("phase")
        return v if v in ("prefill", "decode") else "-"
    if col == "tok_s":
        # serving throughput (server-role heartbeats only)
        v = node.get("tokens_per_sec")
        return "-" if v is None else f"{v:.1f}"
    if col in ("ttft95", "tpot95"):
        # request-level serving latency (server heartbeats, engine/serve
        # serve.ttft_ms / serve.tpot_ms p95): queue-admit -> first token,
        # and the per-token decode gap — what a CALLER experiences,
        # which tok_s alone cannot show
        v = node.get("ttft_ms_p95" if col == "ttft95" else "tpot_ms_p95")
        return "-" if v is None else f"{v:.1f}"
    if col == "q_age95":
        # queue-age p95 (server heartbeats, engine/serve.py observes
        # serve.queue_age_ms at ADMISSION from the request tracer's
        # submit timestamp): how long requests sat queued before a slot
        # — the leading indicator ttft95 lags by a prefill
        v = node.get("q_age_ms_p95")
        return "-" if v is None else f"{v:.1f}"
    if col == "slo_burn":
        # worst fast-window (5m/1h) SLO error-budget burn rate across
        # ttft/tpot/shed (engine/health.py BurnRateMonitor heartbeat
        # extra): >1 means the budget is burning faster than allotted;
        # the server's own multi-window rules page at 14.4x
        v = node.get("slo_burn")
        return "-" if not isinstance(v, (int, float)) else f"{v:.2f}"
    if col == "shed":
        # admission-control rejections (429 + Retry-After) this server
        # or router answered instead of queueing into the latency knee
        # (engine/serve.py admission_state / engine/router.py)
        v = node.get("shed")
        return "-" if v is None else str(int(v))
    if col in ("kv_exp", "kv_adp"):
        # disaggregated KV traffic (kv_exported / kv_adopted heartbeat
        # extras): per-request manifests a prefill worker exported, and
        # manifests a decode worker adopted — the two must both move on
        # a healthy split fleet (the fleetsim serve_phase gate's check,
        # readable per node here)
        v = node.get("kv_exported" if col == "kv_exp" else "kv_adopted")
        return "-" if v is None else str(int(v))
    if col == "pfx_hit":
        # prefix-cache hit rate: the fraction of admissions that reused
        # shared prompt-prefix KV pages (engine/serve.py PrefixCache)
        v = node.get("prefix_hit_rate")
        return "-" if not isinstance(v, (int, float)) else f"{v:.2f}"
    if col == "acc_rate":
        # speculative acceptance: fraction of drafted tokens the target
        # verified and committed (engine/speculative.py); "-" on servers
        # that are not drafting or have not verified anything yet
        v = node.get("spec_accept_rate")
        return "-" if not isinstance(v, (int, float)) else f"{v:.2f}"
    if col == "wire_b":
        # transport bytes the monitor role fetched staging this miner
        # (engine/health.py ledger) — human-scaled: the whole point of
        # the v2 wire is making this column small
        v = node.get("wire_bytes")
        return "-" if v is None else _human_bytes(v)
    if col == "base_b":
        # lifetime BASE bytes this node fetched (engine/basedist.py
        # BaseFetcher heartbeat extras) — the delta-pull twin of
        # wire_b: the whole point of the sharded base plane is making
        # this column grow by KBs per round, not model-sizes
        v = node.get("base_fetch_bytes")
        return "-" if v is None else _human_bytes(v)
    if col == "mirror_hit":
        # of the base shards this node pulled over the network, the
        # fraction a __mirror__ replica served instead of the origin
        # (base_mirror_hit_rate heartbeat extra)
        v = node.get("base_mirror_hit_rate")
        return "-" if not isinstance(v, (int, float)) else f"{v:.2f}"
    if col == "credit":
        # accumulated leave-one-out improvement credit (engine/lineage
        # CreditLedger via the ledger's credit field) — who actually
        # moved the base, not just who scored this round
        v = node.get("credit")
        return "-" if not isinstance(v, (int, float)) or v == 0 \
            else f"{v:+.4f}"
    if col == "quar":
        if node.get("quarantined"):
            return "Q"
        if node.get("probation"):
            return "P"
        return "-"
    if col == "slo":
        br = node.get("breaches") or []
        drift = ["registry_drift"] if node.get("registry_drift") else []
        return ",".join(br + drift) or "-"
    v = node.get(col)
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def format_table(rep: dict) -> str:
    rows = [[_cell(node, c) for c in COLUMNS]
            for node in rep["nodes"].values()]
    header = list(COLUMNS)
    widths = [max(len(r[i]) for r in [header] + rows) if rows
              else len(header[i]) for i in range(len(header))]
    lines = ["  ".join(h.ljust(w) for h, w in zip(header, widths)),
             "  ".join("-" * w for w in widths)]
    lines += ["  ".join(c.ljust(w) for c, w in zip(row, widths))
              for row in rows]
    lines.append("")
    lines.append(f"{rep['heartbeats']} heartbeats over "
                 f"{len(rep['nodes'])} node(s); "
                 f"{len(rep['breaches'])} SLO breach record(s)")
    for b in rep["breaches"]:
        lines.append(f"  breach: {b['slo_breach']} on "
                     f"{b.get('role')}/{b.get('hotkey')} — {b.get('detail')}")
    for r in rep.get("remediations", []):
        lines.append(f"  remediation: {r['remediation']} {r.get('hotkey')} "
                     f"({r.get('rule')}) round {r.get('round')} "
                     f"{r.get('detail') or ''}".rstrip())
    for pr in rep.get("pruned", []):
        lines.append(f"  pruned: {pr.get('role')}/{pr.get('hotkey')} "
                     f"(left the registry after {pr.get('beats')} beats)")
    # step-time anatomy (utils/devprof.py via heartbeat anat.* extras):
    # where a node's step actually goes — host-blocked vs device vs
    # data-wait — next to the throughput the table above shows
    anat_rows = [(key, node) for key, node in rep["nodes"].items()
                 if isinstance(node.get("anat.step_ms"), (int, float))]
    if anat_rows:
        lines.append("step-time anatomy (avg ms):")
        for key, node in anat_rows:
            frac = node.get("anat.device_frac")
            wait = node.get("anat.data_wait_ms")
            lines.append(
                f"  {key}: step={node['anat.step_ms']:.2f}"
                f"  device={node.get('anat.device_ms', 0.0):.2f}"
                + (f" ({frac * 100:.0f}%)" if frac is not None else "")
                + f"  host={node.get('anat.host_ms', 0.0):.2f}"
                + (f"  data_wait={wait:.2f}" if wait is not None else ""))
    for role, dp in sorted((rep.get("devprof") or {}).items()):
        progs = dp.get("programs") or []
        rl = dp.get("roofline") or {}
        top = sorted(progs, key=lambda p: -(p.get("exec_ms") or {})
                     .get("sum", 0.0))[:5]
        if top:
            lines.append(
                f"devprof[{role}] ({rl.get('device_kind', '?')}): " +
                "  ".join(
                    f"{p['prog']}[{p['bucket']}]"
                    f"={((p.get('exec_ms') or {}).get('p50') or 0.0):.2f}ms"
                    + (f"@{p['achieved_flops_frac'] * 100:.1f}%peak"
                       if p.get("achieved_flops_frac") is not None else "")
                    for p in top))
    reg = rep.get("registry") or {}
    interesting = ("miner.step_ms.p50", "miner.data_wait_ms.p50",
                   "compile.ms.count", "compile.ms.p95",
                   "ingest.cache_hits", "ingest.cache_misses",
                   "delta.densify_fallbacks",
                   "health.beats", "fleet.heartbeats",
                   "device.mem_peak_bytes",
                   "serve.tokens", "serve.tokens_per_sec",
                   "serve.step_ms.p95", "serve.ttft_ms.p95",
                   "serve.tpot_ms.p95", "serve.swap_stall_ms.p95",
                   "serve.swaps", "flight.bundles")
    for role, snap in sorted(reg.items()):
        picks = {k: snap[k] for k in interesting if k in snap}
        if picks:
            lines.append(f"registry[{role}]: " + "  ".join(
                f"{k}={v:.4g}" for k, v in picks.items()))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("files", nargs="*", help="per-role JSONL metric files")
    p.add_argument("--work-dir", default=None,
                   help="glob <work-dir>/*.jsonl instead of listing files")
    p.add_argument("--json", dest="json_out", action="store_true",
                   help="print the full report as JSON (machine-readable)")
    p.add_argument("--out", default=None,
                   help="also write the JSON report to this path")
    a = p.parse_args(argv)
    paths = list(a.files)
    if a.work_dir:
        paths += sorted(glob.glob(os.path.join(a.work_dir, "*.jsonl")))
    if not paths:
        p.error("no input files (pass JSONL paths or --work-dir)")
    rep = build_report(paths)
    if not rep["nodes"]:
        print(f"no fleet records found in {len(paths)} file(s) "
              f"({rep['records']} records total — are the monitor roles "
              "running with --heartbeat-interval and --metrics-path?)")
        return 1
    if a.json_out:
        print(json.dumps(rep, indent=1, default=float))
    else:
        print(format_table(rep))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rep, f, indent=1, default=float)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # | head et al. closing stdout is not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
