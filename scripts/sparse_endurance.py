#!/usr/bin/env python
"""sparse8 endurance: N push/merge cycles vs an f32 twin.

Round-4 committed a parity PAIR whose "identical trajectory" was the
miner's LOCAL train loss — which the wire format cannot touch. This
harness measures what that artifact did not: the RECEIVER-side fidelity
of the published base across >= ``--rounds`` full push->merge->publish
cycles (same seeds, steps, corpus; the ONLY difference is
``--delta-dtype``).

Measured findings this harness encodes (see E2E_r05_sparse_endurance):
Adam's per-coordinate normalization makes SHORT-horizon cumulative
deltas nearly uniform in |value| — the worst case for magnitude top-k —
so the sparse fleet's base lags the f32 twin's early. But because every
push re-publishes the WHOLE cumulative delta (replace semantics,
delta.py), the truncation error cannot compound: as the cumulative
delta grows, its top-k covers an increasing share of the signal and the
gap CONTRACTS round over round. The asserted endurance property is
therefore contraction + tracking, not instant equality:

- the late-round gap must be below the early-round gap (no compounding
  divergence — the failure mode the round-4 verdict suspected),
- no round's gap may exceed the initial gap + 0.25,
- both fleets must genuinely learn across the horizon,
- the final gap must be under ``--tolerance``.

Density is a FIDELITY knob that must be calibrated per model scale
(--density; 1/64 is the 124M+ production default where vocab-row
updates concentrate; tiny byte-vocab models touch every row every step
and need 1/8).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fleet(work_dir: str, wire: str, *, rounds: int, steps: int,
           model: str, dataset: str, density: float) -> list[dict]:
    from neurons import averager, miner

    common = [
        "--backend", "local", "--work-dir", work_dir,
        "--model", model, "--dataset", dataset,
        "--tokenizer", "byte", "--batch-size", "4",
        "--seq-len", "32", "--eval-seq-len", "64",
        "--eval-batches", "2",
    ]
    per_round: list[dict] = []
    for rnd in range(rounds):
        rc = miner.main(common + [
            "--hotkey", "hotkey_0", "--max-steps", str(steps),
            "--send-interval", "1e9", "--checkpoint-interval", "1",
            "--self-eval-interval", "0",  # parity twins must train blind:
            # the guard's revert decisions would fork on rounding noise
            "--delta-dtype", wire,
            "--delta-density", str(density)])
        assert rc == 0, f"miner round {rnd} ({wire}) failed"
        rc = averager.main(common + [
            "--hotkey", "hotkey_99", "--rounds", "1",
            "--strategy", "weighted",
            # parity needs every round's merge to become the next round's
            # base in BOTH fleets — the improved-policy veto would let the
            # twins' publish histories diverge on rounding noise
            "--publish-policy", "always",
            "--metrics-path", os.path.join(work_dir, "avg.jsonl")])
        assert rc == 0, f"averager round {rnd} ({wire}) failed"
        rec = [json.loads(l) for l in open(os.path.join(work_dir,
                                                        "avg.jsonl"))]
        merged = [r for r in rec if "merged_loss" in r]
        assert merged, f"no merge metric in round {rnd} ({wire})"
        last = merged[-1]
        per_round.append({"round": rnd, "loss": last["merged_loss"],
                          "accepted": last.get("accepted")})
        assert (last.get("accepted") or 0) >= 1, (wire, rnd, last)
    return per_round


def run(work_dir: str, *, rounds: int = 12, steps: int = 40,
        model: str = "tiny",
        dataset: str = "files:/usr/share/common-licenses/*",
        density: float = 1.0 / 8.0,
        tolerance: float = 1.0, record: str | None = None) -> dict:
    t0 = time.time()
    fleets = {}
    for wire in ("float32", "sparse8"):
        d = os.path.join(work_dir, wire)
        os.makedirs(d, exist_ok=True)
        fleets[wire] = _fleet(d, wire, rounds=rounds, steps=steps,
                              model=model, dataset=dataset, density=density)

    diffs = [abs(a["loss"] - b["loss"])
             for a, b in zip(fleets["float32"], fleets["sparse8"])]
    summary = {
        "scenario": f"sparse8 endurance parity: {rounds} push/merge "
                    f"cycles x {steps} steps, {model}, single-miner twin "
                    "fleets differing ONLY in --delta-dtype",
        "rounds": rounds,
        "density": density,
        "per_round": {w: fleets[w] for w in fleets},
        "abs_loss_diff_per_round": [round(d, 4) for d in diffs],
        "max_abs_diff": round(max(diffs), 4),
        "tolerance": tolerance,
        "wall_seconds": round(time.time() - t0, 1),
    }
    assert rounds >= 4, "contraction needs >= 4 rounds (two disjoint " \
        f"early/late windows); got {rounds}"
    assert len(diffs) >= rounds, f"only {len(diffs)} of {rounds} rounds"
    k = max(2, rounds // 4)
    early = sum(diffs[:k]) / k
    late = sum(diffs[-k:]) / k
    summary["early_gap"] = round(early, 4)
    summary["late_gap"] = round(late, 4)
    summary["final_gap"] = round(diffs[-1], 4)
    summary["final_tolerance"] = summary.pop("tolerance")
    if early > 0.05:  # below the noise floor both gaps are rounding
        assert late < early, \
            (f"sparse8 gap COMPOUNDED: early {early:.3f} -> late "
             f"{late:.3f} (the round-4 verdict's suspected failure mode)")
    assert max(diffs) <= diffs[0] + 0.25, \
        (f"gap spiked mid-run: {max(diffs):.3f} vs initial {diffs[0]:.3f}")
    assert diffs[-1] <= tolerance, \
        (f"final gap {diffs[-1]:.3f} > tolerance {tolerance}")
    # both fleets must actually LEARN across the horizon (a parity of two
    # frozen fleets would prove nothing)
    for w, seq in fleets.items():
        assert seq[-1]["loss"] < seq[0]["loss"] - 0.2, (w, seq[0], seq[-1])
    summary["passed"] = True
    if record:
        with open(record, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return summary


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--work-dir", default="./sparse_endurance_run")
    p.add_argument("--rounds", type=int, default=12)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--model", default="tiny")
    p.add_argument("--dataset",
                   default="files:/usr/share/common-licenses/*")
    p.add_argument("--density", type=float, default=1.0 / 8.0,
                   help="sparse8 top-k density — a FIDELITY knob that "
                        "must scale with model size: the production 1/64 "
                        "default is calibrated at 124M+ where updates "
                        "concentrate; a tiny model's spread-out updates "
                        "need a denser wire (the parity target is "
                        "no-compounding-drift at a GIVEN fidelity)")
    p.add_argument("--tolerance", type=float, default=1.0,
                   help="max FINAL-round gap vs the f32 twin (the "
                        "primary asserts are contraction + no spike)")
    p.add_argument("--record", default=None)
    a = p.parse_args()
    run(a.work_dir, rounds=a.rounds, steps=a.steps, model=a.model,
        dataset=a.dataset, density=a.density, tolerance=a.tolerance,
        record=a.record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
