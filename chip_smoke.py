#!/usr/bin/env python
"""Chip smoke: the two end-to-end paths, once, on the TPU, in one process.

    python chip_smoke.py

1. Touches the backend once and fails unless ``jax.devices()[0]`` is a TPU.
2. Runs one fleet round at the full width of ``gpt2-124m`` (batch 8 x
   seq 1024, random weights from the seed) by calling the role mains in
   turn on a shared work dir: miner -> delta -> validator score ->
   averager merge -> published base.
3. Starts ``neurons.server.main`` on that work dir, POSTs a few
   ``/generate`` requests from a client thread, and checks one greedy
   answer token-for-token against ``engine.serve.reference_generate``.
4. Checks that the train state, the KV pool and the served params live on
   TPU devices and that the train step and every decode step that ran
   contain a Mosaic custom call (flash attention and the paged decode
   kernel are what ran, not their XLA twins).

With four or more devices the round runs over the mesh: the default miner
is data-parallel over every device and a second miner trains under
``--fsdp 2 --tp 2``; state must be laid over all of them.

Every failed check raises. The last stdout line of a passing run is one
JSON object: ``{"ok": true, "device": {"platform": "tpu", ...}}``. One
process per chip: the HTTP client is a thread, and the only child ever
started is the one-off g++ build of the native packer (no JAX in it; it
has exited before training starts).
The work dir (``.chip_smoke/``) is removed on success; the report stays in
``chiprun_out/chip_smoke_report.json``.
"""

from __future__ import annotations

import contextlib
import importlib.metadata
import json
import math
import os
import shutil
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

WORK_DIR = os.path.join(REPO, ".chip_smoke")
REPORT = os.path.join(REPO, "chiprun_out", "chip_smoke_report.json")

MOSAIC_CALL = "tpu_custom_call"


def _check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def require_tpu() -> dict:
    """The one backend touch that decides: a TPU, or exit 2 with the
    device named. No platform override is applied anywhere."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    versions = {pkg: importlib.metadata.version(pkg)
                for pkg in ("jax", "jaxlib", "libtpu")}
    print(f"chip_smoke: platform={info['platform']} "
          f"device_kind={info['kind']} count={info['count']} "
          + " ".join(f"{k}={v}" for k, v in versions.items()), flush=True)
    if info["platform"] != "tpu":
        print(f"chip_smoke: FAIL: needs a TPU, jax.devices()[0] is "
              f"{info['platform']}:{info['kind']}", file=sys.stderr)
        raise SystemExit(2)
    return {"device": info, "versions": versions}


class CompileStats:
    """Seconds spent in backend compiles (cache retrievals included) and
    persistent-cache hit/miss counts, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_seconds": round(self.seconds, 1),
                "cache_hits": self.hits, "cache_misses": self.misses}


def _placement(tree) -> dict:
    """Where a pytree's array leaves live: platforms seen, and the
    smallest and largest number of devices any leaf is laid over."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if isinstance(x, jax.Array)]
    sizes = [len(x.sharding.device_set) for x in leaves]
    return {"platforms": sorted({d.platform for x in leaves
                                 for d in x.sharding.device_set}),
            "leaves": len(leaves),
            "min_devices": min(sizes), "max_devices": max(sizes)}


def _bytes_in_use() -> list:
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()]


@contextlib.contextmanager
def _probe_train_step(role_module, out: dict):
    """Look at the train step the role main builds, at its first call:
    where the state lives and what the step lowers to. ``build`` is the
    roles' one composition seam, so wrapping the module's reference to it
    leaves the main itself untouched."""
    orig_build = role_module.build

    def build(cfg):
        c = orig_build(cfg)
        step = c.engine.train_step

        def probed(state, batch):
            if not out:
                out["params"] = _placement(state.params)
                out["opt_state"] = _placement(state.opt_state)
                out["bytes_in_use"] = _bytes_in_use()
                out["mosaic_calls"] = step.lower(
                    state, batch).as_text().count(MOSAIC_CALL)
                out["mesh"] = (dict(c.engine.mesh.shape)
                               if c.engine.mesh is not None else None)
            return step(state, batch)

        c.engine.train_step = probed
        return c

    role_module.build = build
    try:
        yield
    finally:
        role_module.build = orig_build


def _run_miner(common, work_dir, hotkey, steps, extra, *, n_devices,
               expect_kernels) -> dict:
    from neurons import miner

    metrics = os.path.join(work_dir, f"{hotkey}.jsonl")
    probe: dict = {}
    with _probe_train_step(miner, probe):
        rc = miner.main(common + [
            "--hotkey", hotkey, "--max-steps", str(steps),
            "--send-interval", "0", "--checkpoint-interval", "0",
            "--metrics-path", metrics,
            "--log-every", str(max(1, steps // 6))] + extra)
    _check(rc == 0, f"miner {hotkey} exited {rc}")
    losses = [rec["train_loss"] for rec in map(json.loads, open(metrics))
              if "train_loss" in rec]
    _check(len(losses) >= 2, f"miner {hotkey} logged {len(losses)} losses")
    _check(all(math.isfinite(x) for x in losses),
           f"miner {hotkey} loss not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"miner {hotkey} loss did not fall: {losses[0]} -> {losses[-1]}")
    delta = os.path.join(work_dir, "artifacts", "deltas", f"{hotkey}.msgpack")
    _check(os.path.exists(delta), f"no delta artifact at {delta}")
    _check(probe, f"miner {hotkey} never took a train step")
    if expect_kernels:
        _check(probe["params"]["platforms"] == ["tpu"]
               and probe["opt_state"]["platforms"] == ["tpu"],
               f"train state not on TPU: {probe}")
        _check(probe["mosaic_calls"] > 0,
               "train step holds no Mosaic custom call (flash attention "
               "did not run)")
    if n_devices >= 4:
        _check(probe["params"]["min_devices"] == n_devices
               and probe["opt_state"]["min_devices"] == n_devices,
               f"state not laid over {n_devices} devices: {probe}")
        used = probe["bytes_in_use"]
        if all(b is not None for b in used):    # CPU reports none
            _check(min(used) > 0.25 * max(used),
                   f"device memory piled up, not spread: {used}")
    return {"loss_first": losses[0], "loss_last": losses[-1],
            "delta_bytes": os.path.getsize(delta), **probe}


def fleet_round(common, work_dir, *, steps, n_devices, expect_kernels
                ) -> dict:
    """miner(s) -> validator -> averager through the role mains."""
    from neurons import averager, validator

    out = {"miners": {}}
    miners = {"hotkey_0": []}
    if n_devices >= 4:
        miners["hotkey_1"] = ["--fsdp", "2", "--tp", "2"]
        print(f"chip_smoke: mesh legs: running (default dp={n_devices}, "
              f"then fsdp=2 x tp=2)", flush=True)
    else:
        print(f"chip_smoke: mesh legs: not run ({n_devices} device)",
              flush=True)
    for hotkey, extra in miners.items():
        t0 = time.time()
        out["miners"][hotkey] = _run_miner(
            common, work_dir, hotkey, steps, extra, n_devices=n_devices,
            expect_kernels=expect_kernels)
        out["miners"][hotkey]["seconds"] = round(time.time() - t0, 1)

    t0 = time.time()
    rc = validator.main(common + ["--hotkey", "hotkey_91", "--rounds", "1"])
    _check(rc == 0, f"validator exited {rc}")
    meta = json.load(open(os.path.join(work_dir, "chain", "metagraph.json")))
    scores = meta["weights"].get("hotkey_91", {})
    for hotkey in miners:
        _check(scores.get(hotkey, 0.0) > 0,
               f"validator scored {hotkey} {scores.get(hotkey)}: {scores}")
    out["validator"] = {"scores": {h: scores[h] for h in miners},
                        "seconds": round(time.time() - t0, 1)}

    t0 = time.time()
    rc = averager.main(common + ["--hotkey", "hotkey_95", "--rounds", "1"])
    _check(rc == 0, f"averager exited {rc}")
    # the averager also publishes a genesis base at boot, so "a base
    # exists" proves nothing: the CURRENT base's lineage record must name
    # every miner's delta as a contribution
    from distributedtraining_tpu.engine import lineage
    from distributedtraining_tpu.transport import LocalFSTransport
    store = LocalFSTransport(os.path.join(work_dir, "artifacts"))
    revision = store.base_revision()
    _check(revision is not None, "averager published no base")
    record = lineage.fetch_record(store, revision)
    merged = len(record["contributions"]) if record else 0
    _check(merged == len(miners),
           f"current base {revision} merged {merged} of {len(miners)} "
           f"deltas (publish declined?)")
    out["averager"] = {"base_revision": revision, "merged_deltas": merged,
                       "seconds": round(time.time() - t0, 1)}
    return out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post_generate(port: int, body: dict, *, open_deadline: float) -> dict:
    """POST /generate, retrying only while the server's socket is not
    open yet."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    while True:
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError:
            raise
        except (ConnectionError, urllib.error.URLError):
            if time.monotonic() > open_deadline:
                raise
            time.sleep(0.05)


def serve_leg(common, work_dir, *, prompts, max_new, expect_kernels) -> dict:
    """Serve the base the averager published; answer ``prompts`` over
    HTTP; compare the first answer with the full-recompute reference."""
    import numpy as np

    from distributedtraining_tpu.engine import serve as serve_lib
    from neurons import server

    engines: list = []

    class ProbedEngine(serve_lib.GenerationEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    port = _free_port()
    answers: list = []

    def client():
        deadline = time.monotonic() + 600
        try:
            for prompt in prompts:
                answers.append(_post_generate(
                    port, {"tokens": prompt, "max_new_tokens": max_new},
                    open_deadline=deadline))
        except BaseException as e:   # handed to the main thread, re-raised
            answers.append(e)

    t0 = time.time()
    thread = threading.Thread(target=client, name="smoke-client",
                              daemon=True)
    server.GenerationEngine = ProbedEngine
    try:
        thread.start()
        # bounded: the run ends once the queue has stayed empty for
        # 2 x --swap-poll seconds after the last answer
        rc = server.main(common + [
            "--hotkey", "hotkey_97", "--serve-port", str(port),
            "--max-steps", str(len(prompts) * max_new * 4),
            "--swap-poll", "3", "--rounds", "20"])
    finally:
        server.GenerationEngine = serve_lib.GenerationEngine
    thread.join(timeout=30)
    _check(not thread.is_alive(), "client thread still running")
    _check(rc == 0, f"server exited {rc}")
    for a in answers:
        if isinstance(a, BaseException):
            raise a
    _check(len(answers) == len(prompts),
           f"{len(answers)} answers for {len(prompts)} requests")
    for prompt, a in zip(prompts, answers):
        _check(a["status"] == "done" and len(a["tokens"]) == max_new,
               f"asked {max_new} tokens for a {len(prompt)}-token prompt, "
               f"got {len(a['tokens'])} ({a['status']})")

    _check(len(engines) == 1, f"{len(engines)} engines built")
    eng = engines[0]
    _check(answers[0]["revision"] == eng.revision,
           "answer names another revision than the engine serves")
    ref = serve_lib.reference_generate(eng.model, eng._params, prompts[0],
                                       max_new)
    _check(answers[0]["tokens"] == ref,
           f"greedy answer differs from reference_generate:\n"
           f"  served    {answers[0]['tokens']}\n  reference {ref}")

    placement = {"params": _placement(eng._params),
                 "kv_pool": _placement(eng._kv)}
    buckets = {}
    # the pool is a pair of per-layer tuples (engine/kv_pool.py); `lower`
    # takes them as it takes any pytree
    k_pages, v_pages = eng._kv
    _check(placement["kv_pool"]["leaves"] == 2 * len(eng._layers),
           f"KV pool is not one K and one V array per layer: {placement}")
    for (slots, pages), prog in eng._decode_progs.items():
        text = prog.lower(
            eng._params, k_pages, v_pages,
            np.zeros((slots, pages), np.int32), np.zeros((slots,), np.int32),
            np.zeros((slots,), np.int32)).as_text()
        buckets[f"{slots}x{pages}"] = text.count(MOSAIC_CALL)
    _check(buckets, "no decode step ran")
    if expect_kernels:
        _check(placement["params"]["platforms"] == ["tpu"]
               and placement["kv_pool"]["platforms"] == ["tpu"],
               f"served params / KV pool not on TPU: {placement}")
        _check(all(n > 0 for n in buckets.values()),
               f"a decode step holds no Mosaic custom call (the paged "
               f"kernel did not run): {buckets}")
    return {"requests": len(answers), "tokens_each": max_new,
            "prompt_lens": [len(p) for p in prompts],
            "revision": eng.revision, "reference_match": True,
            "decode_mosaic_calls": buckets, **placement,
            "seconds": round(time.time() - t0, 1)}


def _packer() -> str:
    from distributedtraining_tpu import native
    return "native" if native.load("packing") is not None else "python"


def run(*, model: str, seq_len: int, eval_seq_len: int, batch_size: int,
        steps: int, prompts: list, max_new: int, work_dir: str,
        expect_kernels: bool) -> dict:
    """Both legs on a fresh ``work_dir``. ``expect_kernels`` is what a
    full-width TPU run asserts; the tiny CPU rehearsal
    (tests/test_chip_smoke.py) passes False and checks everything else."""
    import jax

    from distributedtraining_tpu.utils.platform import enable_compile_cache

    stats = CompileStats()
    cache_dir = enable_compile_cache()
    cached_before = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    common = ["--model", model, "--backend", "local", "--chain", "local",
              "--work-dir", work_dir, "--dataset", "synthetic",
              "--tokenizer", "byte", "--batch-size", str(batch_size),
              "--seq-len", str(seq_len), "--eval-seq-len", str(eval_seq_len),
              "--eval-batches", "4"]
    report = {"model": model, "batch_size": batch_size, "seq_len": seq_len,
              "packer": _packer(),
              "compile_cache": {"dir": cache_dir,
                                "entries_before": cached_before}}
    print(f"chip_smoke: packer={report['packer']} compile_cache={cache_dir} "
          f"({cached_before} entries)", flush=True)
    t0 = time.time()
    report["fleet"] = fleet_round(
        common, work_dir, steps=steps, n_devices=len(jax.devices()),
        expect_kernels=expect_kernels)
    report["fleet"]["compile"] = stats.snapshot()
    print(f"chip_smoke: fleet round ok in {time.time() - t0:.0f}s "
          f"{json.dumps(report['fleet'], default=str)}", flush=True)
    t1 = time.time()
    report["serve"] = serve_leg(common, work_dir, prompts=prompts,
                                max_new=max_new,
                                expect_kernels=expect_kernels)
    print(f"chip_smoke: serve leg ok in {time.time() - t1:.0f}s "
          f"{json.dumps(report['serve'], default=str)}", flush=True)
    report["compile"] = stats.snapshot()
    report["seconds"] = round(time.time() - t0, 1)
    shutil.rmtree(work_dir)
    return report


def main() -> int:
    t_start = time.time()
    report = require_tpu()
    # three prompt lengths: inside one KV page, across pages, and long
    # enough for a second prefill bucket
    prompts = [[(7 * i + 3) % 255 + 1 for i in range(n)]
               for n in (5, 37, 200)]
    report.update(run(
        model="gpt2-124m", seq_len=1024, eval_seq_len=512, batch_size=8,
        steps=30, prompts=prompts, max_new=16, work_dir=WORK_DIR,
        expect_kernels=True))
    report["seconds_total"] = round(time.time() - t_start, 1)
    os.makedirs(os.path.dirname(REPORT), exist_ok=True)
    with open(REPORT, "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(f"chip_smoke: compile {json.dumps(report['compile'])} "
          f"total {report['seconds_total']}s", flush=True)
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
