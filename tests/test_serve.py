"""Serving plane (engine/serve.py): continuous-batching generation with a
paged KV cache and hot-swapped base weights.

The correctness spine is the greedy-parity pin: every engine output must
be token-identical to ``reference_generate`` — a full model forward of
the growing sequence per token, no cache, no padding — for the pinned
prompts, before and across a hot-swap boundary. Everything else (paging,
bucket padding, preemption, swap policies, chaos degradation) is then
tested as "still token-identical under X".
"""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine.serve import (BaseRevisionWatcher,
                                                  BucketLadder,
                                                  GenerationEngine,
                                                  ServeHTTPFrontend,
                                                  ServeLoop,
                                                  host_param_template,
                                                  reference_generate)
from distributedtraining_tpu.models import gpt2, llama
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.utils import obs

# f32 keeps the argmax parity pin numerically honest (bf16 near-ties can
# flip between the cached and full-recompute spellings); serving real
# bf16 models is a throughput choice, not a correctness contract
TINY = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                       n_layer=2, n_head=2, dtype="float32",
                       vocab_multiple=64)

GEN = 8  # tokens generated per request in most tests

# the eager reference loop is the slow half of every parity pin; the
# pinned (params, prompt, n) oracles are deterministic, so share them
# across tests instead of re-deriving per test
_REF_CACHE: dict = {}


@pytest.fixture(scope="module")
def setup():
    model, cfg = gpt2.make_model(TINY)
    params1 = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    params2 = model.init_params(jax.random.PRNGKey(7), seq_len=8)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]
               for n in (5, 11, 3, 17)]
    return model, cfg, params1, params2, prompts


@pytest.fixture()
def sink():
    class _Sink:
        def __init__(self):
            self.records = []

        def log(self, rec, **kw):
            self.records.append(rec)

    s = _Sink()
    obs.configure(s, role="server")
    try:
        yield s
    finally:
        obs.reset()


def refs_for(model, params, prompts, n=GEN):
    out = []
    for p in prompts:
        key = (id(model), id(params), tuple(p), n)
        if key not in _REF_CACHE:
            _REF_CACHE[key] = reference_generate(model, params, p, n)
        out.append(_REF_CACHE[key])
    return out


# ---------------------------------------------------------------------------
# Greedy parity
# ---------------------------------------------------------------------------

def test_greedy_parity_continuous_batch(setup):
    """Mixed-length prompts decoded as one rolling batch are
    token-identical to the reference loop, per request."""
    model, cfg, params, _, prompts = setup
    # fewer slots than requests: the scheduler admits as slots free up
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params, prompts)
        assert eng.tokens_emitted == GEN * len(prompts)
    finally:
        eng.close()


def test_table_live_pct_is_the_share_of_the_bucket_that_holds_context(
        setup, sink):
    """`serve.decode.table_live_pct`, once a plain decode step: pages that
    hold context over rows x table width. A two-row bucket with one dead
    row and a half-filled four-page table reads 25."""
    model, cfg, params, _, _ = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    eng._plain_bucket = lambda ahead=0: (2, 4)
    try:
        # contexts of 10..15 tokens: two of the four pages, every step
        eng.generate([list(range(1, 11))], 5)
    finally:
        eng.close()
    hist = obs.registry().peek("serve.decode.table_live_pct")
    assert hist is not None and hist.count >= 4
    assert hist.percentiles((0.0, 100.0)) == {"p0": 25.0, "p100": 25.0}


def test_paged_equals_contiguous(setup):
    """Paged KV (small pages, gathered per step) vs a contiguous cache
    (one page holds the whole sequence): identical outputs — paging is a
    memory layout, not a math change."""
    model, cfg, params, _, prompts = setup
    paged = GenerationEngine(model, params, max_slots=2, page_size=8)
    contiguous = GenerationEngine(model, params, max_slots=2, page_size=64)
    try:
        assert contiguous.pages_per_slot == 1
        out_p = paged.generate(prompts, GEN)
        out_c = contiguous.generate(prompts, GEN)
        assert out_p == out_c == refs_for(model, params, prompts)
    finally:
        paged.close()
        contiguous.close()


def test_llama_gqa_parity():
    """The Llama path: GQA cache stores n_kv_head heads and broadcasts
    at decode; rotary positions come from the slot's sequence length."""
    cfg = llama.LlamaConfig(vocab_size=128, max_seq_len=64, n_embd=32,
                            n_layer=2, n_head=4, n_kv_head=2,
                            intermediate_size=64, remat=False,
                            dtype="float32", vocab_multiple=64)
    model, cfg = llama.make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3), seq_len=8)
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n)) for n in (4, 9)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        assert eng.generate(prompts, 6) == refs_for(model, params, prompts, 6)
        # the cache really is GQA-narrow: one array per layer, stored
        # as lane-dense [pages, P, n_kv_head * head_dim] rows
        k_pages, v_pages = eng._kv
        assert len(k_pages) == len(v_pages) == cfg.n_layer
        assert {x.shape for x in k_pages + v_pages} == {
            (eng.pool_pages, 8, cfg.n_kv_head * cfg.head_dim)}
    finally:
        eng.close()


def test_eos_stops_generation(setup):
    model, cfg, params, _, prompts = setup
    ref = reference_generate(model, params, prompts[0], GEN)
    eos = ref[0]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           eos_id=eos)
    try:
        [out] = eng.generate([prompts[0]], GEN)
        assert out == reference_generate(model, params, prompts[0], GEN,
                                         eos_id=eos)
        assert out[-1] == eos and len(out) < GEN
    finally:
        eng.close()


def test_submit_validation(setup):
    model, cfg, params, _, _ = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        with pytest.raises(ValueError):
            eng.submit([])
        with pytest.raises(ValueError):
            eng.submit(list(range(60)), max_new_tokens=20)  # > max_seq_len
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Bucket ladder / no-retrace
# ---------------------------------------------------------------------------

def test_bucket_ladder_shape():
    lad = BucketLadder(8, prefer_compiled=False)
    assert lad.buckets == (1, 2, 4, 8)
    assert [lad.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    assert lad.bucket_for(9) == 16  # beyond top: multiples of top
    lad2 = BucketLadder(8, prefer_compiled=True)
    lad2.mark(8)
    assert lad2.bucket_for(3) == 8  # pads up to the compiled bucket


def test_steady_state_zero_fresh_compiles(setup, sink):
    """The acceptance pin: after one warm batch, a second identical load
    adds ZERO fresh compiles — compile.ms count and the serve bucket
    counters stay flat (the PR-8 no-retrace discipline on the decode
    ladder)."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=4, page_size=8)
    try:
        refs = refs_for(model, params, prompts)
        assert eng.generate(prompts, GEN) == refs     # warm the ladders
        reg = obs.registry()
        before = (reg.histogram("compile.ms").count,
                  reg.counter("serve.decode_bucket_compiles").value,
                  reg.counter("serve.prefill_bucket_compiles").value)
        assert eng.generate(prompts, GEN) == refs     # steady state
        after = (reg.histogram("compile.ms").count,
                 reg.counter("serve.decode_bucket_compiles").value,
                 reg.counter("serve.prefill_bucket_compiles").value)
        assert after == before, f"steady-state decode compiled: " \
                                f"{before} -> {after}"
    finally:
        eng.close()


def test_prefer_compiled_pads_partial_batch(setup):
    """A partial batch after a full one reuses the compiled full-batch
    program (padding waste) instead of compiling the exact fit."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=4, page_size=8)
    try:
        eng.generate(prompts[:4], GEN)
        keys = set(eng._decode_progs)
        eng.generate(prompts[:2], GEN)       # 2 active: pads up to 4
        assert set(eng._decode_progs) == keys
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_drain_parity_across_boundary(setup, sink):
    """Under the drain policy a request admitted before the swap finishes
    on the revision it started on; one admitted after decodes on the new
    revision — both token-identical to their revision's reference loop,
    and each response is stamped with the revision that produced it."""
    model, cfg, params1, params2, prompts = setup
    tr = InMemoryTransport()
    rev1 = tr.publish_base(params1)
    watcher = BaseRevisionWatcher(tr, lambda: host_param_template(model),
                                  poll_s=999.0)
    assert watcher.poll_once()
    staged = watcher.take_pending()
    eng = GenerationEngine(model, watcher=watcher, max_slots=2, page_size=8,
                           swap_policy="drain")
    eng.install_params(staged[1], revision=staged[0])
    try:
        ra = eng.submit(prompts[0], GEN)
        for _ in range(3):
            eng.step()
        rev2 = tr.publish_base(params2)
        assert watcher.poll_once()           # stages the new revision
        rb = eng.submit(prompts[1], GEN)
        while not (ra.done_evt.is_set() and rb.done_evt.is_set()):
            eng.step()
        assert [ra.tokens] == refs_for(model, params1, prompts[:1])
        assert ra.revision == rev1
        assert [rb.tokens] == refs_for(model, params2, prompts[1:2])
        assert rb.revision == rev2
        reg = obs.registry()
        assert reg.counter("serve.swaps").value == 1
        # the stall the decode loop actually paused for is a pointer
        # rebind — well under one decode step
        stall = reg.histogram("serve.swap_stall_ms").percentiles((95.0,))
        step = reg.histogram("serve.step_ms").percentiles((95.0,))
        assert stall["p95"] < step["p95"]
    finally:
        eng.close()


def test_hot_swap_restart_regenerates_on_new_revision(setup):
    model, cfg, params1, params2, prompts = setup
    eng = GenerationEngine(model, params1, revision="r1", max_slots=2,
                           page_size=8, swap_policy="restart")
    try:
        req = eng.submit(prompts[0], GEN)
        for _ in range(3):
            eng.step()
        assert req.tokens  # mid-stream
        eng._pending_swap = ("r2", jax.device_put(params2))
        while not req.done_evt.is_set():
            eng.step()
        assert [req.tokens] == refs_for(model, params2, prompts[:1])
        assert req.revision == "r2"
    finally:
        eng.close()


def test_chaos_fetch_degrades_to_current_base(setup, sink):
    """A failed/torn revision fetch must degrade to the current base,
    never stall the batch: with every transport fetch failing, the
    watcher counts failures and generation proceeds bit-identically on
    the old revision."""
    from distributedtraining_tpu.transport.chaos import (ChaosSpec,
                                                         ChaosTransport)
    model, cfg, params1, params2, prompts = setup
    inner = InMemoryTransport()
    rev1 = inner.publish_base(params1)
    chaotic = ChaosTransport(inner, ChaosSpec(fetch_error_rate=1.0, seed=3),
                             role="server")
    watcher = BaseRevisionWatcher(chaotic,
                                  lambda: host_param_template(model),
                                  poll_s=999.0)
    eng = GenerationEngine(model, params1, revision=rev1, max_slots=2,
                           page_size=8, watcher=watcher)
    try:
        inner.publish_base(params2)          # a new revision exists...
        assert not watcher.poll_once()       # ...but every fetch fails
        out = eng.generate(prompts[:2], GEN)
        assert out == refs_for(model, params1, prompts[:2])
        assert eng.revision == rev1
        assert obs.registry().counter(
            "serve.swap_fetch_failures").value >= 1
        assert obs.registry().counter("serve.swaps").value == 0
    finally:
        eng.close()


def test_watcher_thread_lifecycle(setup):
    model, cfg, params1, _, _ = setup
    tr = InMemoryTransport()
    tr.publish_base(params1)
    watcher = BaseRevisionWatcher(tr, lambda: host_param_template(model),
                                  poll_s=0.01)
    watcher.start()
    try:
        import time
        deadline = time.monotonic() + 5.0
        while watcher.take_pending() is None:
            assert time.monotonic() < deadline, "watcher never staged"
            time.sleep(0.01)
    finally:
        watcher.close()


# ---------------------------------------------------------------------------
# Paging pressure
# ---------------------------------------------------------------------------

def test_preemption_under_page_pressure(setup, sink):
    """An undersized pool forces preemption; preempted requests requeue
    and regenerate identically (greedy decode is deterministic), and the
    engine records that it happened."""
    model, cfg, params, _, _ = setup
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=10))
               for _ in range(3)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           max_seq_len=32, pool_pages=6)
    try:
        assert eng.generate(prompts, 16) == refs_for(model, params,
                                                     prompts, 16)
        assert obs.registry().counter("serve.preempted").value >= 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Metrics / exporter / fleet report
# ---------------------------------------------------------------------------

def test_serve_ttft_tpot_histograms(setup, sink):
    """Request-level latency observability: TTFT (queue admit -> first
    token, one sample per finished admission) and TPOT (the wall gap
    between a slot's consecutive tokens) land as registry histograms and
    export as dt_serve_ttft_ms_* / dt_serve_tpot_ms_* gauges."""
    from distributedtraining_tpu.utils import obs_http
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=4, page_size=8)
    try:
        outs = eng.generate(prompts[:3], GEN)
        reg = obs.registry()
        ttft = reg.histogram("serve.ttft_ms")
        tpot = reg.histogram("serve.tpot_ms")
        # one TTFT sample per request; TPOT covers every non-first token
        assert ttft.count == 3
        assert tpot.count == sum(len(o) for o in outs) - 3
        assert ttft.percentiles((95.0,))["p95"] >= 0.0
        text = obs_http.render()
        assert "dt_serve_ttft_ms_p95" in text
        assert "dt_serve_tpot_ms_p95" in text
    finally:
        eng.close()


def test_fleet_report_ttft_tpot_columns(tmp_path):
    """The serving-latency heartbeat extras reach the fleet table as
    ttft95/tpot95 columns (scripts/fleet_report.py)."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import fleet_report
    path = tmp_path / "monitor.jsonl"
    path.write_text(json.dumps(
        {"heartbeat": {"hb": 1, "role": "server", "hotkey": "hk-s",
                       "seq": 3, "t": 9.0, "tokens_per_sec": 88.5,
                       "ttft_ms_p95": 41.25, "tpot_ms_p95": 7.5,
                       "steps": 100.0}}) + "\n")
    rep = fleet_report.build_report([str(path)])
    table = fleet_report.format_table(rep)
    assert "ttft95" in fleet_report.COLUMNS
    assert "tpot95" in fleet_report.COLUMNS
    assert "41.2" in table and "7.5" in table


def test_serve_metrics_reach_prometheus_exporter(setup, sink):
    from distributedtraining_tpu.utils import obs_http
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        eng.generate(prompts[:2], GEN)
        text = obs_http.render()
        for needle in ("dt_serve_tokens ", "dt_serve_step_ms_p95",
                       "dt_serve_tokens_per_sec", "dt_serve_queue_depth",
                       "dt_compile_ms_count"):
            assert needle in text, f"{needle} missing from exposition"
    finally:
        eng.close()


def test_server_heartbeat_carries_served_revision(setup):
    """The server's vitals ride the standard heartbeat schema: the
    served revision via the protocol's base_revision field, tokens/sec
    as a numeric extra — parse_heartbeat keeps both for the fleet
    ledger."""
    from distributedtraining_tpu.engine.health import (Vitals,
                                                       build_heartbeat,
                                                       parse_heartbeat)
    vit = Vitals(steps=lambda: 42.0,
                 counters=lambda: {"tokens_per_sec": 123.4,
                                   "queue_depth": 2.0},
                 base_revision=lambda: "rev-abc")
    body = build_heartbeat("server", "hk-s", 1, now=1000.0, **vit.collect())
    parsed = parse_heartbeat(body)
    assert parsed is not None
    assert parsed["base_revision"] == "rev-abc"
    assert parsed["tokens_per_sec"] == pytest.approx(123.4)
    assert parsed["role"] == "server"


def test_fleet_monitor_polls_server_heartbeats():
    """Monitor roles poll the server role alongside miners, and the
    ledger record carries the served revision + tokens/sec extras —
    the fleet table's rev/tok_s columns work from a monitor's JSONL,
    not only the server's own."""
    from distributedtraining_tpu.engine.health import (FleetMonitor,
                                                       HeartbeatPublisher,
                                                       Vitals)
    tr = InMemoryTransport()
    vit = Vitals(steps=lambda: 42.0,
                 counters=lambda: {"tokens_per_sec": 77.7,
                                   "queue_depth": 1.0},
                 base_revision=lambda: "rev-xyz")
    hb = HeartbeatPublisher(tr, "server", "hk-s", interval=999.0,
                            vitals=vit)
    try:
        hb.beat_now()
    finally:
        hb.close()
    fm = FleetMonitor(tr)
    try:
        assert "server" in fm.roles
        assert fm.poll(["hk-s"]) == 1
        rec = fm.ledger()["server/hk-s"]
        assert rec["base_revision"] == "rev-xyz"
        assert rec["tokens_per_sec"] == pytest.approx(77.7)
    finally:
        fm.close()


def test_fleet_report_serve_columns(tmp_path):
    """One CLI shows train -> merge -> serve lag: the report renders the
    rev and tok_s columns from server heartbeats."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import fleet_report
    path = tmp_path / "monitor.jsonl"
    recs = [
        {"heartbeat": {"hb": 1, "role": "server", "hotkey": "hk-s",
                       "seq": 3, "t": 9.0, "base_revision": "deadbeef01",
                       "tokens_per_sec": 88.5, "steps": 100.0}},
        {"heartbeat": {"hb": 1, "role": "miner", "hotkey": "hk-m",
                       "seq": 5, "t": 9.0, "base_revision": "deadbeef01",
                       "steps": 10.0}},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    rep = fleet_report.build_report([str(path)])
    table = fleet_report.format_table(rep)
    assert "rev" in fleet_report.COLUMNS
    assert "tok_s" in fleet_report.COLUMNS
    assert "deadbeef01"[:10] in table
    assert "88.5" in table
    server = rep["nodes"]["server/hk-s"]
    assert server["tokens_per_sec"] == pytest.approx(88.5)


# ---------------------------------------------------------------------------
# HTTP frontend + serve loop
# ---------------------------------------------------------------------------

def test_http_frontend_round_trip(setup):
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, revision="r1", max_slots=2,
                           page_size=8)
    loop = ServeLoop(eng, idle_poll_s=0.02).start()
    fe = ServeHTTPFrontend(eng, 0, timeout_s=60.0)
    port = fe.start()
    try:
        body = json.dumps({"tokens": prompts[0],
                           "max_new_tokens": 8}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        assert out["tokens"] == reference_generate(model, params,
                                                   prompts[0], 8)
        assert out["status"] == "done"
        assert out["revision"] == "r1"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            hz = json.loads(resp.read())
        assert hz["ok"] and hz["revision"] == "r1"
        # malformed request: 400, not a wedged handler
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=b'{"tokens": []}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=10)
        assert ei.value.code == 400
    finally:
        fe.close()
        loop.close()
        eng.close()


# ---------------------------------------------------------------------------
# Persistent compilation cache (ROADMAP item 5, first half)
# ---------------------------------------------------------------------------

def test_compile_cache_restart(tmp_path, setup, sink, monkeypatch):
    """The persistent compile cache, placed from OUTSIDE through
    ``JAX_COMPILATION_CACHE_DIR``: a restarted serving process re-traces
    but deserializes yesterday's executables — the cache directory gains
    NO new entries for the identical bucket programs, and decode output
    stays pinned. (In-memory jit caches are cleared to simulate the
    restart; compile.ms still counts the re-dispatches, now measuring
    cache-load cost.)"""
    from distributedtraining_tpu.utils.platform import enable_compile_cache
    model, cfg, params, _, prompts = setup
    cache_dir = str(tmp_path / "xla-cache")
    refs = refs_for(model, params, prompts[:2], 6)
    suite_dir = jax.config.jax_compilation_cache_dir
    # a process started with the variable set has it mirrored into
    # jax.config at import; this process is already running, so mirror
    # it by hand — enable_compile_cache itself sets no directory then
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    try:
        def bucket_entries():
            # the serving programs proper (incidental one-op jit_<prim>
            # helpers may come and go; they cost microseconds)
            return {f for f in os.listdir(cache_dir)
                    if f.endswith("-cache")
                    and ("jit_serve_prefill" in f
                         or "jit_serve_decode" in f)}

        assert enable_compile_cache() == cache_dir
        assert jax.config.jax_compilation_cache_dir == cache_dir
        eng = GenerationEngine(model, params, max_slots=2, page_size=8)
        assert eng.generate(prompts[:2], 6) == refs
        eng.close()
        entries = bucket_entries()
        assert entries, "persistent cache stayed empty"
        jax.clear_caches()                    # the "restart"
        reg = obs.registry()
        compiles_before = reg.histogram("compile.ms").count
        eng2 = GenerationEngine(model, params, max_slots=2, page_size=8)
        assert eng2.generate(prompts[:2], 6) == refs
        eng2.close()
        # the restarted process re-dispatched (compile.ms moved)...
        assert reg.histogram("compile.ms").count > compiles_before
        # ...but every bucket program came FROM the cache: no new
        # prefill/decode entries
        assert bucket_entries() == entries, (
            f"restart recompiled fresh bucket programs: "
            f"{sorted(bucket_entries() - entries)}")
    finally:
        # back to the suite's cache (tests/conftest.py)
        monkeypatch.undo()
        jax.config.update("jax_compilation_cache_dir", suite_dir)
        from jax._src import compilation_cache
        compilation_cache.reset_cache()


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    """Unset, every entry point lands on ONE fixed path —
    ``<repo>/.jax_cache`` — never a temp name, pid or time (a cache that
    moves never hits)."""
    from distributedtraining_tpu.utils import platform
    suite_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert platform.enable_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", suite_dir)
        from jax._src import compilation_cache
        compilation_cache.reset_cache()


def test_run_config_serving_flags():
    from distributedtraining_tpu.config import RunConfig
    cfg = RunConfig.from_args("server", [
        "--serve-port", "8123", "--serve-slots", "4", "--page-size", "8",
        "--kv-pages", "64", "--max-new-tokens", "32", "--swap-policy",
        "restart", "--swap-poll", "2.5",
        "--model", "tiny", "--backend", "memory"])
    assert cfg.role == "server"
    assert cfg.serve_port == 8123
    assert cfg.serve_slots == 4
    assert cfg.serve_page_size == 8
    assert cfg.serve_kv_pages == 64
    assert cfg.serve_max_new == 32
    assert cfg.swap_policy == "restart"
    assert cfg.swap_poll == 2.5
    # the compile cache has one placement knob and it is not a flag
    # (JAX_COMPILATION_CACHE_DIR, utils/platform.enable_compile_cache)
    assert not hasattr(cfg, "compile_cache_dir")


# ---------------------------------------------------------------------------
# Sampled decode (round 16): seeded determinism + compile discipline
# ---------------------------------------------------------------------------

SAMPLE_KW = dict(temperature=0.9, top_p=0.95, seed=42)


def test_sampled_decode_deterministic_across_runs(setup):
    """Same seed + same batch composition => bit-identical sampled
    streams across engine instances (the PRNG key is
    fold_in(PRNGKey(seed), token_index) — a pure function of the
    request, never of wall clock or slot layout)."""
    model, cfg, params, _, prompts = setup
    outs = []
    for _ in range(2):
        eng = GenerationEngine(model, params, max_slots=2, page_size=8)
        try:
            outs.append(eng.generate(prompts[:2], GEN, **SAMPLE_KW))
        finally:
            eng.close()
    assert outs[0] == outs[1]
    # and sampling actually sampled: not the greedy stream
    assert outs[0] != refs_for(model, params, prompts[:2])


def test_sampled_stream_independent_of_batch_mix(setup):
    """A request's sampled stream is identical whether its batch
    neighbors are greedy or sampled — and the greedy lane inside a
    mixed batch stays bit-identical to the reference oracle (both lanes
    run the ONE sampled program; temperature rides as data)."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        pure = eng.generate(prompts[:2], GEN, **SAMPLE_KW)
    finally:
        eng.close()
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        r_greedy = eng.submit(prompts[0], GEN)
        r_sampled = eng.submit(prompts[1], GEN, **SAMPLE_KW)
        while not (r_greedy.done_evt.is_set()
                   and r_sampled.done_evt.is_set()):
            eng.step()
        assert list(r_greedy.tokens) == refs_for(
            model, params, prompts[:1])[0]
        assert list(r_sampled.tokens) == pure[1]
    finally:
        eng.close()


def test_sampled_decode_zero_fresh_compiles(setup, sink):
    """The mixed greedy/sampled acceptance pin: after one warm mixed
    batch, an identical second wave adds ZERO fresh compiles — the
    sampled program family rides the same (slot, page) BucketLadder and
    sampling parameters are arguments, not trace constants."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=4, page_size=8)

    def wave():
        reqs = [eng.submit(p, GEN) if i % 2 == 0
                else eng.submit(p, GEN, **SAMPLE_KW)
                for i, p in enumerate(prompts)]
        while not all(r.done_evt.is_set() for r in reqs):
            eng.step()
        return [list(r.tokens) for r in reqs]

    try:
        w1 = wave()                                   # warm
        reg = obs.registry()
        before = (reg.histogram("compile.ms").count,
                  reg.counter("serve.decode_bucket_compiles").value,
                  reg.counter("serve.prefill_bucket_compiles").value)
        w2 = wave()                                   # steady state
        after = (reg.histogram("compile.ms").count,
                 reg.counter("serve.decode_bucket_compiles").value,
                 reg.counter("serve.prefill_bucket_compiles").value)
        assert after == before, \
            f"sampled steady state compiled: {before} -> {after}"
        assert w1 == w2                               # seeded determinism
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Prefix cache: shared pages, refcounts, copy-on-write
# ---------------------------------------------------------------------------

def test_prefix_cache_shared_prefill_parity(setup, sink):
    """Requests sharing a two-page system prompt reuse its cached KV
    pages (suffix-only prefill) and still decode token-identical to the
    full-recompute oracle; the cache counts hits and prefill tokens
    saved."""
    model, cfg, params, _, _ = setup
    rng = np.random.RandomState(11)
    sysp = [int(t) for t in rng.randint(0, cfg.vocab_size, size=16)]
    prompts = [sysp + [int(t) for t in rng.randint(0, cfg.vocab_size,
                                                   size=4)]
               for _ in range(3)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           prefix_cache=True, debug_invariants=True)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params,
                                                      prompts)
        assert eng.prefix_hits >= 1
        assert eng.prefix_tokens_saved >= 16
        assert obs.registry().counter("serve.prefix_hits").value >= 1
    finally:
        eng.close()


def test_prefix_cache_cow_divergent_continuations(setup):
    """Copy-on-write correctness: a shared prefix ending mid-page is
    copied before the diverging request writes into it — every
    continuation matches its unshared reference exactly (a stronger pin
    than the 1e-6 budget), and the engine actually took the CoW path."""
    model, cfg, params, _, _ = setup
    rng = np.random.RandomState(13)
    # 12 shared tokens = 1 full page + half a page on page_size=8:
    # the second admission's suffix starts mid-page => admit-time CoW
    sysp = [int(t) for t in rng.randint(0, cfg.vocab_size, size=12)]
    prompts = [sysp + [int(t) for t in rng.randint(0, cfg.vocab_size,
                                                   size=5)]
               for _ in range(2)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           prefix_cache=True, debug_invariants=True)
    try:
        assert eng.generate(prompts, GEN) == refs_for(model, params,
                                                      prompts)
        assert eng.cow_copies >= 1
    finally:
        eng.close()


def test_page_pool_invariant_preempt_readmit_exhaustion(setup, sink):
    """The round-16 accounting regression: preempted-then-readmitted
    slots release and re-acquire pages through the refcount discipline.
    ``debug_invariants`` audits free + referenced == total (with exact
    per-holder refcounts) after EVERY step, through preemption,
    readmission, and pool exhaustion, with the prefix cache holding
    references of its own."""
    model, cfg, params, _, _ = setup
    rng = np.random.RandomState(17)
    sysp = [int(t) for t in rng.randint(0, cfg.vocab_size, size=8)]
    prompts = [sysp + [int(t) for t in rng.randint(0, cfg.vocab_size,
                                                   size=2 + i)]
               for i in range(3)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           max_seq_len=32, pool_pages=7,
                           prefix_cache=True, debug_invariants=True)
    try:
        assert eng.generate(prompts, 16) == refs_for(model, params,
                                                     prompts, 16)
        assert obs.registry().counter("serve.preempted").value >= 1
        eng._check_invariants()
    finally:
        eng.close()


def test_page_pool_check_catches_drift():
    """PagePool.check is a real audit: a refcount the engine cannot
    explain fails loudly."""
    from distributedtraining_tpu.engine.serve import PagePool
    pool = PagePool(5)
    pages = pool.alloc(2)
    pool.check({pages[0]: 1, pages[1]: 1})       # honest books balance
    pool.incref(pages[0])
    with pytest.raises(AssertionError):
        pool.check({pages[0]: 1, pages[1]: 1})   # drifted books do not
    pool.decref(pages[0])
    pool.decref(pages[0])
    pool.decref(pages[1])
    pool.check({})


# ---------------------------------------------------------------------------
# HTTP admission control: 429 on shed, 503 on drain
# ---------------------------------------------------------------------------

def test_http_shed_429_with_retry_after(setup):
    """Past --max-queue the frontend sheds with 429 + Retry-After
    instead of queueing the caller into the latency knee."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           max_queue=1)
    fe = ServeHTTPFrontend(eng, 0, timeout_s=30.0)
    port = fe.start()
    try:
        eng.submit(prompts[0], 4)        # no loop running: stays queued
        body = json.dumps({"tokens": prompts[1]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert eng.shed_count == 1
    finally:
        fe.close()
        eng.close()


def test_http_drain_503_during_swap(setup):
    """While a drain-policy swap waits on in-flight sequences, new HTTP
    requests get 503 + Retry-After (come back on the new revision), not
    an indefinite queue slot."""
    model, cfg, params, params2, prompts = setup
    eng = GenerationEngine(model, params, revision="r1", max_slots=2,
                           page_size=8, swap_policy="drain")
    fe = ServeHTTPFrontend(eng, 0, timeout_s=30.0)
    port = fe.start()
    try:
        eng.submit(prompts[0], GEN)
        eng.step()                       # admit: one sequence in flight
        eng._pending_swap = ("r2", jax.device_put(params2))
        body = json.dumps({"tokens": prompts[1]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
    finally:
        fe.close()
        eng.close()


def test_http_sampling_params_round_trip(setup):
    """temperature/top_p/seed ride the POST body; the same seed returns
    the same stream on a second identical request."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    loop = ServeLoop(eng, idle_poll_s=0.02).start()
    fe = ServeHTTPFrontend(eng, 0, timeout_s=60.0)
    port = fe.start()
    try:
        body = json.dumps({"tokens": prompts[0], "max_new_tokens": 8,
                           "temperature": 0.9, "top_p": 0.95,
                           "seed": 7}).encode()
        outs = []
        for _ in range(2):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                outs.append(json.loads(resp.read())["tokens"])
        assert outs[0] == outs[1]
        assert outs[0] != reference_generate(model, params, prompts[0], 8)
    finally:
        fe.close()
        loop.close()
        eng.close()


def test_prefix_cache_flushed_on_hot_swap(setup, sink):
    """A base-revision swap invalidates the prefix cache: cached KV is a
    function of the params that produced it, so post-swap shared-prefix
    requests must re-prefill under the NEW params and match the new
    revision's oracle exactly — never reuse revision-1 pages."""
    model, cfg, params1, params2, _ = setup
    rng = np.random.RandomState(17)
    sysp = [int(t) for t in rng.randint(0, cfg.vocab_size, size=16)]
    prompts = [sysp + [int(t) for t in rng.randint(0, cfg.vocab_size,
                                                   size=4)]
               for _ in range(2)]
    eng = GenerationEngine(model, params1, revision="r1", max_slots=2,
                           page_size=8, prefix_cache=True,
                           debug_invariants=True)
    try:
        # warm the cache under params1 (second request hits the prefix)
        assert eng.generate(prompts, GEN) == refs_for(model, params1,
                                                      prompts)
        assert eng.prefix_hits >= 1
        assert len(eng._cache) > 0
        eng._pending_swap = ("r2", jax.device_put(params2))
        eng.step()                          # idle engine: swap lands now
        assert eng.revision == "r2"
        assert len(eng._cache) == 0         # stale entries flushed...
        assert obs.registry().counter("serve.prefix_flushes").value == 1
        # ...and their pool references released (books still balance)
        eng._check_invariants()
        # the same shared-prefix traffic now decodes on params2 exactly
        assert eng.generate(prompts, GEN) == refs_for(model, params2,
                                                      prompts)
        assert eng.prefix_hits >= 2         # cache rebuilt and hit again
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Phase spans, emit stamps and program names (ISSUE 25)
# ---------------------------------------------------------------------------

STEP_PHASES = ("serve.step", "serve.admit", "serve.prefill", "serve.grow",
               "serve.decode.build", "serve.decode.dispatch",
               "serve.decode.fetch", "serve.decode.emit")


def test_step_phases_fill_their_histograms_and_nest(setup, sink):
    """With obs on, a step() that admits feeds the admission's phases and
    the decode program's build and dispatch; the program's fetch and emit
    are the NEXT step's (the engine returns one program ahead), beside
    that step's own build and dispatch. The children lie inside
    `serve.step`, the prefill inside `serve.admit`; nothing is written to
    the sink per close."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        eng.submit(prompts[0], GEN)
        eng.step()
        reg = obs.registry()

        def counts():
            return {p: getattr(reg.peek(f"{p}_ms"), "count", 0)
                    for p in STEP_PHASES}

        later = ("serve.decode.fetch", "serve.decode.emit")
        assert counts() == {p: int(p not in later) for p in STEP_PHASES}
        eng.step()
        once = ("serve.prefill",) + later
        assert counts() == {p: 1 if p in once else 2 for p in STEP_PHASES}
        total = {p: reg.peek(f"{p}_ms").total for p in STEP_PHASES}
        inside = sum(total[p] for p in STEP_PHASES
                     if p not in ("serve.step", "serve.prefill"))
        assert total["serve.step"] >= inside
        assert total["serve.admit"] >= total["serve.prefill"]
        assert not [r for r in sink.records if "span" in r]
        # the per-token twin of serve.step_ms is gone; `serve.tokens`, the
        # counter, is not it
        assert not [n for n in reg.names() if n.startswith("serve.token_")]
    finally:
        eng.close()


def test_prefill_ms_is_timed_to_completion(setup, sink):
    """`serve.prefill_ms` (and the request trace's prefill stage) end
    after the first token reached the host, not after the dispatch: a
    prefill whose result takes 50 ms to arrive reads at least 50 ms."""
    import time as _time
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    block_s = 0.05

    class _Late:
        """The prefill's first token, arriving `block_s` after the
        dispatch returned (what `int()` of a device array waits for)."""

        def __init__(self, value):
            self.value = value

        def __int__(self):
            _time.sleep(block_s)
            return int(self.value)

    real = eng._prefill_prog

    def late_prog(t_bucket):
        prog = real(t_bucket)

        def run(*args):
            nxt, row, k_pages, v_pages = prog(*args)
            return _Late(nxt), row, k_pages, v_pages
        return run

    eng._prefill_prog = late_prog
    try:
        req = eng.submit(prompts[0], GEN)
        eng.step()
        h = obs.registry().peek("serve.prefill_ms")
        assert h.count == 1 and h.total >= block_s * 1e3
        stage = [s for s in eng.trace.get(req.rid).stages
                 if s["stage"] == "prefill"]
        assert stage and stage[0]["dur_ms"] >= block_s * 1e3
    finally:
        eng.close()


def test_emit_stamps_one_per_token_and_one_token_a_step(setup):
    """Always on, obs or not: the step() that prefills a request hands its
    caller the first token and returns with the decode program in flight;
    every later step() hands over one more, each with its own stamp, and a
    finished request has one non-decreasing stamp per token on the clock
    `submitted_pc` was taken from."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        req = eng.submit(prompts[1], GEN)
        eng.step()
        assert len(req.tokens) == 1 and len(req.emit_t) == 1
        assert not eng.idle and eng._flight is not None
        eng.step()
        assert len(req.tokens) == 2 and len(req.emit_t) == 2
        assert req.submitted_pc <= req.emit_t[0] < req.emit_t[1]
        while not req.done_evt.is_set():
            eng.step()
        assert len(req.emit_t) == len(req.tokens) == GEN
        assert req.emit_t == sorted(req.emit_t)
        assert eng.idle
    finally:
        eng.close()


def test_requeue_clears_the_emit_stamps(setup):
    """A preempted request regenerates from its prompt: its stamps start
    over with its tokens."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        req = eng.submit(prompts[0], GEN)
        eng.step()
        assert req.emit_t
        assert eng._preempt_one()
        assert req.tokens == [] and req.emit_t == []
        while not req.done_evt.is_set():
            eng.step()
        assert len(req.emit_t) == len(req.tokens) == GEN
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["serve_decode", "serve_prefill"])
def test_programs_carry_stable_names(setup, family):
    """The device's `XLA Modules` line shows `jit_<function>`: the jitted
    functions are named after devprof's vocabulary."""
    model, cfg, params, _, prompts = setup
    eng = GenerationEngine(model, params, max_slots=2, page_size=8)
    try:
        k_pages, v_pages = eng._kv
        if family == "serve_decode":
            lowered = eng._decode_prog(2, 2).lower(
                eng._params, k_pages, v_pages, np.zeros((2, 2), np.int32),
                np.zeros((2,), np.int32), np.zeros((2,), np.int32))
        else:
            lowered = eng._prefill_prog(8).lower(
                eng._params, np.zeros((1, 8), np.int32), np.int32(3),
                k_pages, v_pages, np.zeros((1,), np.int32))
        assert f"@jit_{family} " in lowered.as_text()
    finally:
        eng.close()


def test_spans_carry_the_request_and_the_shape(setup, sink, monkeypatch):
    """`serve.prefill` names its request (the id request traces key on)
    and its token bucket, a shared-prefix prefill its context pages too;
    `serve.decode.dispatch` names the program's bucket and the live slot
    count. Each prefill, full or shared, feeds `serve.prefill_ms` and
    hands its time to the request trace's stage."""
    made = []

    class _Annotation:
        def __init__(self, name, **args):
            made.append((name, args))

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(obs, "_trace_annotation", lambda: _Annotation)
    model, cfg, params, _, _ = setup
    rng = np.random.RandomState(11)
    sysp = [int(t) for t in rng.randint(0, cfg.vocab_size, size=16)]
    prompts = [sysp + [int(t) for t in rng.randint(0, cfg.vocab_size,
                                                   size=4)]
               for _ in range(2)]
    eng = GenerationEngine(model, params, max_slots=2, page_size=8,
                           prefix_cache=True)
    try:
        full = eng.submit(prompts[0], GEN)
        eng.step()
        shared = eng.submit(prompts[1], GEN)
        eng.step()
        assert eng.prefix_hits == 1
        prefills = [a for n, a in made if n == "serve.prefill"]
        assert prefills == [{"rid": full.rid, "bucket": 32},
                            {"rid": shared.rid, "bucket": 8,
                             "ctx_pages": 4}]
        dispatches = [a for n, a in made if n == "serve.decode.dispatch"]
        assert dispatches[0] == {"slots": 1, "pages": 4, "live": 1}
        assert dispatches[1]["live"] == 2
        assert obs.registry().peek("serve.prefill_ms").count == 2
        for req in (full, shared):
            stage = [s for s in eng.trace.get(req.rid).stages
                     if s["stage"] == "prefill"]
            assert len(stage) == 1 and stage[0]["dur_ms"] > 0.0
    finally:
        eng.close()
