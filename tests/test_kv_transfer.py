"""Disaggregated prefill/decode serving (engine/kv_transfer.py + the
phase-specialized engine/serve.py workers + the phase-aware router).

The correctness spine is CROSS-WORKER IDENTITY: a request prefilled on
worker A (phase="prefill", KV pages exported as content-addressed
shards + a manifest-last per-request manifest) and decoded on worker B
(phase="decode", pages adopted into B's own PagePool) must produce
exactly what the unified engine produces — token-identical for greedy
lanes, BIT-identical for sampled lanes (the counter PRNG makes token
index, not worker, the stream coordinate), and still identical with a
speculative drafter on the decode side (losslessness composes with
adoption). Everything else — torn manifests, hash misses, base-revision
skew, pool accounting, the router's two-leg hop — is then tested as
"still identical, with the degrade counted".
"""

import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine import kv_transfer as kvt
from distributedtraining_tpu.engine.router import (RouterHTTPFrontend,
                                                   RouterPolicy)
from distributedtraining_tpu.engine.serve import (GenerationEngine,
                                                  ServeHTTPFrontend,
                                                  ServeLoop,
                                                  reference_generate)
from distributedtraining_tpu.engine.speculative import DraftEngine
from distributedtraining_tpu.models import gpt2
from distributedtraining_tpu.transport import InMemoryTransport
from distributedtraining_tpu.transport import base as tbase
from distributedtraining_tpu.utils import obs

TINY = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                       n_layer=2, n_head=2, dtype="float32",
                       vocab_multiple=64)

GEN = 8

_REF_CACHE: dict = {}


@pytest.fixture(scope="module")
def setup():
    model, cfg = gpt2.make_model(TINY)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]
               for n in (5, 11, 3, 17)]
    return model, cfg, params, prompts


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


def disagg_pair(model, params, *, revision="r1", decode_revision=None,
                transport=None, **dec_kw):
    """One prefill worker + one decode worker over a shared transport."""
    tr = transport if transport is not None else InMemoryTransport()
    pe = GenerationEngine(model, params, revision=revision, max_slots=4,
                          page_size=8, phase="prefill",
                          kv_exporter=kvt.KVExporter(tr))
    de = GenerationEngine(model, params,
                          revision=decode_revision or revision,
                          max_slots=4, page_size=8, phase="decode",
                          kv_adopter=kvt.KVAdopter(tr), **dec_kw)
    return tr, pe, de


def drain(eng, reqs):
    while not all(r.done_evt.is_set() for r in reqs):
        eng.step()
    return [list(r.tokens) for r in reqs]


def hop(pe, de, prompts, n=GEN, *, sampling=None):
    """Run the disaggregated two-leg path: prefill on ``pe``, hand the
    (kv_ref, first_token) pair to ``de``, return the decode outputs."""
    kw = dict(sampling or {})
    pre = [pe.submit(p, n, request_id=f"rq-hop-{i}", **kw)
           for i, p in enumerate(prompts)]
    drain(pe, pre)
    dec = [de.submit(p, n, kv_ref=r.kv_ref, first_token=r.first_token,
                     **kw)
           for p, r in zip(prompts, pre)]
    return pre, drain(de, dec)


# ---------------------------------------------------------------------------
# Wire codecs (pure)
# ---------------------------------------------------------------------------

def test_page_codec_roundtrip_and_rejects():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 8, 2, 16), dtype=np.float32)
    v = rng.standard_normal((2, 8, 2, 16), dtype=np.float32)
    data = kvt.pack_kv_page(k, v)
    out = kvt.unpack_kv_page(data)
    assert out is not None
    np.testing.assert_array_equal(out[0], k)
    np.testing.assert_array_equal(out[1], v)
    # every defect degrades to None, never raises
    assert kvt.unpack_kv_page(b"not msgpack") is None
    assert kvt.unpack_kv_page(data, max_bytes=16) is None
    skew = kvt.pack_kv_page(k, v[:, :4])          # K/V shape skew
    assert kvt.unpack_kv_page(skew) is None
    assert kvt.unpack_kv_page(
        kvt.pack_kv_page(k[0], v[0])) is None     # wrong rank


def test_manifest_codec_roundtrip_and_rejects():
    geom = {"layers": 2, "page_size": 8, "kv_heads": 2, "head_dim": 16,
            "dtype": "float32"}
    digest = "ab" * 32
    data = kvt.build_kv_manifest(request_id="rq-1", revision="r1",
                                 pages=[(digest, 128)], geometry=geom,
                                 prompt_len=5, first_token=7)
    man = kvt.parse_kv_manifest(data)
    assert man == {"request_id": "rq-1", "revision": "r1",
                   "prompt_len": 5, "first_token": 7, "geometry": geom,
                   "pages": [(digest, 128)]}
    # defensive reader: bad magic, truncation, tampered digest, zero
    # pages — all degrade to None (no transfer), never raise
    assert kvt.parse_kv_manifest(b"XX" + data[2:]) is None
    assert kvt.parse_kv_manifest(data[:-3]) is None
    assert kvt.parse_kv_manifest(
        data.replace(digest.encode(), b"zz" * 32)) is None
    bad = json.loads(data[len(kvt.KV_MANIFEST_MAGIC):])
    bad["pages"] = []
    assert kvt.parse_kv_manifest(
        kvt.KV_MANIFEST_MAGIC + json.dumps(bad).encode()) is None


# ---------------------------------------------------------------------------
# Cross-worker identity
# ---------------------------------------------------------------------------

def test_greedy_cross_worker_parity_and_pool_audit(setup, sink):
    """Prefill on A, decode on B: token-identical to the unified
    reference, with the page-pool conservation invariant audited every
    decode step (debug_invariants) and all pages returned to the free
    list when the batch drains."""
    model, cfg, params, prompts = setup
    tr, pe, de = disagg_pair(model, params, debug_invariants=True)
    try:
        pre, out = hop(pe, de, prompts)
        assert out == refs_for(model, params, prompts)
        assert pe.kv_exported == len(prompts)
        assert de.kv_adopted == len(prompts)
        assert de.kv_reprefills == 0
        # prefill legs finish as "prefilled" carrying the handoff pair
        assert all(r.status == "prefilled" and r.kv_ref
                   and r.first_token is not None for r in pre)
        # every adopted page came back: free + referenced tiles the pool
        de.pool.check({})
        assert de.pool.free == de.pool.total
    finally:
        pe.close()
        de.close()


def test_sampled_cross_worker_bit_identity(setup, sink):
    """Sampled lanes survive the worker hop BIT-identically: the
    counter PRNG is a pure function of (seed, token index), so the
    prefill worker's index-0 draw plus the decode worker's index-1..N
    draws reconstruct the unified engine's stream draw-for-draw."""
    model, cfg, params, prompts = setup
    sampling = {"temperature": 0.8, "top_p": 0.9, "seed": 23}
    uni = GenerationEngine(model, params, revision="r1", max_slots=4,
                           page_size=8)
    try:
        ref = uni.generate(prompts, GEN, **sampling)
    finally:
        uni.close()
    tr, pe, de = disagg_pair(model, params)
    try:
        _, out = hop(pe, de, prompts, sampling=sampling)
        assert out == ref
        assert de.kv_adopted == len(prompts)
    finally:
        pe.close()
        de.close()


def test_speculative_decode_on_adopted_pages(setup, sink):
    """Losslessness composes with adoption: a decode worker running
    draft-and-verify over ADOPTED pages (self-draft: acceptance 1.0)
    still produces the unified greedy output."""
    model, cfg, params, prompts = setup
    tr, pe, de = disagg_pair(
        model, params, debug_invariants=True, draft_k=4,
        draft=DraftEngine(model, params, max_slots=4, page_size=8))
    try:
        _, out = hop(pe, de, prompts)
        assert out == refs_for(model, params, prompts)
        assert de.kv_adopted == len(prompts)
        assert de.spec_accept_rate == pytest.approx(1.0)
    finally:
        pe.close()
        de.close()


def test_zero_steady_state_fresh_compiles(setup, sink):
    """Two identical waves of exports and adoptions, greedy and sampled
    lanes mixed: the second adds ZERO fresh compiles on either worker
    class (the adopt program compiled once in wave 1, the bucket ladders
    are warm after it), every request is still adopted, and the output
    is unchanged."""
    model, cfg, params, prompts = setup
    tr, pe, de = disagg_pair(model, params)

    def wave():
        return [hop(pe, de, prompts, sampling=sampling)[1]
                for sampling in (None, {"temperature": 0.8, "top_p": 0.9,
                                        "seed": 23})]

    try:
        first = wave()
        reg = obs.registry()
        before = reg.histogram("compile.ms").count
        assert before > 0
        assert wave() == first
        assert reg.histogram("compile.ms").count == before
        assert de.kv_adopted == 4 * len(prompts) and de.kv_reprefills == 0
    finally:
        pe.close()
        de.close()


# ---------------------------------------------------------------------------
# Degrades (every defect -> local prefill, counted, output-identical)
# ---------------------------------------------------------------------------

def test_transfer_defects_degrade_to_local_prefill(setup, sink):
    """Absent manifest, torn manifest bytes, and a corrupted page shard
    all degrade identically: the decode worker prefills locally,
    counts the re-prefill, and the output stays reference-identical."""
    model, cfg, params, prompts = setup
    tr, pe, de = disagg_pair(model, params)
    ref = refs_for(model, params, prompts[:1])
    try:
        # 1) absent manifest: the prefill leg never published
        r = de.submit(prompts[0], GEN, kv_ref="rq-never-published",
                      first_token=ref[0][0])
        assert drain(de, [r]) == ref
        assert de.kv_reprefills == 1 and de.kv_adopted == 0

        # 2) torn manifest: shards landed, the manifest write tore
        pre = [pe.submit(prompts[0], GEN, request_id="rq-torn")]
        drain(pe, pre)
        tbase.publish_kv_manifest(tr, "rq-torn", b"DTKV1\n{torn")
        r = de.submit(prompts[0], GEN, kv_ref="rq-torn",
                      first_token=pre[0].first_token)
        assert drain(de, [r]) == ref
        assert de.kv_reprefills == 2 and de.kv_adopted == 0

        # 3) hash miss: a shard the manifest pins serves wrong bytes
        pre = [pe.submit(prompts[0], GEN, request_id="rq-badpage")]
        drain(pe, pre)
        man = kvt.parse_kv_manifest(
            tbase.fetch_kv_manifest_bytes(tr, "rq-badpage"))
        digest = man["pages"][0][0]
        tr._deltas[tbase.kv_page_id(digest)] = \
            b"\x00" * man["pages"][0][1]
        r = de.submit(prompts[0], GEN, kv_ref="rq-badpage",
                      first_token=pre[0].first_token)
        assert drain(de, [r]) == ref
        assert de.kv_reprefills == 3 and de.kv_adopted == 0
        reg = obs.registry()
        assert reg.counter("serve.kv_reprefills").value == 3
        assert reg.counter("serve.kv_page_rejects").value >= 1
    finally:
        pe.close()
        de.close()


def test_revision_mismatch_refuses_adoption(setup, sink):
    """KV is a pure function of (params, tokens): pages prefilled on
    another base revision are refused LOUDLY — counted distinctly from
    transfer faults — and the request re-prefills on the decode
    worker's own revision, so the output matches ITS base."""
    model, cfg, params, prompts = setup
    tr, pe, de = disagg_pair(model, params, revision="r1",
                             decode_revision="r2")
    try:
        _, out = hop(pe, de, prompts[:2])
        assert out == refs_for(model, params, prompts[:2])
        assert de.kv_rev_mismatch == 2
        assert de.kv_reprefills == 2
        assert de.kv_adopted == 0
        assert obs.registry().counter("serve.kv_rev_mismatch").value == 2
    finally:
        pe.close()
        de.close()


def test_shared_prefix_dedupes_wire_bytes(setup, sink):
    """Content addressing pays: two prompts sharing a full-page prefix
    export bit-identical pages, so the second request's shards are
    publish no-ops and the adopter serves them from its page store
    without touching the wire."""
    model, cfg, params, _ = setup
    shared = [int(t) for t in
              np.random.RandomState(5).randint(0, cfg.vocab_size, 16)]
    pair = [shared + [3], shared + [9]]
    tr, pe, de = disagg_pair(model, params)
    try:
        _, out = hop(pe, de, pair)
        assert out == refs_for(model, params, pair)
        deduped = obs.registry().counter("serve.kv_pages_deduped").value
        # two full 8-token pages of shared prefix, deduped on BOTH the
        # export side (publish ledger) and the adopt side (page store)
        assert deduped >= 4
    finally:
        pe.close()
        de.close()


# ---------------------------------------------------------------------------
# Mixed fleet through the phase-aware router
# ---------------------------------------------------------------------------

@pytest.fixture()
def mixed_fleet(setup):
    """One unified + one prefill + one decode backend (shared KV
    transport), each behind a live HTTP frontend."""
    model, cfg, params, prompts = setup
    tr = InMemoryTransport()
    specs = [
        {"phase": "unified"},
        {"phase": "prefill", "kv_exporter": kvt.KVExporter(tr)},
        {"phase": "decode", "kv_adopter": kvt.KVAdopter(tr)},
    ]
    engines, loops, fes, urls = [], [], [], []
    for kw in specs:
        eng = GenerationEngine(model, params, revision="r1", max_slots=2,
                               page_size=8, **kw)
        loop = ServeLoop(eng, idle_poll_s=0.02).start()
        fe = ServeHTTPFrontend(eng, 0, timeout_s=60.0)
        urls.append(f"http://127.0.0.1:{fe.start()}")
        engines.append(eng)
        loops.append(loop)
        fes.append(fe)
    try:
        yield model, params, engines, urls
    finally:
        for fe in fes:
            fe.close()
        for loop in loops:
            loop.close()
        for eng in engines:
            eng.close()


def test_router_two_leg_disaggregated_route(mixed_fleet, sink):
    """The router learns worker classes from /healthz, routes the
    prefill leg to the prefill worker and the decode leg (kv_ref +
    first_token) to the decode worker, and the spliced output is
    reference-identical."""
    model, params, engines, urls = mixed_fleet
    router = RouterHTTPFrontend(urls, 0, poll_interval_s=30.0,
                                timeout_s=60.0)
    router.refresh()
    port = router.start()
    try:
        assert sorted(b.phase for b in router.backends) == \
            ["decode", "prefill", "unified"]
        prompt = [3, 1, 4, 1, 5]
        body = json.dumps({"tokens": prompt,
                           "max_new_tokens": 6}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        assert out["tokens"] == reference_generate(model, params,
                                                   prompt, 6)
        assert router.disagg_routed == 1
        assert engines[1].kv_exported == 1   # prefill worker
        assert engines[2].kv_adopted == 1    # decode worker
        # the fleet view names each worker's class
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            hz = json.loads(resp.read())
        assert sorted(b["phase"] for b in hz["backends"]) == \
            ["decode", "prefill", "unified"]
    finally:
        router.close()


def test_router_excludes_prefill_workers_from_unified_fallback(
        mixed_fleet, sink):
    """With the decode worker gone the two-leg route is impossible; the
    router falls back to the UNIFIED pool only — a prefill-phase worker
    cannot serve /generate (409 by phase discipline), so it must never
    be in the fallback set."""
    model, params, engines, urls = mixed_fleet
    router = RouterHTTPFrontend(urls[:2], 0, poll_interval_s=30.0,
                                timeout_s=60.0)   # unified + prefill only
    router.refresh()
    port = router.start()
    try:
        prompt = [2, 7, 1, 8]
        body = json.dumps({"tokens": prompt,
                           "max_new_tokens": 6}).encode()
        for _ in range(3):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                out = json.loads(resp.read())
            assert out["tokens"] == reference_generate(model, params,
                                                       prompt, 6)
        assert router.disagg_routed == 0
        assert engines[1].kv_exported == 0   # prefill worker never hit
        assert engines[0].tokens_emitted >= 18
    finally:
        router.close()


# ---------------------------------------------------------------------------
# Fleet surfaces: report columns for the disaggregated plane
# ---------------------------------------------------------------------------

def test_fleet_report_phase_and_kv_columns(tmp_path):
    """One fleet table answers "do both worker classes exist AND is KV
    moving between them": the phase / kv_exp / kv_adp columns render
    from disaggregated server heartbeats, and unified rows show '-'."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import fleet_report
    path = tmp_path / "monitor.jsonl"
    recs = [
        {"heartbeat": {"hb": 1, "role": "server", "hotkey": "hk-pre",
                       "seq": 2, "t": 9.0, "phase": "prefill",
                       "kv_exported": 41, "kv_adopted": 0}},
        {"heartbeat": {"hb": 1, "role": "server", "hotkey": "hk-dec",
                       "seq": 2, "t": 9.0, "phase": "decode",
                       "kv_exported": 0, "kv_adopted": 37}},
        {"heartbeat": {"hb": 1, "role": "server", "hotkey": "hk-uni",
                       "seq": 2, "t": 9.0, "tokens_per_sec": 12.5}},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    for col in ("phase", "kv_exp", "kv_adp"):
        assert col in fleet_report.COLUMNS
    rep = fleet_report.build_report([str(path)])
    table = fleet_report.format_table(rep)
    assert "prefill" in table and "decode" in table
    assert "41" in table and "37" in table
    pre = rep["nodes"]["server/hk-pre"]
    assert pre["phase"] == "prefill" and pre["kv_exported"] == 41
    # a unified server's row renders '-' in every disagg column
    uni_row = next(ln for ln in table.splitlines() if "hk-uni" in ln)
    assert "prefill" not in uni_row and "decode" not in uni_row
