"""Delta wire v2: sparse+quantized packed per-layer shards with
dedupe-aware ingest (delta.pack_delta_v2, the serialization shard
container, DeltaPublisher's changed-shards-only upload, and the
manifest-first DeltaIngestor path).

The parity pins here are the round's acceptance contract:
decode(encode(delta)) must match the sparsify+quantize v1 reference,
packed-form screen verdicts must match the dense screen on the same
cohort, and a torn shard set must never be decoded.
"""

import os

import jax
import numpy as np
import pytest

from distributedtraining_tpu import delta as dl
from distributedtraining_tpu import serialization as ser
from distributedtraining_tpu.engine.ingest import DeltaCache, DeltaIngestor
from distributedtraining_tpu.engine.publish import DeltaPublisher
from distributedtraining_tpu.transport import base as tbase
from distributedtraining_tpu.transport.localfs import LocalFSTransport
from distributedtraining_tpu.transport.memory import InMemoryTransport
from distributedtraining_tpu.transport.retry import RetryPolicy
from distributedtraining_tpu.utils import obs

FAST_RETRY = RetryPolicy(attempts=2, base_delay=0.0, max_delay=0.0,
                         jitter=0.0)


class _Report:
    pushes = 0
    pushes_failed = 0
    pushes_superseded = 0


def _tree(seed=0, big=(300, 40), small=(32,)):
    """A delta tree with one above-cutoff tensor (top-k sparsified) and
    one below-cutoff tensor (dense-form entry)."""
    rs = np.random.RandomState(seed)
    return {"wte": (rs.randn(*big) * 0.01).astype(np.float32),
            "ln": {"g": (rs.randn(*small) * 0.01).astype(np.float32)}}


def _template(tree):
    return jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.float32), tree)


def _leaves(t):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]


def _v2_publisher(transport, hotkey, *, density=1 / 64, quant="int8"):
    return DeltaPublisher(
        transport, hotkey, report=_Report(), publish_retry=FAST_RETRY,
        meta_retry=FAST_RETRY,
        wire_spec={"format": 2, "density": density, "quant": quant})


def _ingestor(transport, template, **kw):
    kw.setdefault("workers", 1)
    kw.setdefault("max_delta_abs", 1e3)
    kw.setdefault("retry_policy", FAST_RETRY)
    return DeltaIngestor(transport, template, **kw)


# ---------------------------------------------------------------------------
# Parity pins
# ---------------------------------------------------------------------------

def test_pack_decode_matches_sparse_quantize_reference():
    """decode(encode(delta)) == densify(sparsify_delta(delta)): the v2
    packed form keeps the v1 top-k selection and int8 scales exactly
    (dense-form entries differ in LAYOUT only — empty idx, full q)."""
    delta = _tree()
    packed, _ = dl.pack_delta_v2(delta, density=1 / 64)
    dec = dl.densify_packed_v2(jax.device_get(packed), delta)
    ref = dl.densify_sparse_delta(
        jax.device_get(dl.sparsify_delta(delta, density=1 / 64)), delta)
    for a, b in zip(_leaves(dec), _leaves(ref)):
        np.testing.assert_array_equal(a, b)
    # the below-cutoff tensor really ships dense-form (no index bytes)
    entries = dl.packed_layer_entries(jax.device_get(packed))
    assert entries["ln/g"]["idx"].shape == (0,)
    assert entries["ln/g"]["q"].shape == (32,)
    assert entries["wte"]["idx"].shape[0] < delta["wte"].size


def test_packed_screen_verdicts_match_dense_screen():
    """The fused packed-form screen returns the dense screen's verdicts
    on the same cohort — good, magnitude-capped, and nonfinite members
    alike — without densifying ahead of the verdict."""
    good = _tree(0)
    too_big = _tree(1)
    too_big["wte"][0, 0] = 50.0           # decoded max exceeds the cap
    bad = _tree(2)
    base = _template(good)

    packed_cohort, dense_cohort = [], []
    for d in (good, too_big):
        p = jax.device_get(dl.pack_delta_v2(d, density=1 / 64)[0])
        packed_cohort.append(p)
        dense_cohort.append(dl.densify_packed_v2(p, base))
    # nonfinite member: quant="none" carries f32 kept values, so a NaN
    # survives encoding (int8 would crush it at the miner's finite flag)
    p_bad = jax.device_get(dl.pack_delta_v2(bad, density=1 / 64,
                                            quant="none")[0])
    q = p_bad["leaves"]["wte"]["q"].copy()
    q[0] = np.nan
    p_bad["leaves"]["wte"]["q"] = q
    packed_cohort.append(p_bad)
    dense_cohort.append(dl.densify_packed_v2(p_bad, base))

    vp = dl.screen_deltas(packed_cohort, base, max_abs=1.0)
    vd = dl.screen_deltas(dense_cohort, base, max_abs=1.0)
    assert [ok for ok, _ in vp] == [ok for ok, _ in vd] == [
        True, False, False]
    # same reason vocabulary, including the identical magnitude value
    assert vp == vd


def test_apply_delta_loss_parity_within_quant_tolerance():
    """base + decode(encode(delta)) scores like base + delta on a real
    model when the delta's support fits the kept-coordinate budget: the
    only loss difference left is int8 rounding."""
    from distributedtraining_tpu.models.toy import FeedforwardNet

    model = FeedforwardNet()
    base = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    rs = np.random.RandomState(3)
    # sparse update: every tensor gets a few large coordinates, well
    # under the 1/64 top-k budget of the big layers (small layers ship
    # dense anyway), so sparsification drops nothing and the remaining
    # error is quantization only
    delta = jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.float32), base)

    def spike(a, n=8):
        flat = a.reshape(-1)
        flat[rs.choice(flat.size, size=min(n, flat.size),
                       replace=False)] = 0.05
        return a

    delta = jax.tree_util.tree_map(spike, delta)
    packed, _ = dl.pack_delta_v2(delta, density=1 / 64)
    dec = dl.densify_packed_v2(jax.device_get(packed), base)

    images = rs.randn(16, 28, 28, 1).astype(np.float32)
    labels = rs.randint(0, 10, size=(16,))

    def loss(params):
        logits = model.apply({"params": params}, images)
        logp = jax.nn.log_softmax(logits)
        return float(-logp[np.arange(16), labels].mean())

    l_ref = loss(dl.apply_delta(base, delta))
    l_dec = loss(dl.apply_delta(base, dec))
    # int8 tolerance: per-tensor error <= scale = max|kept|/127
    assert abs(l_ref - l_dec) < 5e-3, (l_ref, l_dec)


def test_error_feedback_residual_ships_dropped_mass():
    """A coordinate persistently below the top-k threshold accumulates
    in the residual until it crosses it — repeated lossy publishes
    converge instead of dropping it forever (and without the residual
    it is dropped forever)."""
    n = 64 * 1024
    rs = np.random.RandomState(0)
    flat = np.zeros(n, np.float32)
    k = dl.sparse_k(n, 1 / 64)
    flat[:k] = 1.0 + 0.1 * rs.rand(k)     # the recurring top-k winners
    victim = n - 7
    flat[victim] = 0.3                    # persistently dropped
    delta = {"w": flat.reshape(256, 256)}

    # stateless (no residual): never ships the victim
    packed, _ = dl.pack_delta_v2(delta, density=1 / 64)
    dec = dl.densify_packed_v2(jax.device_get(packed), delta)
    assert dec["w"].reshape(-1)[victim] == 0.0

    residual = None
    shipped_at = None
    for i in range(6):
        packed, residual = dl.pack_delta_v2(delta, density=1 / 64,
                                            residual=residual)
        dec = dl.densify_packed_v2(jax.device_get(packed), delta)
        if dec["w"].reshape(-1)[victim] != 0.0:
            shipped_at = i
            break
    assert shipped_at is not None, "residual never promoted the victim"
    assert shipped_at >= 1                # genuinely below-threshold at first


# ---------------------------------------------------------------------------
# Codec hardening
# ---------------------------------------------------------------------------

def test_manifest_codec_round_trip_and_hostile_inputs():
    layers = {"a": ("ab" * 32, 10), "b/c": ("cd" * 32, 20)}
    man = ser.build_wire_manifest(layers, density=1 / 64, quant="int8")
    assert ser.is_wire_v2_manifest(man)
    parsed = ser.parse_wire_manifest(man)
    assert parsed["quant"] == "int8"
    assert parsed["density"] == pytest.approx(1 / 64)
    assert set(parsed["layers"]) == {"a", "b/c"}
    assert parsed["layers"]["a"] == {"h": "ab" * 32, "n": 10}

    import json
    assert ser.parse_wire_manifest(b"not a manifest") is None
    assert ser.parse_wire_manifest(ser.WIRE_V2_MAGIC + b"{broken") is None
    assert ser.parse_wire_manifest(
        ser.WIRE_V2_MAGIC + json.dumps({"format": 1, "layers": {}}).encode()
    ) is None
    bad_hash = {"format": 2, "layers": {"a": {"h": "XYZ", "n": 1}}}
    assert ser.parse_wire_manifest(
        ser.WIRE_V2_MAGIC + json.dumps(bad_hash).encode()) is None
    bad_n = {"format": 2, "layers": {"a": {"h": "ab" * 32, "n": -1}}}
    assert ser.parse_wire_manifest(
        ser.WIRE_V2_MAGIC + json.dumps(bad_n).encode()) is None
    # a hostile manifest can never be confused with msgpack wire forms
    assert dl.sparse_delta_from_bytes(man, {"a": np.zeros(4, np.float32)}) is None


def test_shard_codec_round_trip_and_garbage():
    entry = {"idx": np.asarray([1, 5], np.int32),
             "q": np.asarray([3, -7], np.int8),
             "scale": np.float32(0.25)}
    data = ser.pack_shard(entry)
    back = ser.unpack_shard(data)
    for key in ("idx", "q", "scale"):
        np.testing.assert_array_equal(back[key], entry[key])
    assert ser.unpack_shard(b"\x00garbage") is None
    assert ser.unpack_shard(ser.to_msgpack({"idx": 1})) is None
    with pytest.raises(ValueError):
        ser.pack_shard({"idx": entry["idx"]})


def test_wire_blob_round_trip():
    delta = _tree()
    packed = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
    blob = ser.pack_wire_blob(packed)
    assert ser.is_wire_v2_blob(blob)
    dense = ser.unpack_wire_blob(blob, _template(delta))
    ref = dl.densify_packed_v2(packed, _template(delta))
    for a, b in zip(_leaves(dense), _leaves(ref)):
        np.testing.assert_array_equal(a, b)
    # the generic decode chain accepts a blob too (pod broadcast path)
    from distributedtraining_tpu.engine.lora_train import densify_delta_bytes
    dense2 = densify_delta_bytes(blob, _template(delta))
    assert dense2 is not None
    assert ser.unpack_wire_blob(b"DTWIRE2B\n\x00junk",
                                _template(delta)) is None


def test_negative_scale_is_rejected_everywhere():
    """A hostile NEGATIVE scale must not slip under the magnitude cap:
    |q| * scale with scale < 0 would give a negative screen verdict
    while densifying to arbitrarily large |values|. Admission, densify,
    the cohort screen, and the sparse8 densifier all refuse it, and the
    fused screen's magnitude is sign-robust even on unvalidated input."""
    delta = _tree()
    base = _template(delta)
    p = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
    p["leaves"]["wte"]["scale"] = np.float32(-1e6)
    assert not dl.packed_matches(p, base)
    assert dl.densify_packed_v2(p, base) is None
    assert dl.screen_deltas([p], base, max_abs=1.0) == [
        (False, "shape_mismatch")]
    # defense in depth: even without the admission gate, the screen's
    # magnitude uses |scale| — the verdict cannot go negative
    _, mags = dl._packed_screen_stats(p["leaves"])
    assert float(mags[0]) > 1.0
    # the shared validator covers the v1 sparse8 wire too
    sp = jax.device_get(dl.sparsify_delta(delta, density=1 / 64))
    sp["leaves"]["wte"]["scale"] = np.float32(-1.0)
    assert dl.densify_sparse_delta(sp, base) is None


def test_empty_leaf_packs_and_round_trips():
    """A zero-element tensor (n == 0 forces the dense-form branch) must
    encode, screen, and decode — not crash the publish path on an empty
    jnp.max reduction."""
    delta = {"w": (np.random.RandomState(0).randn(300, 40)
                   * 0.01).astype(np.float32),
             "empty": np.zeros((0,), np.float32)}
    base = _template(delta)
    packed, res = dl.pack_delta_v2(delta, density=1 / 64)
    packed = jax.device_get(packed)
    assert np.shape(jax.device_get(res)["empty"]) == (0,)
    assert dl.packed_matches(packed, base)
    dec = dl.densify_packed_v2(packed, base)
    assert dec["empty"].shape == (0,)
    np.testing.assert_array_equal(
        dec["w"], dl.densify_sparse_delta(
            jax.device_get(dl.sparsify_delta(delta, density=1 / 64)),
            base)["w"])
    assert dl.screen_deltas([packed], base, max_abs=1e3) == [(True, "ok")]
    # sparse8 (v1) tolerates the empty leaf too
    sp = jax.device_get(dl.sparsify_delta(delta, density=1 / 64))
    assert dl.densify_sparse_delta(sp, base)["empty"].shape == (0,)


def test_hostile_layer_keys_fail_template_validation():
    delta = _tree()
    packed = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
    entries = dl.packed_layer_entries(packed)
    # colliding / alien keys reassemble into a tree that fails the
    # template check, never an exception
    bad = dict(entries)
    bad["wte/evil"] = entries["ln/g"]
    tree = dl.packed_from_layer_entries(bad)
    assert not dl.packed_matches(tree, _template(delta))
    assert dl.densify_packed_v2(tree, _template(delta)) is None


# ---------------------------------------------------------------------------
# Publish -> ingest round trips
# ---------------------------------------------------------------------------

class CountingFS(LocalFSTransport):
    """LocalFS with byte/op accounting on the raw publish/fetch surface."""

    def __init__(self, root):
        super().__init__(root)
        self.published = []
        self.fetched = []

    def publish_raw(self, mid, data):
        self.published.append((mid, len(data)))
        return super().publish_raw(mid, data)

    def fetch_delta_bytes(self, mid):
        d = super().fetch_delta_bytes(mid)
        if d is not None:
            self.fetched.append((mid, len(d)))
        return d


def test_publish_ingest_round_trip_with_shard_dedupe(tmp_path):
    """The acceptance round: a v2 push stages correctly, a warm round
    with an unchanged manifest downloads nothing, and a one-layer change
    re-uploads/re-fetches ONLY that layer's shard (plus the manifest) —
    with the wire.* counters observing it."""
    from distributedtraining_tpu.utils.metrics import JSONLSink

    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path)
    obs.configure(sink, role="test")
    transport = CountingFS(str(tmp_path / "fs"))
    delta = _tree()
    template = _template(delta)
    pub = _v2_publisher(transport, "m0")
    ing = _ingestor(transport, template)
    try:
        pack = jax.jit(lambda d: dl.pack_delta_v2(d, density=1 / 64))
        packed = jax.device_get(pack(delta))[0]
        assert pub.publish_now(packed, None, "rev0", "cid-1")
        # manifest-last: the delta artifact lands after every shard
        assert transport.published[-1][0] == "m0"
        assert all(tbase.is_shard_id(m) for m, _ in transport.published[:-1])
        # rider declares the wire format (the META negotiation surface)
        assert transport.fetch_delta_meta("m0")["wire"]["format"] == 2

        s = ing.stage(["m0"])[0]
        assert s.ok and s.reason == "ok"
        assert s.wire_bytes > 0
        ref = dl.densify_packed_v2(packed, template)
        for a, b in zip(_leaves(s.delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)
        v2_bytes = sum(n for _, n in transport.published)
        dense_bytes = len(ser.to_msgpack(delta))
        assert dense_bytes > 5 * v2_bytes   # tiny tree; >=10x at scale

        # warm round: unchanged revision — zero transport bytes
        transport.fetched.clear()
        s2 = ing.stage(["m0"])[0]
        assert s2.ok and s2.cached and s2.wire_bytes == 0
        assert transport.fetched == []

        # one-layer change: only ln/g's shard (+ manifest) moves
        delta2 = {"wte": delta["wte"],
                  "ln": {"g": (delta["ln"]["g"] + 0.5).astype(np.float32)}}
        packed2 = jax.device_get(pack(delta2))[0]
        transport.published.clear()
        assert pub.publish_now(packed2, None, "rev0", "cid-2")
        pub_ids = [m for m, _ in transport.published]
        assert pub_ids == [tbase.shard_id("m0", "ln/g"), "m0"]

        transport.fetched.clear()
        deduped0 = obs.registry().counter("wire.shards_deduped").value
        s3 = ing.stage(["m0"])[0]
        assert s3.ok and not s3.cached
        fetch_ids = [m for m, _ in transport.fetched]
        assert fetch_ids == ["m0", tbase.shard_id("m0", "ln/g")]
        assert obs.registry().counter("wire.shards_deduped").value > deduped0
        for a, b in zip(_leaves(s3.delta),
                        _leaves(dl.densify_packed_v2(packed2, template))):
            np.testing.assert_array_equal(a, b)
    finally:
        ing.close()
        pub.close()
        obs.reset()
        sink.close()


def test_torn_shard_set_is_never_decoded(tmp_path):
    """Mid-publish state — old manifest, one shard already overwritten
    with newer content — must read as a transient miss, never a decode
    of mixed halves. A warm cache keeps serving the last CONSISTENT
    decode."""
    transport = CountingFS(str(tmp_path / "fs"))
    delta = _tree()
    template = _template(delta)
    pub = _v2_publisher(transport, "m0")
    ing_warm = _ingestor(transport, template)
    ing_cold = _ingestor(transport, template, cache_bytes=0)
    try:
        packed = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
        assert pub.publish_now(packed, None, "rev0")
        assert ing_warm.stage(["m0"])[0].ok

        # tear: overwrite one shard as a new publish would, manifest not
        # yet updated
        packed2 = jax.device_get(dl.pack_delta_v2(
            {"wte": delta["wte"],
             "ln": {"g": (delta["ln"]["g"] * 2).astype(np.float32)}},
            density=1 / 64)[0])
        new_entries = dl.packed_layer_entries(packed2)
        tbase.publish_shard(transport, "m0", "ln/g",
                            ser.pack_shard(new_entries["ln/g"]))

        cold = ing_cold.stage(["m0"])[0]
        assert not cold.ok and cold.reason == "no_delta"

        warm = ing_warm.stage(["m0"])[0]   # manifest revision unchanged
        assert warm.ok and warm.cached     # last consistent decode served
        ref = dl.densify_packed_v2(packed, template)
        for a, b in zip(_leaves(warm.delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)
    finally:
        ing_warm.close()
        ing_cold.close()
        pub.close()


def test_mid_publish_manifest_failure_heals_next_push(tmp_path):
    """A publish whose manifest upload dies after its shards landed
    leaves the transport readable-but-stale; the publisher reports a
    failed push, re-uploads on the next interval, and readers never
    decode the half-new state."""

    class FailManifest(CountingFS):
        manifest_outage = 0     # manifest publish attempts left to fail

        def publish_raw(self, mid, data):
            if self.manifest_outage and not tbase.is_shard_id(mid):
                self.manifest_outage -= 1
                raise OSError("injected manifest outage")
            return super().publish_raw(mid, data)

    transport = FailManifest(str(tmp_path / "fs"))
    delta = _tree()
    template = _template(delta)
    pub = _v2_publisher(transport, "m0")
    ing = _ingestor(transport, template, cache_bytes=0)
    try:
        pack = jax.jit(lambda d: dl.pack_delta_v2(d, density=1 / 64))
        assert pub.publish_now(jax.device_get(pack(delta))[0], None, "r0")
        assert ing.stage(["m0"])[0].ok

        delta2 = {"wte": (delta["wte"] + 0.1).astype(np.float32),
                  "ln": delta["ln"]}
        packed2 = jax.device_get(pack(delta2))[0]
        transport.manifest_outage = FAST_RETRY.attempts
        assert not pub.publish_now(packed2, None, "r0")   # counted failed
        assert pub.report.pushes_failed == 1

        torn = ing.stage(["m0"])[0]        # old manifest + new wte shard
        assert not torn.ok and torn.reason == "no_delta"

        assert pub.publish_now(packed2, None, "r0")       # heals
        healed = ing.stage(["m0"])[0]
        assert healed.ok
        ref = dl.densify_packed_v2(packed2, template)
        for a, b in zip(_leaves(healed.delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)
    finally:
        ing.close()
        pub.close()


def test_chaos_transport_carries_shard_and_manifest_ops(tmp_path):
    """ChaosTransport gates every shard/manifest operation like any
    other publish/fetch: injected faults surface as ordinary per-miner
    staging isolation (fetch_error / failed push), and a clean round
    afterwards works — the v2 wire adds no un-gated surface."""
    from distributedtraining_tpu.transport.chaos import (ChaosError,
                                                         ChaosSpec,
                                                         ChaosTransport)

    inner = CountingFS(str(tmp_path / "fs"))
    delta = _tree()
    template = _template(delta)

    # deterministic publish faults: the publisher retries past the first
    # injected error (seeded stream, rate .45, attempts=2 per op)
    chaos = ChaosTransport(inner, ChaosSpec(publish_error_rate=1.0, seed=3),
                           sleep=lambda s: None)
    pub = _v2_publisher(chaos, "m0")
    try:
        with pytest.raises(Exception):
            # every op faults: _publish_v2 must raise (not half-succeed
            # silently) so publish_now counts a failed push
            pub._publish_v2(jax.device_get(
                dl.pack_delta_v2(delta, density=1 / 64)[0]))
        assert not pub.publish_now(
            jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0]),
            None, "r0")
        assert pub.report.pushes_failed == 1
    finally:
        pub.close()

    # fetch faults: staging isolates per miner, then a clean round works
    pub2 = _v2_publisher(inner, "m0")
    assert pub2.publish_now(
        jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0]),
        None, "r0")
    pub2.close()
    chaos_fetch = ChaosTransport(inner, ChaosSpec(fetch_error_rate=1.0,
                                                  seed=1),
                                 sleep=lambda s: None)
    ing = _ingestor(chaos_fetch, template, cache_bytes=0)
    try:
        s = ing.stage(["m0"])[0]
        assert not s.ok and s.reason in ("fetch_error", "no_delta")
    finally:
        ing.close()
    ing2 = _ingestor(inner, template)
    try:
        assert ing2.stage(["m0"])[0].ok
        assert chaos_fetch.faults > 0
    finally:
        ing2.close()


def test_signed_transport_signs_manifest_and_passes_shards(tmp_path):
    """SignedTransport envelopes the manifest under the delta context
    (receivers with a registered key verify it); shards pass through
    unsigned, pinned by the signed manifest's content hashes; a
    tampered manifest is rejected wholesale."""
    pytest.importorskip("cryptography")
    from distributedtraining_tpu.transport.signed import SignedTransport
    from distributedtraining_tpu.utils.identity import Identity

    ident = Identity.generate()
    keys = {"m0": ident.public_bytes}
    inner = CountingFS(str(tmp_path / "fs"))
    signed = SignedTransport(inner, identity=ident,
                             pubkey_resolver=keys.get, my_hotkey="m0")
    reader = SignedTransport(CountingFS(str(tmp_path / "fs")),
                             pubkey_resolver=keys.get)
    delta = _tree()
    template = _template(delta)
    pub = _v2_publisher(signed, "m0")
    ing = _ingestor(reader, template)
    try:
        packed = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
        assert pub.publish_now(packed, None, "r0")
        s = ing.stage(["m0"])[0]
        assert s.ok
        ref = dl.densify_packed_v2(packed, template)
        for a, b in zip(_leaves(s.delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)

        # forged manifest (unsigned, key registered) is rejected
        forged_layers = {k: (ser.shard_digest(b"x"), 1)
                         for k in dl.packed_layer_entries(packed)}
        inner.publish_raw("m0", ser.build_wire_manifest(
            forged_layers, density=1 / 64, quant="int8"))
        ing.cache.clear()
        s2 = ing.stage(["m0"])[0]
        assert not s2.ok
    finally:
        ing.close()
        pub.close()


def test_mixed_fleet_v1_and_v2_miners_stage_and_merge():
    """The mixed-fleet acceptance round: one dense v1 miner and one v2
    miner stage through the same ingestor (the path both the validator
    and the averager gather through) and merge together."""
    transport = InMemoryTransport()
    delta_v1 = _tree(0)
    delta_v2 = _tree(1)
    template = _template(delta_v1)

    # v1 miner: classic dense publish + rider without a wire declaration
    transport.publish_delta("legacy", delta_v1)
    transport.publish_delta_meta("legacy", {"base_revision": "r0",
                                            "delta_id": "legacy-1"})
    # v2 miner: shard manifest + wire-declaring rider
    pub = _v2_publisher(transport, "modern")
    packed = jax.device_get(dl.pack_delta_v2(delta_v2, density=1 / 64)[0])
    assert pub.publish_now(packed, None, "r0", "modern-1")
    pub.close()
    assert transport.fetch_delta_meta("modern")["wire"]["format"] == 2
    assert "wire" not in transport.fetch_delta_meta("legacy")

    ing = _ingestor(transport, template, workers=2)
    try:
        staged = {s.hotkey: s for s in ing.stage(["legacy", "modern"],
                                                 base_revision="r0")}
        assert staged["legacy"].ok and staged["modern"].ok
        for a, b in zip(_leaves(staged["legacy"].delta), _leaves(delta_v1)):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        ref = dl.densify_packed_v2(packed, template)
        for a, b in zip(_leaves(staged["modern"].delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)

        # and they merge into one base like any homogeneous cohort
        merged = dl.chunked_weighted_merge(
            template, [staged["legacy"].delta, staged["modern"].delta],
            np.asarray([0.5, 0.5], np.float32))
        expect = jax.tree_util.tree_map(
            lambda a, b: 0.5 * np.asarray(a) + 0.5 * np.asarray(b),
            staged["legacy"].delta, staged["modern"].delta)
        for a, b in zip(_leaves(merged), _leaves(expect)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    finally:
        ing.close()


def test_receiver_can_refuse_wire_v2():
    """--no-wire-v2 (accept_wire_v2=False): manifests stage as no_delta
    while v1 miners keep working — the v1-only posture."""
    transport = InMemoryTransport()
    delta = _tree()
    template = _template(delta)
    transport.publish_delta("legacy", delta)
    pub = _v2_publisher(transport, "modern")
    assert pub.publish_now(
        jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0]),
        None, "r0")
    pub.close()
    ing = _ingestor(transport, template, accept_wire_v2=False)
    try:
        staged = {s.hotkey: s for s in ing.stage(["legacy", "modern"])}
        assert staged["legacy"].ok
        assert not staged["modern"].ok
        assert staged["modern"].reason == "no_delta"
    finally:
        ing.close()


def test_shard_cache_is_content_addressed_across_miners(tmp_path):
    """Two miners shipping an identical layer dedupe to ONE shard cache
    entry: the second miner's unchanged layer is served from cache even
    though its manifest was never seen before."""
    transport = CountingFS(str(tmp_path / "fs"))
    delta = _tree()
    template = _template(delta)
    pub_a = _v2_publisher(transport, "a")
    pub_b = _v2_publisher(transport, "b")
    ing = _ingestor(transport, template)
    try:
        pack = jax.jit(lambda d: dl.pack_delta_v2(d, density=1 / 64))
        packed = jax.device_get(pack(delta))[0]
        assert pub_a.publish_now(packed, None, "r0")
        assert pub_b.publish_now(packed, None, "r0")
        assert ing.stage(["a"])[0].ok
        transport.fetched.clear()
        s = ing.stage(["b"])[0]
        assert s.ok
        # miner b cost ONE manifest read; every shard came from the
        # content-addressed cache
        assert [m for m, _ in transport.fetched] == ["b"]
    finally:
        ing.close()
        pub_a.close()
        pub_b.close()


def test_delta_cache_shard_budget_and_eviction():
    cache = DeltaCache(max_bytes=2048)
    big = {"idx": np.zeros(0, np.int32), "q": np.zeros(1024, np.int8),
           "scale": np.float32(1)}
    cache.shard_put("a" * 64, big)
    assert cache.shard_lookup("a" * 64) is not None
    cache.shard_put("b" * 64, big)
    # budget forces the older shard out (LRU)
    assert cache.shard_lookup("a" * 64) is None
    assert cache.shard_lookup("b" * 64) is not None
    assert cache.nbytes <= 2048
    cache.clear()
    assert cache.nbytes == 0 and cache.shard_lookup("b" * 64) is None


def test_shard_slug_injective_for_dotted_layer_keys(tmp_path):
    """Layer keys containing '.' must not collide with '/'-separated
    ones after the slug join ('a/b.c' vs 'a/b/c'): a collision makes the
    publisher silently overwrite one layer's shard with the other and
    ingest fail that layer's hash check every round."""
    keys = ["a/b.c", "a/b/c", "a.b/c", "a/b%c", "a/b%2Ec", "a.b.c"]
    slugs = [tbase.shard_layer_slug(k) for k in keys]
    assert len(set(slugs)) == len(keys), slugs
    assert len({tbase.shard_id("m0", k) for k in keys}) == len(keys)

    # end to end: a model with a dotted parameter name publishes both
    # layers and stages them back intact
    rs = np.random.RandomState(0)
    delta = {"a": {"b.c": (rs.randn(64) * 0.01).astype(np.float32),
                   "b": {"c": (rs.randn(64) * 0.02).astype(np.float32)}}}
    template = _template(delta)
    transport = CountingFS(str(tmp_path / "fs"))
    pub = _v2_publisher(transport, "m0")
    ing = _ingestor(transport, template)
    try:
        packed = jax.device_get(dl.pack_delta_v2(delta, density=1 / 64)[0])
        assert len(dl.packed_layer_entries(packed)) == 2
        assert pub.publish_now(packed, None, "r0")
        # two distinct shard artifacts landed (plus the manifest)
        assert len([m for m, _ in transport.published
                    if tbase.is_shard_id(m)]) == 2
        s = ing.stage(["m0"])[0]
        assert s.ok, s.reason
        ref = dl.densify_packed_v2(packed, template)
        for a, b in zip(_leaves(s.delta), _leaves(ref)):
            np.testing.assert_array_equal(a, b)
    finally:
        ing.close()
        pub.close()


def test_reserved_shard_ids_and_localfs_roots(tmp_path):
    from distributedtraining_tpu.transport import localfs

    sid = tbase.shard_id("m0", "h_0/attn/w")
    assert tbase.is_shard_id(sid)
    assert tbase.is_reserved_id(sid)
    assert not tbase.is_shard_id("m0")
    root = str(tmp_path / "fs")
    LocalFSTransport(root)
    assert os.path.abspath(root) in localfs.live_roots()


def test_miner_loop_snapshot_carries_residual(tmp_path):
    """MinerLoop --wire-v2 integration: the push program threads the
    error-feedback residual across pushes, the artifact on the wire is
    a manifest, and a base pull resets the residual."""
    from distributedtraining_tpu.engine.train import MinerLoop, TrainEngine
    from distributedtraining_tpu.models import gpt2

    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=128, n_positions=32, n_embd=16, n_layer=1, n_head=2))
    engine = TrainEngine(model, seq_len=16)
    transport = CountingFS(str(tmp_path / "fs"))
    loop = MinerLoop(engine, transport, "m0", send_interval=1e9,
                     push_async=False, wire_v2=True,
                     wire_density=1 / 64)
    loop.bootstrap(rng=jax.random.PRNGKey(0))
    assert loop._wire_residual is None
    loop._push_delta()
    assert loop._wire_residual is not None
    data = transport.fetch_delta_bytes("m0")
    assert ser.is_wire_v2_manifest(data)
    meta = transport.fetch_delta_meta("m0")
    assert meta["wire"] == {"format": 2, "density": 1 / 64,
                            "quant": "int8"}
    # a staged ingest decodes it against the engine's wire template
    from distributedtraining_tpu.engine.train import host_wire_template
    ing = _ingestor(transport, host_wire_template(engine))
    try:
        assert ing.stage(["m0"])[0].ok
    finally:
        ing.close()
    # base pull resets the residual
    transport.publish_base(jax.device_get(loop.state.params))
    loop._check_pull()
    assert loop._wire_residual is None
    loop.flush()


def test_nonfinite_delta_does_not_poison_residual(tmp_path):
    """A transient non-finite delta is skipped by the nan guard AND the
    loop-carried error-feedback residual keeps its pre-divergence value
    (new_res = delta + residual - decoded would smear the NaN into every
    later publish until the next base pull). After the miner recovers,
    the next publish is clean and stages."""
    from distributedtraining_tpu.engine.train import (MinerLoop,
                                                      TrainEngine,
                                                      host_wire_template)
    from distributedtraining_tpu.models import gpt2

    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=128, n_positions=32, n_embd=16, n_layer=1, n_head=2))
    engine = TrainEngine(model, seq_len=16)
    transport = CountingFS(str(tmp_path / "fs"))
    loop = MinerLoop(engine, transport, "m0", send_interval=1e9,
                     push_async=False, wire_v2=True, wire_density=1 / 64)
    loop.bootstrap(rng=jax.random.PRNGKey(0))
    # drift params so the first (healthy) push leaves a real residual
    loop.state = loop.state.replace(params=jax.tree_util.tree_map(
        lambda x: x + 0.01, loop.state.params))
    healthy = loop.state
    loop._push_delta()
    res_before = jax.device_get(loop._wire_residual)
    assert all(np.isfinite(l).all() for l in _leaves(res_before))

    # transient divergence: NaN params -> the guard skips the push and
    # the residual must NOT commit the contaminated update
    published = len(transport.published)
    loop.state = loop.state.replace(params=jax.tree_util.tree_map(
        lambda x: jax.numpy.full_like(x, np.nan), loop.state.params))
    loop._push_delta()
    assert len(transport.published) == published      # push skipped
    res_after = jax.device_get(loop._wire_residual)
    for a, b in zip(_leaves(res_before), _leaves(res_after)):
        np.testing.assert_array_equal(a, b)

    # recovery: the very next healthy publish is finite and stages
    loop.state = healthy
    loop._push_delta()
    assert len(transport.published) > published
    ing = _ingestor(transport, host_wire_template(engine))
    try:
        s = ing.stage(["m0"])[0]
        assert s.ok, s.reason
        assert all(np.isfinite(l).all() for l in _leaves(s.delta))
    finally:
        ing.close()
    loop.flush()


def test_wire_v2_rejects_conflicting_v1_compression(tmp_path):
    from distributedtraining_tpu.engine.train import MinerLoop, TrainEngine
    from distributedtraining_tpu.models import gpt2

    model, _ = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=128, n_positions=32, n_embd=16, n_layer=1, n_head=2))
    engine = TrainEngine(model, seq_len=16)
    with pytest.raises(ValueError, match="wire_v2"):
        MinerLoop(engine, InMemoryTransport(), "m0", wire_v2=True,
                  delta_dtype="sparse8")
