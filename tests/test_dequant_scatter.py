"""Fused dequantize->scatter-add kernel (ops/dequant_scatter.py) and its
delta.accumulate_delta integration.

Round 20's ingest half: the kernel-backed packed accumulate must match
the densify_packed_v2 + dense accumulate_delta spelling to 1e-6 on
every entry class the wire produces (int8 and f32 kept values,
dense-form below-cutoff leaves, empty leaves), keep today's screened
semantics on hostile payloads (duplicate indices SUM like the XLA
scatter-add; negative scales never reach an accumulate at all), and
the densify round-trip the kernel deletes must be VISIBLE when it
happens (the ``delta.densify_fallbacks`` counter, satellite 2).
Kernels run interpreted here (tier-1 forces CPU); real-chip variants
live in tests_tpu/test_dequant_scatter_tpu.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu import delta as dl
from distributedtraining_tpu.ops import dequant_scatter as dsc
from distributedtraining_tpu.utils import obs


@pytest.fixture(autouse=True)
def _no_force_interpret():
    yield
    dsc.use_interpret(False)


def _accumulate_both_ways(template, packed, w):
    """(kernel-backed result, XLA scatter-add result, densify+dense
    result) for one packed tree folded into a zeros accumulator."""
    acc0 = jax.tree_util.tree_map(
        lambda x: jnp.zeros(np.shape(x), jnp.float32), template)
    xla = dl.accumulate_delta(acc0, packed, w)
    dsc.use_interpret(True)
    assert dsc.enabled()
    kernel = dl.accumulate_delta(acc0, packed, w)
    dsc.use_interpret(False)
    dense = dl.densify_packed_v2(packed, template)
    assert dense is not None
    densified = dl.accumulate_delta(acc0, dense, w)
    return kernel, xla, densified


def _assert_tree_close(a, b, atol=1e-6):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol)


# ---------------------------------------------------------------------------
# Kernel primitive
# ---------------------------------------------------------------------------

def test_kernel_matches_xla_scatter_int8_f32_duplicates():
    rng = np.random.default_rng(0)
    n, k = 2048, 96
    flat = jnp.asarray(rng.standard_normal(n), jnp.float32)
    q8 = jnp.asarray(rng.integers(-127, 128, k), jnp.int8)
    qf = jnp.asarray(rng.standard_normal(k), jnp.float32)
    for idx in (jnp.asarray(rng.integers(0, n, k), jnp.int32),  # dups likely
                jnp.zeros((k,), jnp.int32)):                    # all dups
        for q in (q8, qf):
            out = dsc.dequant_scatter_add(flat, idx, q, 0.37,
                                          interpret=True)
            assert out is not None
            ref = flat.at[idx].add(q.astype(jnp.float32) * 0.37)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref))


def test_shape_rule_and_selection():
    """Selection is a shape rule plus the backend, never a probe: leaves
    too big for VMEM, not whole 128-lane rows, empty, or with more
    entries than SMEM holds are not the kernel's; calling it on one
    anyway is a ValueError, not a silent decline."""
    assert dsc.kernel_supports(2048, 96)
    assert not dsc.kernel_supports(dsc.MAX_ACC_ELEMS + 128, 1)
    assert not dsc.kernel_supports(2048 + 64, 1)        # ragged rows
    assert not dsc.kernel_supports(2048, 0)
    assert not dsc.kernel_supports(dsc.MAX_ACC_ELEMS, dsc.MAX_ENTRIES + 1)
    idx = jnp.asarray([0], jnp.int32)
    q = jnp.asarray([1], jnp.int8)
    with pytest.raises(ValueError, match="unsupported shapes"):
        dsc.dequant_scatter_add(jnp.zeros((100,), jnp.float32), idx, q,
                                1.0, interpret=True)
    # production CPU (no interpret hook, no TPU): accumulate paths do
    # not route here
    assert not dsc.enabled()


# ---------------------------------------------------------------------------
# accumulate_delta integration: parity vs densify+accumulate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["int8", "none"])
def test_accumulate_kernel_matches_densify_path(quant):
    """The acceptance pin: kernel-routed packed accumulate ==
    densify_packed_v2 + dense accumulate_delta <= 1e-6, over a tree
    with an above-cutoff leaf (indexed entries), a below-cutoff leaf
    (dense-form entry), and an EMPTY leaf."""
    rng = np.random.default_rng(1)
    d = {"w": jnp.asarray(rng.standard_normal((96, 64)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((32,)), jnp.float32),
         "empty": jnp.zeros((0,), jnp.float32)}
    template = jax.tree_util.tree_map(
        lambda x: np.zeros(np.shape(x), np.float32), d)
    packed, _ = dl.pack_delta_v2(d, density=1.0 / 16.0, quant=quant)
    # the big leaf really is indexed-form, the small one dense-form
    assert packed["leaves"]["w"]["idx"].shape[0] > 0
    assert packed["leaves"]["b"]["idx"].shape[0] == 0
    kernel, xla, densified = _accumulate_both_ways(template, packed, 0.7)
    _assert_tree_close(kernel, densified)
    _assert_tree_close(kernel, xla)
    _assert_tree_close(xla, densified)


def test_aggregate_deltas_kernel_parity_mixed_cohort():
    """M mixed contributions (packed int8, packed f32, dense v1) folded
    by aggregate_deltas: kernel-routed == XLA <= 1e-6 over the whole
    aggregate — the sub-averager fold (engine/hier_average.py) and the
    flat packed merge (engine/average.py) both ride this path."""
    rng = np.random.default_rng(2)
    template = {"w": np.zeros((96, 64), np.float32),
                "b": np.zeros((32,), np.float32)}
    deltas = []
    for i in range(3):
        d = {"w": jnp.asarray(rng.standard_normal((96, 64)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((32,)), jnp.float32)}
        if i == 0:
            deltas.append(d)    # dense v1
        else:
            deltas.append(dl.pack_delta_v2(
                d, density=1.0 / 8.0,
                quant="int8" if i == 1 else "none")[0])
    w = jnp.asarray([0.2, 0.5, 0.3], jnp.float32)
    xla = dl.aggregate_deltas(template, deltas, w)
    dsc.use_interpret(True)
    kernel = dl.aggregate_deltas(template, deltas, w)
    dsc.use_interpret(False)
    _assert_tree_close(kernel, xla)


# ---------------------------------------------------------------------------
# Hostile payloads keep today's screened semantics
# ---------------------------------------------------------------------------

def test_hostile_duplicate_indices_sum_on_both_paths():
    """A hostile duplicate-index entry (honest encoders emit unique
    top-k indices): the kernel SUMS duplicates exactly like the XLA
    scatter-add — deterministic, and screened upstream regardless."""
    template = {"w": np.zeros((8192,), np.float32)}
    entry = {"idx": jnp.asarray([5, 5, 5, 9], jnp.int32),
             "q": jnp.asarray([10, 20, -5, 7], jnp.int8),
             "scale": jnp.asarray(0.5, jnp.float32)}
    packed = {dl.WIRE_V2_KEY: np.int32(dl.WIRE_V2_FORMAT),
              "leaves": {"w": entry}}
    assert dl.packed_matches(packed, template)
    acc0 = {"w": jnp.zeros((8192,), jnp.float32)}
    xla = dl.accumulate_delta(acc0, packed, 1.0)
    dsc.use_interpret(True)
    kernel = dl.accumulate_delta(acc0, packed, 1.0)
    dsc.use_interpret(False)
    np.testing.assert_allclose(np.asarray(kernel["w"]),
                               np.asarray(xla["w"]), atol=1e-6)
    assert float(kernel["w"][5]) == pytest.approx((10 + 20 - 5) * 0.5)


def test_negative_scale_never_reaches_accumulate():
    """Negative scales stay rejected at admission (packed_matches and
    the fused packed screen) — the kernel path changes nothing about
    what is allowed to accumulate."""
    template = {"w": np.zeros((8192,), np.float32)}
    hostile = {dl.WIRE_V2_KEY: np.int32(dl.WIRE_V2_FORMAT),
               "leaves": {"w": {"idx": np.asarray([1], np.int32),
                                "q": np.asarray([127], np.int8),
                                "scale": np.asarray(-1e6, np.float32)}}}
    assert not dl.packed_matches(hostile, template)
    verdicts = dl.screen_deltas([hostile], template, max_abs=1e3)
    assert verdicts[0] == (False, "shape_mismatch")


# ---------------------------------------------------------------------------
# Satellite 2: densify=False end-to-end, fallbacks counted
# ---------------------------------------------------------------------------

def _publish_packed(transport, hotkey, d, template):
    from distributedtraining_tpu.engine.publish import DeltaPublisher
    from distributedtraining_tpu.transport.retry import RetryPolicy

    class _Report:
        pushes = 0
        pushes_failed = 0
        pushes_superseded = 0

    fast = RetryPolicy(attempts=2, base_delay=0.0, max_delay=0.0,
                       jitter=0.0)
    pub = DeltaPublisher(transport, hotkey, report=_Report(),
                         publish_retry=fast, meta_retry=fast,
                         wire_spec={"format": 2, "density": 1.0 / 8.0,
                                    "quant": "int8"})
    assert pub.publish_now(dl.pack_delta_v2(d, density=1.0 / 8.0)[0],
                           None, "rev0")
    pub.close()


def test_ingest_densify_fallbacks_counter(tmp_path):
    """densify=True ingest of a packed submission counts ONE
    ``delta.densify_fallbacks``; densify=False ingest counts none and
    stages the PACKED tree — the regression signal fleet_report
    surfaces."""
    from distributedtraining_tpu.engine.ingest import DeltaIngestor
    from distributedtraining_tpu.transport.memory import InMemoryTransport

    rng = np.random.default_rng(3)
    template = {"w": np.zeros((96, 64), np.float32)}
    d = {"w": jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)}
    transport = InMemoryTransport()
    _publish_packed(transport, "m0", d, template)

    class _Sink:
        def log(self, *a, **k):
            pass

    try:
        for densify, expect in ((True, 1), (False, 0)):
            obs.reset()
            obs.configure(_Sink(), role="test")
            ing = DeltaIngestor(transport, template, densify=densify,
                                workers=1, cache_bytes=0)
            (s,) = ing.stage(["m0"])
            ing.close()
            assert s.reason == "ok"
            assert dl.is_packed_v2(s.delta) is (not densify)
            snap = obs.registry().snapshot()
            assert snap.get("delta.densify_fallbacks", 0) == expect, \
                (densify, snap.get("delta.densify_fallbacks"))
    finally:
        obs.reset()


def test_flat_averager_stays_packed_end_to_end(tmp_path):
    """The satellite's end-to-end pin: an AveragerLoop whose strategy
    folds host lists (WeightedAverage) now ingests wire-v2 submissions
    with densify=False — the packed tree reaches the scatter-add merge
    un-densified, zero densify fallbacks, and the published base equals
    the densify-path base <= 1e-6."""
    from distributedtraining_tpu.engine import TrainEngine, WeightedAverage
    from distributedtraining_tpu.engine.average import AveragerLoop
    from distributedtraining_tpu.engine.train import host_wire_template
    from distributedtraining_tpu.models import gpt2
    from distributedtraining_tpu.transport.memory import InMemoryTransport

    class _Chain:
        my_hotkey = "avg"

        def sync(self):
            import types
            return types.SimpleNamespace(hotkeys=["m0"])

        def should_set_weights(self):
            return False

    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
        dtype="float32", vocab_multiple=64))
    engine = TrainEngine(model, seq_len=16)
    transport = InMemoryTransport()
    base = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    from distributedtraining_tpu.engine.train import wire_out
    transport.publish_base(wire_out(engine, base))

    template = host_wire_template(engine)
    rng = np.random.default_rng(4)
    d = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.standard_normal(np.shape(x)) * 1e-3,
                              jnp.float32), template)
    _publish_packed(transport, "m0", d, template)

    avg = AveragerLoop(engine, transport, _Chain(), WeightedAverage(),
                       val_batches=None, publish_policy="always")
    try:
        assert avg._ingest().densify is False
        assert avg._packed_ingest is True
        ids, deltas = avg.gather_deltas()
        assert ids == ["m0"]
        assert dl.is_packed_v2(deltas[0])
        # the packed fold equals densify + dense fold
        w = jnp.asarray([1.0], jnp.float32)
        packed_agg = dl.aggregate_deltas(template, deltas, w)
        dense = dl.densify_packed_v2(deltas[0], template)
        dense_agg = dl.aggregate_deltas(template, [dense], w)
        _assert_tree_close(packed_agg, dense_agg)
    finally:
        avg.close()
