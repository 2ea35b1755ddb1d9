"""Delta algebra: round-trip, screening, stacking, merge gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu import delta


def small_tree(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "layer": {"kernel": jax.random.normal(k1, (4, 8)) * scale,
                  "bias": jax.random.normal(k2, (8,)) * scale},
        "head": jax.random.normal(k3, (8, 2)) * scale,
    }


def test_delta_roundtrip():
    base = small_tree(0)
    trained = small_tree(1)
    d = delta.compute_delta(trained, base)
    restored = delta.apply_delta(base, d)
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(trained)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_nan_screen():
    t = small_tree(0)
    assert not delta.has_nonfinite(t)
    t["head"] = t["head"].at[0, 0].set(jnp.nan)
    assert delta.has_nonfinite(t)
    t["head"] = t["head"].at[0, 0].set(jnp.inf)
    assert delta.has_nonfinite(t)


def test_shape_screen():
    base = small_tree(0)
    good = small_tree(1)
    assert delta.shapes_match(good, base)
    bad = dict(good)
    bad["head"] = jnp.zeros((8, 3))
    assert not delta.shapes_match(bad, base)
    missing = {"layer": good["layer"]}
    assert not delta.shapes_match(missing, base)


def test_dtype_screen_catches_f64_wire_payload():
    """jnp.asarray would downcast f64->f32 under x64-disabled JAX and make the
    dtype check vacuous; screen must compare numpy-side (live-probe regression)."""
    base = small_tree(0)
    d64 = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, np.float64), base)
    ok, reason = delta.screen_delta(d64, base)
    assert not ok and reason == "shape_mismatch"


def test_screen_delta_magnitude():
    base = small_tree(0)
    d = delta.compute_delta(small_tree(1), base)
    ok, reason = delta.screen_delta(d, base, max_abs=1e-6)
    assert not ok and reason.startswith("magnitude_exceeded")
    ok, reason = delta.screen_delta(d, base, max_abs=1e6)
    assert ok


def test_stack_and_weighted_merge():
    base = small_tree(0)
    deltas = [delta.compute_delta(small_tree(i), base) for i in range(1, 4)]
    stacked = delta.stack_deltas(deltas)
    assert jax.tree_util.tree_leaves(stacked)[0].shape[0] == 3

    w = jnp.array([1.0, 0.0, 0.0])
    merged = delta.weighted_merge(base, stacked, w)
    expect = delta.apply_delta(base, deltas[0])
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(a, b, rtol=1e-5)

    # uniform weights = plain average
    w = jnp.full((3,), 1.0 / 3)
    merged = delta.weighted_merge(base, stacked, w)
    mean_delta = jax.tree_util.tree_map(
        lambda *xs: sum(xs) / 3, *deltas)
    expect = delta.apply_delta(base, mean_delta)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(a, b, rtol=1e-5)


def test_flat_merge_matches_leafwise():
    """weighted_merge_flat is the single-kernel spelling of weighted_merge:
    identical values AND identical meta-gradient w.r.t. the weights."""
    base = small_tree(0)
    deltas = [delta.compute_delta(small_tree(i), base) for i in range(1, 5)]
    stacked = delta.stack_deltas(deltas)
    w = jnp.asarray([0.4, 0.3, 0.2, 0.1])

    a = delta.weighted_merge(base, stacked, w)
    b = delta.weighted_merge_flat(base, stacked, w)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-6)

    def probe(merge_fn, w):
        merged = merge_fn(base, stacked, w)
        return sum(jnp.sum(l * l) for l in jax.tree_util.tree_leaves(merged))

    g1 = jax.grad(lambda w: probe(delta.weighted_merge, w))(w)
    g2 = jax.grad(lambda w: probe(delta.weighted_merge_flat, w))(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)


def test_merge_weight_gradient_matches_finite_difference():
    """jax.grad through the merge must equal numeric meta-gradient — this is
    the correctness core of the parameterized averager."""
    base = small_tree(0)
    deltas = [delta.compute_delta(small_tree(i), base) for i in range(1, 4)]
    stacked = delta.stack_deltas(deltas)

    def loss(w):
        merged = delta.weighted_merge(base, stacked, w)
        return sum(jnp.sum(l * l) for l in jax.tree_util.tree_leaves(merged))

    w0 = jnp.array([0.3, 0.5, 0.2])
    g = jax.grad(loss)(w0)
    eps = 1e-3
    for i in range(3):
        wp = w0.at[i].add(eps)
        wm = w0.at[i].add(-eps)
        fd = (loss(wp) - loss(wm)) / (2 * eps)
        np.testing.assert_allclose(g[i], fd, rtol=1e-2)


def test_per_tensor_merge():
    base = small_tree(0)
    deltas = [delta.compute_delta(small_tree(i), base) for i in range(1, 3)]
    stacked = delta.stack_deltas(deltas)
    w = delta.init_merge_weights(base, 2, per_tensor=True)
    merged = delta.per_tensor_weighted_merge(base, stacked, w)
    mean_delta = jax.tree_util.tree_map(lambda *xs: sum(xs) / 2, *deltas)
    expect = delta.apply_delta(base, mean_delta)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(expect)):
        np.testing.assert_allclose(a, b, rtol=1e-5)


def test_bf16_wire_delta_screens_and_merges():
    """compute_delta(wire_dtype='bfloat16'): half-size artifact accepted by
    the default screen (f64/int substitutions stay rejected), applied with
    f32 promotion, and merged with f32 accumulation."""
    import jax
    import jax.numpy as jnp

    from distributedtraining_tpu import delta

    base = {"a": jnp.ones((8, 4), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}
    trained = jax.tree_util.tree_map(lambda x: x + 0.01, base)
    d16 = delta.compute_delta(trained, base, wire_dtype="bfloat16")
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(d16))

    ok, reason = delta.screen_delta(d16, base)
    assert ok, reason
    # a f64 submission must still be rejected (promotion attack)
    d64 = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), d16)
    ok, reason = delta.screen_delta(d64, base)
    assert not ok and reason == "shape_mismatch"

    applied = delta.apply_delta(base, d16)
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(applied))

    # merge of an all-bf16 stack: output f32, values within bf16 rounding
    # of the f32 merge (accumulation happens in f32 per merge_leaf)
    d32 = delta.compute_delta(trained, base)
    w = jnp.asarray([0.7, 0.3])
    m16 = delta.weighted_merge(base, delta.stack_deltas([d16, d16]), w)
    m32 = delta.weighted_merge(base, delta.stack_deltas([d32, d32]), w)
    for a, b in zip(jax.tree_util.tree_leaves(m16),
                    jax.tree_util.tree_leaves(m32)):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2)


def test_chunked_weighted_merge_matches_stacked():
    """Bounded-memory merge == stacked merge, including a chunk that does
    not divide M (zero-padding path) and bf16 wire deltas in the list."""
    import jax
    import jax.numpy as jnp

    from distributedtraining_tpu import delta

    base = {"a": jnp.ones((16, 8), jnp.float32),
            "b": {"c": jnp.full((5,), 2.0, jnp.float32)}}
    rng = np.random.default_rng(0)
    deltas = []
    for i in range(5):
        d = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(0, 0.01, x.shape), x.dtype),
            base)
        if i == 3:  # one bf16 wire submission in the mix
            d = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), d)
        deltas.append(d)
    w = jnp.asarray([0.4, 0.1, 0.2, 0.2, 0.1])

    want = delta.weighted_merge(base, delta.stack_deltas(deltas), w)
    for chunk in (1, 2, 5, 8):
        got = delta.chunked_weighted_merge(base, deltas, w, chunk=chunk)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError):
        delta.chunked_weighted_merge(base, [], w)
    with pytest.raises(ValueError):
        delta.chunked_weighted_merge(base, deltas, w[:3])


def test_int8_wire_quantization_roundtrip_and_screens():
    """Per-tensor int8 wire format: bounded roundtrip error, hostile
    scales die in the existing screens after dequantization, non-float
    trees are refused loudly (no silent template mismatch)."""
    import jax
    import jax.numpy as jnp

    from distributedtraining_tpu import delta

    rng = np.random.default_rng(0)
    base = {"a": jnp.zeros((64, 32), jnp.float32),
            "b": jnp.zeros((17,), jnp.float32)}
    d = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.normal(0, 0.01, x.shape), x.dtype), base)

    q = delta.quantize_delta(d)
    deq = delta.dequantize_delta(q)
    for a, b in zip(jax.tree_util.tree_leaves(deq),
                    jax.tree_util.tree_leaves(d)):
        err = float(jnp.abs(a - b).max())
        bound = float(jnp.abs(b).max()) / 127.0  # one quantization step
        assert err <= bound + 1e-9, (err, bound)
    ok, reason = delta.screen_delta(deq, base)
    assert ok, reason

    # hostile scales: inf/nan -> nonfinite screen; huge -> magnitude screen
    evil = jax.tree_util.tree_map(
        lambda l: {"q": l["q"], "scale": jnp.asarray(float("inf"))},
        q, is_leaf=delta._is_qleaf)
    ok, reason = delta.screen_delta(delta.dequantize_delta(evil), base)
    assert not ok and reason == "nonfinite"
    big = jax.tree_util.tree_map(
        lambda l: {"q": l["q"], "scale": jnp.asarray(1e30, jnp.float32)},
        q, is_leaf=delta._is_qleaf)
    ok, reason = delta.screen_delta(delta.dequantize_delta(big), base,
                                    max_abs=1e3)
    assert not ok and reason.startswith("magnitude_exceeded")

    # non-float leaves refuse loudly (the wire format is all-float)
    with pytest.raises(ValueError, match="non-float"):
        delta.quantize_delta({"a": jnp.zeros((4,), jnp.int32)})


def test_int8_hostile_f64_q_rejected():
    """A structurally matching tree whose "q" leaves are f64 must NOT pass
    the dtype-pinned quant load (8x memory amplification otherwise)."""
    import jax
    import jax.numpy as jnp

    from distributedtraining_tpu import delta, serialization as ser

    base = {"a": np.zeros((8, 4), np.float32)}
    tmpl = delta.quantized_template(base)
    legit = delta.quantize_delta({"a": jnp.full((8, 4), 0.01)})
    ser.validated_load(ser.to_msgpack(legit), tmpl, check_dtypes=True)
    hostile = {"a": {"q": np.ones((8, 4), np.float64),
                     "scale": np.float32(1.0)}}
    with pytest.raises(ser.PayloadError):
        ser.validated_load(ser.to_msgpack(hostile), tmpl, check_dtypes=True)


# -- sparse8 wire format -----------------------------------------------------

def _sparse_case():
    rng = np.random.default_rng(3)
    tree = {"big": jnp.asarray(rng.normal(size=(9000,)) * 0.01, jnp.float32),
            "ln": {"b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}}
    template = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, np.float32), tree)
    return tree, template


def test_sparse8_roundtrip_topk_and_dense_small_leaves():
    from distributedtraining_tpu import serialization as ser

    tree, template = _sparse_case()
    sp = delta.sparsify_delta(tree, density=1.0 / 8)
    back = delta.sparse_delta_from_bytes(ser.to_msgpack(sp), template)
    assert back is not None
    big = np.asarray(tree["big"])
    got = np.asarray(back["big"])
    k = delta.sparse_k(big.size, 1.0 / 8)
    nz = np.nonzero(got)[0]
    top = set(np.argsort(-np.abs(big))[:k].tolist())
    assert set(nz.tolist()).issubset(top)
    # kept coordinates agree to one int8 step of the tensor max
    step = np.abs(big).max() / 127
    assert np.abs(got[nz] - big[nz]).max() <= step + 1e-7
    # small leaf ships dense: exact to its own int8 step
    ln, gln = np.asarray(tree["ln"]["b"]), np.asarray(back["ln"]["b"])
    assert np.abs(gln - ln).max() <= np.abs(ln).max() / 127 + 1e-7


def test_sparse8_jitted_matches_eager():
    tree, template = _sparse_case()
    from distributedtraining_tpu import serialization as ser
    eager = delta.sparsify_delta(tree, density=1.0 / 8)
    jitted = jax.jit(delta.sparsify_delta,
                     static_argnames=("density",))(tree, density=1.0 / 8)
    a = delta.sparse_delta_from_bytes(ser.to_msgpack(eager), template)
    b = delta.sparse_delta_from_bytes(ser.to_msgpack(jitted), template)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sparse8_hostile_payloads_rejected():
    """Everything the publisher controls is validated: marker, paths,
    dtypes, k <= n, index bounds; the dense/int8 template loaders must
    also refuse the sparse artifact."""
    from distributedtraining_tpu import serialization as ser

    tree, template = _sparse_case()
    good = delta.sparsify_delta(tree)
    data = ser.to_msgpack(good)

    def mutate(fn):
        import copy
        t = copy.deepcopy(jax.device_get(good))
        fn(t)
        return delta.sparse_delta_from_bytes(ser.to_msgpack(t), template)

    assert delta.sparse_delta_from_bytes(data, template) is not None
    assert delta.sparse_delta_from_bytes(b"garbage", template) is None
    # out-of-bounds index
    assert mutate(lambda t: t["leaves"]["big"].__setitem__(
        "idx", np.asarray([10 ** 8], np.int32))) is None
    # wrong q dtype (would parse at inflated bytes)
    assert mutate(lambda t: t["leaves"]["big"].__setitem__(
        "q", t["leaves"]["big"]["q"].astype(np.float64))) is None
    # extra top-level key
    assert mutate(lambda t: t.__setitem__("extra", np.zeros(1))) is None
    # missing leaf
    assert mutate(lambda t: t["leaves"].pop("ln")) is None
    # non-finite scale
    assert mutate(lambda t: t["leaves"]["big"].__setitem__(
        "scale", np.float32(np.inf))) is None
    # k > n
    assert mutate(lambda t: (
        t["leaves"]["ln"]["b"].__setitem__(
            "idx", np.zeros(64, np.int32)),
        t["leaves"]["ln"]["b"].__setitem__(
            "q", np.zeros(64, np.int8)))) is None
    # dense and int8 loaders refuse the sparse artifact
    import pytest as _pytest
    with _pytest.raises(ser.PayloadError):
        ser.validated_load(data, template)
    with _pytest.raises(ser.PayloadError):
        ser.validated_load(data, delta.quantized_template(template),
                           check_dtypes=True)


def test_sparse8_hostile_marker_types_return_none():
    """The format marker is attacker bytes: string/array/float/NaN markers
    must read as not-sparse8 (None), never raise out of the decoder — a
    raised TypeError used to escape the fetch try-chain and abort the
    whole validator round (round-4 advisor, high)."""
    from distributedtraining_tpu import serialization as ser

    _, template = _sparse_case()
    for marker in ("1", b"1", np.asarray([1, 1], np.int32),
                   np.float32(np.nan), np.float32(1.0), None, [1], {"x": 1}):
        tree = {"__delta_format__": marker, "leaves": {}}
        try:
            data = ser.to_msgpack(tree)
        except Exception:
            continue  # unencodable marker can't arrive over the wire
        assert delta.sparse_delta_from_bytes(data, template) is None, marker
    # and densify itself obeys the return-None contract on direct calls
    assert delta.densify_sparse_delta(
        {"__delta_format__": "sparse8", "leaves": {}}, template) is None


def test_sparse8_artifact_bytes_against_f32():
    """What the sparse8 wire is for, as bytes: over a model's own leaf
    shapes (small biases and norms travel dense), the artifact at the
    DEFAULT density is under a quarter of the float32 bytes, so it beats
    even the dense int8 wire's 4x, and the receiver densifies it."""
    from distributedtraining_tpu import serialization as ser
    from distributedtraining_tpu.models import gpt2

    model, _ = gpt2.make_model(gpt2.GPT2Config(
        n_layer=2, n_embd=64, n_head=2, vocab_size=256, n_positions=32))
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    rs = np.random.RandomState(0)
    d = jax.tree_util.tree_map(
        lambda s: (0.01 * rs.randn(*s.shape)).astype(np.float32), shapes)
    f32_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(d))

    blob = ser.to_msgpack(jax.jit(delta.sparsify_delta)(d))
    assert len(blob) * 4 < f32_bytes, (len(blob), f32_bytes)
    template = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, np.float32), shapes)
    dense = delta.sparse_delta_from_bytes(blob, template)
    assert dense is not None
    assert (jax.tree_util.tree_structure(dense)
            == jax.tree_util.tree_structure(template))
