"""Native C++ packer vs the pure-Python oracle: exact output parity.

The C++ path (native/packing.cpp) must be bit-identical to pack_documents'
Python loop for every field, including the chunked-streaming wrapper that
feeds it bounded buffers.
"""

import numpy as np
import pytest

from distributedtraining_tpu import native
from distributedtraining_tpu.data import packing


def _collect(it):
    rows = list(it)
    if not rows:
        return None
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}


def _random_docs(rng, n_docs, max_len):
    return [list(rng.integers(1, 1000, rng.integers(0, max_len + 1)))
            for _ in range(n_docs)]


requires_native = pytest.mark.skipif(native.load("packing") is None,
                                     reason="native toolchain unavailable")


@requires_native
@pytest.mark.parametrize("seq_len,drop", [(16, True), (16, False),
                                          (64, True), (64, False)])
def test_native_matches_oracle(seq_len, drop):
    rng = np.random.default_rng(0)
    docs = _random_docs(rng, 200, 3 * seq_len)  # includes empty + long docs
    want = _collect(packing.pack_documents(docs, seq_len,
                                           drop_remainder=drop,
                                           native=False))
    got = _collect(packing.pack_documents(docs, seq_len,
                                          drop_remainder=drop, native=True))
    assert want.keys() == got.keys()
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)


@requires_native
def test_native_chunked_streaming_matches_oracle():
    """Tiny chunk budget forces many native calls with carry-over tails."""
    rng = np.random.default_rng(1)
    seq_len = 32
    docs = _random_docs(rng, 300, 2 * seq_len)
    want = _collect(packing.pack_documents(docs, seq_len,
                                           drop_remainder=False,
                                           native=False))
    got = _collect(packing._pack_documents_native(
        iter(docs), seq_len, drop_remainder=False, chunk_tokens=64))
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)


@requires_native
def test_native_empty_and_degenerate():
    assert _collect(packing.pack_documents([], 16, native=True)) is None
    # single doc exactly one row
    doc = list(range(1, 17))
    got = _collect(packing.pack_documents([doc], 16, native=True))
    want = _collect(packing.pack_documents([doc], 16, native=False))
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)


@requires_native
def test_native_packer_is_taken_on_array_docs(monkeypatch):
    """On a realistic workload of array documents (what HF tokenizers hand
    back: the zero-conversion path) ``native=True`` really runs the C++
    packer, ``native=False`` never touches it, and both pack the same
    sequences. How much faster the native path is belongs to a host
    benchmark, not to a test under load."""
    rng = np.random.default_rng(2)
    docs = [rng.integers(1, 50000, 700).astype(np.int32)
            for _ in range(400)]
    calls = []
    real = packing._pack_documents_native

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(packing, "_pack_documents_native", counted)
    py = _collect(packing.pack_documents(docs, 1024, native=False))
    assert calls == []
    nat = _collect(packing.pack_documents(docs, 1024, native=True))
    assert calls == [1]
    assert py.keys() == nat.keys()
    for k in py:
        np.testing.assert_array_equal(py[k], nat[k], err_msg=k)
