"""Content-addressed KV page transfer for disaggregated serving.

The paper's serving anatomy (and devprof's roofline table) says prefill
is compute-bound and decode is memory-bandwidth-bound — co-scheduling
them on one chip set makes ttft and tpot fight for the same resource.
Disaggregation splits the engine into phase-specialized workers: a
PREFILL worker runs the bucketed ``serve.prefill`` programs and exports
the finished request's KV pages; a DECODE worker adopts those pages
into its own ``PagePool`` and runs the paged-attention decode kernel
flat-out. This module is the transfer plane between them.

The wire unit is one page across all layers, ``[L, P, Hkv, D]`` for K
and for V: the engine gathers it from (and adoption writes it into) the
per-layer ``[pages, P, Hkv*D]`` pool arrays of engine/kv_pool.py, a few
pages at a time. Pages travel as content-addressed shards
(``__kv__.s.<sha256>``) and a per-request manifest
(``__kv__.<request-slug>``) lists the page digests in page-table order
plus the geometry and the BASE REVISION the pages were prefillied on —
the same publish/fetch + manifest-last machinery engine/basedist.py
proved for the sharded base plane:

- shards publish FIRST, the manifest LAST: a reader that can decode the
  manifest sees a complete shard set or takes a hash miss and degrades;
- every fetched page is re-hashed on receipt and compared to the
  manifest digest — a torn, stale, or hostile store can at worst serve
  bytes that fail verification;
- ANY failure (absent manifest, bad magic, hash miss, geometry or
  revision mismatch) degrades to local prefill on the decode worker —
  the transfer is an optimization, never a correctness dependency.

Content addressing buys the same dedupe economics as base shards: two
requests sharing a system-prompt prefix export bit-identical full
pages, so the second request's shards are publish no-ops and a decode
worker's page store serves them without touching the wire.
"""

from __future__ import annotations

import json
import logging
import time
from collections import OrderedDict
from typing import Callable

import jax
import numpy as np
from flax import serialization as flax_ser

from .. import serialization as ser
from ..transport import base as tbase
from ..utils import devprof, obs
from . import kv_pool

logger = logging.getLogger(__name__)

# Deliberately NOT valid msgpack (same trick as BASE_MANIFEST_MAGIC):
# a reader that lands on arbitrary msgpack bytes rejects at the magic
# check instead of mis-parsing.
KV_MANIFEST_MAGIC = b"DTKV1\n"

KV_MANIFEST_MAX_BYTES = tbase.KV_MANIFEST_MAX_BYTES
KV_PAGE_MAX_BYTES = tbase.KV_PAGE_MAX_BYTES

# page count cap per manifest: a request's page table is bounded by
# max_seq_len / page_size; 4096 pages is far beyond any toy or real
# geometry this engine serves and bounds a hostile manifest's fan-out
KV_MAX_PAGES = 4096


# ---------------------------------------------------------------------------
# Page codec
# ---------------------------------------------------------------------------

def pack_kv_page(k_page, v_page) -> bytes:
    """One page's wire bytes: the K and V ``[L, P, Hkv, D]`` slices as
    a 2-entry msgpack tree (flax serialization — the exact codec base
    shards use, so every transport that moves bases moves pages)."""
    return flax_ser.msgpack_serialize({
        "k": np.asarray(jax.device_get(k_page)),
        "v": np.asarray(jax.device_get(v_page)),
    })


def unpack_kv_page(data: bytes, *, max_bytes: int = KV_PAGE_MAX_BYTES):
    """Decode one page's bytes to ``(k, v)`` ndarrays, or None on ANY
    defect (oversize, bad msgpack, wrong keys, shape/dtype skew between
    K and V, wrong rank). Geometry agreement with the ADOPTING pool is
    the caller's check — this layer only enforces self-consistency."""
    if not isinstance(data, (bytes, bytearray)) or len(data) > max_bytes:
        return None
    try:
        raw = flax_ser.msgpack_restore(bytes(data))
    except Exception:
        return None
    if not isinstance(raw, dict) or set(raw) != {"k", "v"}:
        return None
    k, v = raw["k"], raw["v"]
    if not (isinstance(k, np.ndarray) and isinstance(v, np.ndarray)):
        return None
    if k.shape != v.shape or k.dtype != v.dtype or k.ndim != 4:
        return None
    return k, v


# ---------------------------------------------------------------------------
# Manifest codec (defensive twin of serialization.build/parse_base_manifest)
# ---------------------------------------------------------------------------

def build_kv_manifest(*, request_id: str, revision: str,
                      pages: list[tuple[str, int]],
                      geometry: dict, prompt_len: int,
                      first_token: int) -> bytes:
    """Canonical manifest bytes for one request's exported KV.

    ``pages`` is [(sha256_hex, nbytes), ...] in PAGE-TABLE ORDER (the
    order is load-bearing: page i holds prompt rows i*P..(i+1)*P).
    ``geometry`` pins the adopting pool's shape contract:
    layers/page_size/kv_heads/head_dim/dtype. ``revision`` is the base
    revision the pages were prefilled on — a decode worker on any other
    revision must refuse the transfer (KV is a pure function of params).
    ``first_token`` is the token the prefill worker's own first-token
    rule produced (greedy argmax or the counter-PRNG sample at index 0)
    — the decode worker re-emits it verbatim, which is what makes the
    disaggregated output bit-identical to the unified engine's."""
    body = {
        "format": 1,
        "request_id": str(request_id),
        "revision": str(revision),
        "prompt_len": int(prompt_len),
        "first_token": int(first_token),
        "geometry": {k: (str(v) if k == "dtype" else int(v))
                     for k, v in geometry.items()},
        "pages": [{"h": h, "n": int(n)} for h, n in pages],
    }
    data = KV_MANIFEST_MAGIC + json.dumps(
        body, sort_keys=True, separators=(",", ":")).encode()
    if len(data) > KV_MANIFEST_MAX_BYTES:
        raise ValueError(
            f"kv manifest {len(data)}B exceeds cap {KV_MANIFEST_MAX_BYTES}B")
    return data


_HEX = set("0123456789abcdef")
_GEOM_KEYS = ("layers", "page_size", "kv_heads", "head_dim", "dtype")


def parse_kv_manifest(data: bytes) -> dict | None:
    """Decode + validate manifest bytes, or None on ANY defect — the
    reader-side half of the contract, defensive like
    serialization.parse_base_manifest (bad magic, oversize, non-JSON,
    wrong format, malformed digests, absurd sizes/counts all degrade
    to 'no transfer' rather than raising into the scheduler)."""
    if not isinstance(data, (bytes, bytearray)):
        return None
    data = bytes(data)
    if not data.startswith(KV_MANIFEST_MAGIC) or \
            len(data) > KV_MANIFEST_MAX_BYTES:
        return None
    try:
        body = json.loads(data[len(KV_MANIFEST_MAGIC):])
    except Exception:
        return None
    if not isinstance(body, dict) or body.get("format") != 1:
        return None
    rid = body.get("request_id")
    rev = body.get("revision")
    if not (isinstance(rid, str) and 0 < len(rid) <= 200):
        return None
    if not (isinstance(rev, str) and len(rev) <= 200):
        return None
    plen = body.get("prompt_len")
    first = body.get("first_token")
    if not (isinstance(plen, int) and not isinstance(plen, bool)
            and plen > 0):
        return None
    if not (isinstance(first, int) and not isinstance(first, bool)
            and first >= 0):
        return None
    geom = body.get("geometry")
    if not (isinstance(geom, dict) and set(geom) == set(_GEOM_KEYS)):
        return None
    for k in _GEOM_KEYS:
        v = geom[k]
        if k == "dtype":
            if not (isinstance(v, str) and 0 < len(v) <= 32):
                return None
        elif not (isinstance(v, int) and not isinstance(v, bool)
                  and 0 < v <= 1 << 20):
            return None
    pages = body.get("pages")
    if not (isinstance(pages, list) and 0 < len(pages) <= KV_MAX_PAGES):
        return None
    out_pages: list[tuple[str, int]] = []
    for ent in pages:
        if not (isinstance(ent, dict) and set(ent) == {"h", "n"}):
            return None
        h, n = ent["h"], ent["n"]
        if not (isinstance(h, str) and len(h) == 64 and set(h) <= _HEX):
            return None
        if not (isinstance(n, int) and not isinstance(n, bool)
                and 0 < n <= KV_PAGE_MAX_BYTES):
            return None
        out_pages.append((h, n))
    return {"request_id": rid, "revision": rev, "prompt_len": plen,
            "first_token": first, "geometry": dict(geom),
            "pages": out_pages}


# ---------------------------------------------------------------------------
# Adopter-side page store (LRU by content hash, basedist.BaseShardStore twin)
# ---------------------------------------------------------------------------

DEFAULT_STORE_BYTES = 64 << 20


class KVPageStore:
    """Content-addressed LRU over verified (k, v) page pairs. A decode
    worker adopting many requests that share a system prompt hits this
    store for the shared full pages and never touches the wire."""

    def __init__(self, max_bytes: int = DEFAULT_STORE_BYTES):
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_bytes = max_bytes
        self._entries: "OrderedDict[str, tuple]" = OrderedDict()
        self._nbytes = 0

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, digest: str):
        ent = self._entries.get(digest)
        if ent is None:
            return None
        self._entries.move_to_end(digest)
        return ent

    def put(self, digest: str, k: np.ndarray, v: np.ndarray) -> None:
        if digest in self._entries:
            self._entries.move_to_end(digest)
            return
        nb = k.nbytes + v.nbytes
        self._entries[digest] = (k, v)
        self._nbytes += nb
        while self._nbytes > self.max_bytes and len(self._entries) > 1:
            _, (ok, ov) = self._entries.popitem(last=False)
            self._nbytes -= ok.nbytes + ov.nbytes

    def clear(self) -> None:
        self._entries.clear()
        self._nbytes = 0


# ---------------------------------------------------------------------------
# Exporter (prefill worker) / adopter fetch (decode worker)
# ---------------------------------------------------------------------------

class KVExporter:
    """Prefill-worker side: publish one request's KV pages as
    content-addressed shards, then the manifest LAST. The session-local
    digest set is the dedupe ledger (the ``_last_shards`` idiom): a
    page already published this session is a wire no-op — re-publishing
    a content-addressed slot is idempotent anyway, the set just saves
    the bytes."""

    def __init__(self, transport):
        self.transport = transport
        self._published: set[str] = set()
        self.exports = 0
        self.bytes_published = 0

    def export(self, *, request_id: str, revision: str,
               pages, prompt_len: int, first_token: int,
               page_size: int) -> bool:
        """Publish ``pages`` ([(k, v) ndarray pairs] in page-table
        order) + the manifest. True on success; False leaves no
        readable manifest (manifest-last), so the decode side simply
        prefills locally."""
        t0 = time.perf_counter()
        try:
            entries: list[tuple[str, int]] = []
            fresh = 0
            for k, v in pages:
                data = pack_kv_page(k, v)
                digest = ser.shard_digest(data)
                entries.append((digest, len(data)))
                if digest in self._published:
                    obs.count("serve.kv_pages_deduped")
                    continue
                tbase.publish_kv_page(self.transport, digest, data)
                self._published.add(digest)
                fresh += 1
                self.bytes_published += len(data)
                obs.count("serve.kv_export_bytes", len(data))
            k0, v0 = pages[0]
            manifest = build_kv_manifest(
                request_id=request_id, revision=revision or "",
                pages=entries,
                geometry={"layers": k0.shape[0], "page_size": page_size,
                          "kv_heads": k0.shape[2], "head_dim": k0.shape[3],
                          "dtype": str(k0.dtype)},
                prompt_len=prompt_len, first_token=first_token)
            tbase.publish_kv_manifest(self.transport, request_id, manifest)
            self.bytes_published += len(manifest)
            obs.count("serve.kv_export_bytes", len(manifest))
        except Exception:
            logger.exception("kv export failed for request %s", request_id)
            obs.count("serve.kv_export_failures")
            return False
        self.exports += 1
        obs.count("serve.kv_exports")
        obs.count("serve.kv_pages_exported", len(pages))
        obs.observe("serve.kv_export_ms",
                    (time.perf_counter() - t0) * 1e3)
        return True


class KVAdopter:
    """Decode-worker side: fetch + verify one request's exported KV.

    ``fetch`` returns the parsed manifest with ``pages`` replaced by
    verified ``(k, v)`` ndarray pairs, or None on ANY transfer defect
    (absent/torn manifest, shard miss, hash mismatch, self-inconsistent
    page). Revision and geometry agreement are the ENGINE's checks —
    it owns both sides of that contract and counts the mismatch
    distinctly (a revision skew is a routing event, not a transfer
    fault)."""

    def __init__(self, transport, *, store: KVPageStore | None = None):
        self.transport = transport
        self.store = store if store is not None else KVPageStore()
        self.adoptions = 0
        self.bytes_fetched = 0

    def fetch(self, request_id: str) -> dict | None:
        t0 = time.perf_counter()
        raw = tbase.fetch_kv_manifest_bytes(self.transport, request_id)
        if raw is None:
            obs.count("serve.kv_manifest_misses")
            return None
        man = parse_kv_manifest(raw)
        if man is None:
            obs.count("serve.kv_manifest_rejects")
            return None
        out_pages = []
        for digest, nbytes in man["pages"]:
            hit = self.store.lookup(digest)
            if hit is not None:
                obs.count("serve.kv_pages_deduped")
                out_pages.append(hit)
                continue
            data = tbase.fetch_kv_page(self.transport, digest)
            if data is None or len(data) != nbytes or \
                    ser.shard_digest(data) != digest:
                # torn publication, eviction, or a hostile store —
                # every one degrades identically: no transfer
                obs.count("serve.kv_page_rejects")
                return None
            pair = unpack_kv_page(data)
            if pair is None:
                obs.count("serve.kv_page_rejects")
                return None
            self.bytes_fetched += len(data)
            obs.count("serve.kv_fetch_bytes", len(data))
            self.store.put(digest, *pair)
            out_pages.append(pair)
        self.adoptions += 1
        obs.observe("serve.kv_fetch_ms", (time.perf_counter() - t0) * 1e3)
        return {**man, "pages": out_pages}


# ---------------------------------------------------------------------------
# The adoption write program (serve.kv_adopt)
# ---------------------------------------------------------------------------

def make_adopt_prog(donate: bool) -> Callable:
    """One jitted page write: scatter a fetched ``[L, P, Hkv, D]`` K/V
    pair into pool slot ``dst``, layer by layer (engine/kv_pool.py).
    Bucket-free (page geometry is static
    per engine), compiled ONCE at the first adoption and warm forever —
    the decode worker's zero-steady-state-compiles pin covers it. The
    serve engine owns the ``_timed_compile`` first-call accounting,
    exactly like its ``serve.page_copy`` twin."""
    def kv_adopt(k_pages, v_pages, k_new, v_new, dst):
        return kv_pool.adopt_page(k_pages, v_pages, k_new, v_new, dst)

    return devprof.wrap(
        "serve.kv_adopt",
        jax.jit(kv_adopt, donate_argnums=(0, 1) if donate else ()),
        bucket=1)
