"""ctypes wrapper: NativeTensorizer — wire bytes → AttributeBatch.

Drop-in accelerated replacement for compiler/layout.Tensorizer on the
serving path: input is serialized istio.mixer.v1.CompressedAttributes
records (what Check RPCs carry), output is the same AttributeBatch the
device step consumes; the python Tensorizer stays the conformance
oracle. The shim reads each record where it lies (no protobuf message,
no copy of a word or a value it has seen before) and owns the
authoritative intern table of runtime values: compile-time constants
seed it in the python InternTable's id order, and what a batch adds is
exported after the batch into `_runtime_values` under negative
per-batch ids, never into the InternTable.
"""
from __future__ import annotations

import ctypes
import dataclasses
import datetime
import struct
from typing import Any, Sequence

import numpy as np

from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST
from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import (AttributeBatch, BatchLayout,
                                       InternTable, WideRows, _normalize,
                                       canonical_bytes)
from istio_tpu.native.build import ensure_built

_MAGIC = 0x49545032   # v2: byte-slot records carry an encoding kind

# byte-slot encoding kinds (shim.cpp must mirror): 0 utf-8 attr,
# 1 utf-8 (map,key), then numeric order-key slots
_BYTE_KINDS = {ValueType.INT64: 2, ValueType.DOUBLE: 3,
               ValueType.DURATION: 4, ValueType.TIMESTAMP: 5}


_canonical_key = canonical_bytes     # shared canonical encoding


def _decode_key(raw: bytes) -> Any:
    tag, payload = chr(raw[0]), raw[1:]
    if tag == "b":
        return payload == b"\x01"
    if tag == "i":
        return struct.unpack("<q", payload)[0]
    if tag == "d":
        return struct.unpack("<d", payload)[0]
    if tag == "s":
        return payload.decode("utf-8")
    if tag == "p":
        return payload
    if tag == "D":
        ns = struct.unpack("<q", payload)[0]
        return datetime.timedelta(microseconds=ns / 1000)
    if tag == "t":
        ns = struct.unpack("<q", payload)[0]
        return datetime.datetime.fromtimestamp(ns / 1e9,
                                               datetime.timezone.utc)
    raise ValueError(f"unknown intern tag {tag}")


def _pack_str(s: str | bytes) -> bytes:
    raw = s.encode("utf-8") if isinstance(s, str) else bytes(s)
    return struct.pack("<I", len(raw)) + raw


def _layout_blob(layout: BatchLayout, interner: InternTable) -> bytes:
    out = [struct.pack("<II", _MAGIC, layout.max_str_len)]
    out.append(struct.pack("<I", len(GLOBAL_WORD_LIST)))
    out += [_pack_str(w) for w in GLOBAL_WORD_LIST]
    out.append(struct.pack("<I", len(layout.slots)))
    for name, col in layout.slots.items():
        out.append(struct.pack("<I", col) + _pack_str(name))
    out.append(struct.pack("<I", len(layout.map_slots)))
    for name, col in layout.map_slots.items():
        out.append(struct.pack("<I", col) + _pack_str(name))
    out.append(struct.pack("<I", len(layout.derived_slots)))
    for (m, k), col in layout.derived_slots.items():
        out.append(struct.pack("<I", col) + _pack_str(m) + _pack_str(k))
    out.append(struct.pack("<I", len(layout.byte_slots)))
    for src, bcol in layout.byte_slots.items():
        if isinstance(src, tuple):
            # kind 1: (map, key) utf-8 slot
            out.append(struct.pack("<IB", bcol, 1) + _pack_str(src[0]) +
                       _pack_str(src[1]))
        else:
            # kind 0: utf-8 attr; kinds 2-5: numeric slots carrying the
            # 8-byte order key (layout.order_key_bytes — the shim must
            # produce IDENTICAL bytes so ordered comparisons agree)
            kind = _BYTE_KINDS.get(layout.manifest.get(src), 0)
            out.append(struct.pack("<IB", bcol, kind) + _pack_str(src))
    out.append(struct.pack("<III", layout.n_columns, layout.n_maps,
                           layout.n_byte_slots))
    # seed interns in id order (ids 3..)
    with interner._lock:
        keys = [_canonical_key(key) for key, idx in
                sorted(interner._by_key.items(), key=lambda kv: kv[1])
                if idx >= 3]
    out.append(struct.pack("<I", len(keys)))
    out += [_pack_str(k) for k in keys]
    return b"".join(out)


class NativeTensorizer:
    """Wire → AttributeBatch via the C++ shim, with ZERO-COPY staging
    for hot batch shapes: the shim writes word values / string bytes
    straight into persistent, page-aligned slot-tensor staging buffers
    (a ring per batch shape, rotated per decode), so the dominant
    shapes pay no per-batch numpy allocation and no astype copies —
    presence planes are returned as dtype VIEWS of the staging bytes.

    Buffer lifecycle contract: the arrays inside a returned
    AttributeBatch stay valid for the next `staging_depth - 1`
    decodes of the SAME shape on this tensorizer. The serving path
    honors the bound by construction — the batcher pipelines at most
    `pipeline` (< staging_depth; RuntimeServer._bound_staging_depth
    raises the ring depth to cover a user-raised pipeline) batches
    and every consumer finishes its host reads before the batch
    future resolves. At most _STAGING_SHAPES shapes keep rings,
    evicted least-recently-used — eviction is safe because in-flight
    batches keep the old slots alive by reference; the evicted
    shape's next decode simply re-allocates."""

    # distinct batch shapes that keep staging rings (the serving
    # bucket ladder is 3-4 shapes; LRU-evicted past the cap so
    # adversarial shape churn can neither leak memory nor pin the
    # rings on cold shapes)
    _STAGING_SHAPES = 4

    def __init__(self, layout: BatchLayout, interner: InternTable,
                 staging_depth: int = 8):
        import threading
        from istio_tpu.runtime import monitor   # lazy: runtime imports us
        self._monitor = monitor
        self.layout = layout
        self.interner = interner
        self.staging_depth = max(int(staging_depth), 2)
        # shape key (n rows) → (next slot idx, [slot dicts])
        self._staging: dict[int, list] = {}
        self._call_lock = threading.Lock()
        lib = ctypes.CDLL(ensure_built())
        lib.shim_create.restype = ctypes.c_void_p
        lib.shim_create.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.shim_destroy.argtypes = [ctypes.c_void_p]
        lib.shim_error.restype = ctypes.c_char_p
        lib.shim_error.argtypes = [ctypes.c_void_p]
        lib.shim_intern_count.restype = ctypes.c_int32
        lib.shim_intern_count.argtypes = [ctypes.c_void_p]
        lib.shim_export_interns.restype = ctypes.c_int64
        lib.shim_export_interns.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.shim_flush_interns.restype = None
        lib.shim_flush_interns.argtypes = [ctypes.c_void_p,
                                           ctypes.c_int32]
        lib.shim_tensorize.restype = ctypes.c_int32
        lib.shim_tensorize.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
        self._lib = lib
        if layout.extern_slots:
            raise RuntimeError(
                "layout has ingest-converted extern columns "
                f"({sorted(layout.extern_slots)}); the native shim "
                "cannot run extern conversions")
        blob = _layout_blob(layout, interner)
        self._h = lib.shim_create(blob, len(blob))
        if not self._h:
            raise RuntimeError("shim_create failed (bad layout blob)")
        self._seed_count = lib.shim_intern_count(self._h)
        self._known_ids = self._seed_count
        # shim id → python id. Seeds preserve python id order (identity
        # prefix). Runtime-observed shim ids map to NEGATIVE per-batch
        # ephemeral ids (-1 - k) indexing `_runtime_values[k]` — they
        # never enter the python intern table (bounded memory; see
        # InternTable docstring). `_runtime_values` is replaced, not
        # mutated, on flush so in-flight batches keep their snapshot.
        self._remap = np.arange(self._seed_count, dtype=np.int32)
        self._runtime_values: list = []
        self._flush_threshold = 1 << 17   # ~131k distinct values
        self._staged_decodes = 0

    def tensorize_wire(self, records: Sequence[bytes]) -> AttributeBatch:
        """Records held as `bytes`, one a row (the gRPC and BatchCheck
        fronts, the batcher)."""
        n = len(records)
        return self._tensorize(
            n, (ctypes.c_char_p * n)(*records),
            (ctypes.c_int64 * n)(*[len(r) for r in records]))

    def tensorize_spans(self, base: int, offsets: np.ndarray,
                        lengths: np.ndarray) -> AttributeBatch:
        """Records that lie in one buffer at address `base` (a taken
        batch, api/take.TakenRows.wire_spans): row i is `lengths[i]`
        (int64) bytes at `offsets[i]` (uint64); an empty row is a
        padding row. The pointer array is one numpy add, laid out as
        the shim's `const uint8_t* const*`; the caller keeps the
        buffer alive and unwritten for the call (the shim reads words
        and values in place)."""
        ptrs = offsets + np.uint64(base)
        return self._tensorize(
            len(ptrs),
            ptrs.ctypes.data_as(ctypes.POINTER(ctypes.c_char_p)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))

    def _tensorize(self, n: int, bufs, lens) -> AttributeBatch:
        # one decode at a time. The lock guards the shim handle (its
        # intern table and the scratch it reads a record into), the
        # remap array and `_runtime_values` that follow the table, and
        # the staging-ring rotation. Both pumps (and the batcher pool's
        # pipelined batches) share this tensorizer, so they queue here:
        # span `tensorize.call_wait` is that wait and nothing else
        with self._monitor.span("tensorize.call_wait"):
            self._call_lock.acquire()
        try:
            return self._tensorize_locked(n, bufs, lens)
        finally:
            self._call_lock.release()

    @staticmethod
    def _aligned_zeros(shape: tuple, dtype) -> np.ndarray:
        """Page-aligned persistent staging buffer: the h2d engine can
        DMA-map a 4096-aligned region without the bounce copy an
        arbitrary numpy heap pointer may force."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        if nbytes == 0:
            return np.zeros(shape, dtype)
        raw = np.zeros(nbytes + 4096, np.uint8)
        off = (-raw.ctypes.data) % 4096
        return raw[off:off + nbytes].view(dtype).reshape(shape)

    def _fresh_buffers(self, n: int, aligned: bool = False) -> dict:
        lay = self.layout
        nmap = max(lay.n_maps, 1)
        nbyte = max(lay.n_byte_slots, 1)
        alloc = self._aligned_zeros if aligned else np.zeros
        bufs = {
            "ids": alloc((n, lay.n_columns), np.int32),
            "hash_ids": alloc((n, lay.n_columns), np.int32),
            "present_u8": alloc((n, max(lay.n_columns, 0)), np.uint8),
            "map_present_u8": alloc((n, nmap), np.uint8),
            "str_bytes": alloc((n, nbyte, lay.max_str_len), np.uint8),
            "str_lens": alloc((n, nbyte), np.int32),
        }
        if lay.wide_str_len:
            # the wide rows' planes (layout.WideRows). Never zeroed
            # here: the shim zeroes a row as it claims it, so the pages
            # of rows no batch has claimed are never touched, and a
            # deployment whose strings all fit holds them as address
            # space alone
            bufs["wide"] = WideRows(
                row=np.zeros(n, np.int32),
                data=np.zeros((n, nbyte, lay.wide_str_len), np.uint8),
                lens=np.zeros((n, nbyte), np.int32), count=0)
        return bufs

    def _buffers_for(self, n: int) -> dict:
        """Staging-ring slot for batch shape `n` (zeroed, ready for
        the shim). Ring slots are allocated lazily up to
        staging_depth, then reused round-robin — the reuse bound
        callers rely on. The shape→ring map is LRU-bounded: a new
        shape past the cap evicts the least-recently-used ring (dict
        insertion order = access order; in-flight batches keep
        evicted slots alive by reference, so eviction never clobbers
        a live buffer — the evicted shape just re-allocates next
        time). Note the serving path decodes BUCKET-padded batches,
        so the live shape set is the bucket ladder, not raw arrival
        counts."""
        ring = self._staging.pop(n, None)
        if ring is not None and ring["depth"] != self.staging_depth:
            # depth changed mid-life (RuntimeServer raising the bound
            # for a deeper pipeline): re-anchoring `next` onto a new
            # modulus can shrink the reuse distance below the old
            # bound, so start a FRESH ring instead — in-flight
            # batches keep the old slots alive by reference, exactly
            # like LRU eviction
            ring = None
        if ring is None:
            if len(self._staging) >= self._STAGING_SHAPES:
                # evict the least-recently-used shape's ring
                evicted = next(iter(self._staging))
                del self._staging[evicted]
            ring = {"next": 0, "slots": [],
                    "depth": self.staging_depth}
        self._staging[n] = ring   # (re)insert at the MRU end
        idx = ring["next"] % self.staging_depth
        ring["next"] += 1
        if idx >= len(ring["slots"]):
            slot = self._fresh_buffers(n, aligned=True)
            ring["slots"].append(slot)
        else:
            slot = ring["slots"][idx]
            for name, arr in slot.items():
                if name != "wide":
                    arr[...] = 0
        self._staged_decodes += 1
        return slot

    def staging_stats(self) -> dict:
        return {"shapes": {n: len(r["slots"])
                           for n, r in self._staging.items()},
                "depth": self.staging_depth,
                "staged_decodes": self._staged_decodes}

    def _tensorize_locked(self, n: int, bufs, lens) -> AttributeBatch:
        """`bufs` / `lens`: the n records' addresses and lengths, as
        the shim takes them."""
        lay = self.layout
        buf_set = self._buffers_for(n)
        ids = buf_set["ids"]
        hash_ids = buf_set["hash_ids"]
        present_u8 = buf_set["present_u8"]
        map_present_u8 = buf_set["map_present_u8"]
        str_bytes = buf_set["str_bytes"]
        str_lens = buf_set["str_lens"]

        wide = buf_set.get("wide")
        n_wide = ctypes.c_int32(0)
        rc = self._lib.shim_tensorize(
            self._h, bufs, lens, n,
            ids.ctypes.data_as(ctypes.c_void_p),
            hash_ids.ctypes.data_as(ctypes.c_void_p),
            present_u8.ctypes.data_as(ctypes.c_void_p),
            map_present_u8.ctypes.data_as(ctypes.c_void_p),
            str_bytes.ctypes.data_as(ctypes.c_void_p),
            str_lens.ctypes.data_as(ctypes.c_void_p),
            *([None] * 3 if wide is None else
              [a.ctypes.data_as(ctypes.c_void_p)
               for a in (wide.data, wide.lens, wide.row)]),
            lay.wide_str_len, ctypes.byref(n_wide))
        if rc != 0:
            raise ValueError(self._lib.shim_error(self._h).decode())
        self._sync_interns()
        ephemeral = self._runtime_values
        if ids.size:
            # translate shim id space → python id space so the ids plane
            # compares equal against compiled constants / list entries
            np.take(self._remap, ids, out=ids)
        if len(ephemeral) > self._flush_threshold:
            # bound intern memory: drop runtime entries from the shim
            # and start a fresh side table; `ephemeral` (this batch's
            # snapshot) stays alive as long as the batch does
            self._lib.shim_flush_interns(self._h, self._seed_count)
            self._known_ids = self._seed_count
            self._remap = np.arange(self._seed_count, dtype=np.int32)
            self._runtime_values = []
        # presence planes are dtype VIEWS of the staging bytes (bool
        # is 1 byte) — zero copies on the decode path; the view shares
        # the ring slot's lifecycle like every other plane
        return AttributeBatch(ids=ids, present=present_u8.view(bool),
                              map_present=map_present_u8.view(bool),
                              str_bytes=str_bytes, str_lens=str_lens,
                              hash_ids=hash_ids,
                              ephemeral_values=ephemeral,
                              wide=wide and dataclasses.replace(
                                  wide, count=n_wide.value))

    def _sync_interns(self) -> None:
        """Extend the shim→python id remap with newly observed values.

        New shim ids are runtime values (every compile-time constant
        was seeded): each maps to the negative ephemeral id of its
        slot in `_runtime_values` — stable across batches until the
        flush replaces the side table."""
        count = self._lib.shim_intern_count(self._h)
        if count == self._known_ids:
            return
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            got = self._lib.shim_export_interns(self._h, self._known_ids,
                                                buf, cap)
            if got >= 0:
                raw = buf.raw[:got]
                break
            cap = -got
        off = 0
        new_ids = []
        while off < len(raw):
            (k_len,) = struct.unpack_from("<I", raw, off)
            off += 4
            key = raw[off:off + k_len]
            off += k_len
            new_ids.append(-1 - len(self._runtime_values))
            self._runtime_values.append(_decode_key(key))
        self._remap = np.concatenate(
            [self._remap, np.asarray(new_ids, np.int32)])
        self._known_ids = count

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h:
            self._lib.shim_destroy(h)
