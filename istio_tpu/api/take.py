"""A taken batch, read in place — the blob h2srv_take hands a pump.

native/httpd.cpp take_impl writes one blob a take: u32 batch number,
u32 n, n rows of a fixed-width index (TAKE_ROW mirrors struct TakeRow
field for field), then the heap the rows point into (payload, dedup
id, traceparent, quota section; offsets from the blob's first byte).
The rows of a take are columns of that one buffer, not python
objects: TakenRows answers what the serving path asks of a batch
(length, padding, the payload spans the C++ tensorizer reads) from the
index alone, and makes a LazyWireBag only for a row that something on
the host asks for.
"""
from __future__ import annotations

import struct
from collections.abc import Sequence

import numpy as np

from istio_tpu.api.wire import LazyWireBag
from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST
from istio_tpu.runtime import monitor
from istio_tpu.runtime.batcher import PadBag

TAKE_ROW = np.dtype([
    ("tag", "<u8"), ("payload_off", "<u4"), ("payload_len", "<u4"),
    ("gwc", "<u4"), ("dedup_off", "<u4"), ("dedup_len", "<u4"),
    ("traceparent_off", "<u4"), ("traceparent_len", "<u4"),
    ("quota_off", "<u4"), ("quota_count", "<u2"), ("kind", "u1"),
    ("reserved", "u1", (5,))])
assert TAKE_ROW.itemsize == 48      # static_assert in httpd.cpp

CHECK, REPORT = 0, 1


class TakenRows(Sequence):
    """Rows of one take as a Sequence[Bag]: `index` (TAKE_ROW) names
    the real rows, `pads` counts the bucket padding behind them, and
    row i becomes a LazyWireBag, memoised, the first time it is asked
    for. The bag OWNS a copy of its payload: a deferred quota row is
    finished from a pool thread after the pump has taken again, and a
    canary or exemplar tap may keep a bag. Slices and the padded /
    trimmed forms share the index, the buffer and the memo."""

    __slots__ = ("_blob", "index", "pads", "_bags", "_at")

    def __init__(self, blob: np.ndarray, index: np.ndarray,
                 pads: int = 0, bags: dict | None = None, at: int = 0):
        self._blob = blob          # uint8 view of the pump's buffer
        self.index = index
        self.pads = pads
        self._bags = {} if bags is None else bags
        self._at = at              # row 0's key in the shared memo

    @classmethod
    def read(cls, buf) -> "TakenRows":
        """Every row of the blob in `buf` (anything with the buffer
        protocol: the pump's ctypes buffer, bytes), nothing copied."""
        (_, n) = struct.unpack_from("<II", buf, 0)
        return cls(np.frombuffer(buf, np.uint8),
                   np.frombuffer(buf, TAKE_ROW, n, 8))

    def of_kind(self, kind: int) -> "TakenRows":
        """The rows of one kind (CHECK / REPORT), in take order."""
        mine = self.index["kind"] == kind
        return self if mine.all() else \
            TakenRows(self._blob, self.index[mine])

    # -- the batch as the serving path sees it --

    def __len__(self) -> int:
        return len(self.index) + self.pads

    @property
    def real(self) -> "TakenRows":
        """Without the padding rows (batcher.trim_pads)."""
        return self if not self.pads else TakenRows(
            self._blob, self.index, 0, self._bags, self._at)

    def pad_to(self, target: int) -> "TakenRows":
        """Padded to `target` rows (batcher.pad_to_bucket): a count,
        not a PadBag a row."""
        return TakenRows(self._blob, self.index,
                         max(target - len(self.index), 0), self._bags,
                         self._at)

    def __getitem__(self, i):
        real = len(self.index)
        if isinstance(i, slice):
            lo, hi, step = i.indices(real + self.pads)
            if step != 1:
                raise ValueError("a taken batch is sliced in row order")
            hi = max(hi, lo)
            return TakenRows(self._blob, self.index[lo:min(hi, real)],
                             hi - max(lo, min(hi, real)), self._bags,
                             self._at + lo)
        if i < 0:
            i += real + self.pads
        if not 0 <= i < real + self.pads:
            raise IndexError(i)
        if i >= real:
            return PadBag()
        bag = self._bags.get(self._at + i)
        if bag is None:
            # one .item() a row: a numpy scalar a field costs more
            _, off, length, gwc, *_ = self.index[i].item()
            bag = self._bags[self._at + i] = LazyWireBag(
                self._blob[off:off + length].tobytes(), gwc or None,
                native_ok=gwc in (0, len(GLOBAL_WORD_LIST)))
            monitor.FRONT_BAGS_MATERIALISED.inc()
        return bag

    def wire_spans(self) -> tuple[int, np.ndarray, np.ndarray] | None:
        """(buffer address, payload offsets, payload lengths), a row
        of the batch each, padding rows empty: what the C++ tensorizer
        reads, in place. None where a row's dictionary prefix is not
        the one the C++ decoder assumes (the bags' `wire` says so row
        by row)."""
        gwc = self.index["gwc"]
        if ((gwc != 0) & (gwc != len(GLOBAL_WORD_LIST))).any():
            return None
        real = len(self.index)
        offsets = np.zeros(real + self.pads, np.uint64)
        lengths = np.zeros(real + self.pads, np.int64)
        offsets[:real] = self.index["payload_off"]
        lengths[:real] = self.index["payload_len"]
        # native code reads these addresses: no span past the buffer
        if real and int((offsets + lengths.view(np.uint64)).max()) \
                > self._blob.size:
            raise ValueError("take index points past its buffer")
        return self._blob.ctypes.data, offsets, lengths

    # -- what the front reads of a row beside its bag --

    @property
    def tags(self) -> np.ndarray:
        return self.index["tag"]

    def _bytes(self, i: int, field: str) -> bytes:
        row = self.index[i]
        off = int(row[field + "_off"])
        return self._blob[off:off + int(row[field + "_len"])].tobytes()

    def payload(self, i: int) -> bytes:
        return self._bytes(i, "payload")

    def dedup_id(self, i: int) -> str:
        return self._bytes(i, "dedup").decode("utf-8", "replace")

    def traceparents(self):
        """The traceparent headers sent, in row order; rows without
        one are not looked at."""
        for i in np.flatnonzero(self.index["traceparent_len"]).tolist():
            yield self._bytes(i, "traceparent").decode("utf-8", "replace")

    def asking(self) -> list[int]:
        """The rows that ask for a quota."""
        return np.flatnonzero(self.index["quota_count"]).tolist()

    def asks_quota(self) -> bool:
        """Whether a row asks for a quota."""
        return bool(self.index["quota_count"].any())

    def quotas(self, i: int) -> dict[str, tuple[int, bool]]:
        """Row i's quota section → {name: (amount, best_effort)}."""
        row = self.index[i]
        blob, off = self._blob, int(row["quota_off"])
        out = {}
        for _ in range(int(row["quota_count"])):
            (nlen,) = struct.unpack_from("<I", blob, off)
            name = blob[off + 4:off + 4 + nlen].tobytes().decode(
                "utf-8", "replace")
            amount, be = struct.unpack_from("<qB", blob, off + 4 + nlen)
            out[name] = (amount, bool(be))
            off += 4 + nlen + 9
        return out
