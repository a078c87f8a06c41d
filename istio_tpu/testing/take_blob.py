"""A take blob written in python — what native/httpd.cpp take_impl
writes, for tests that feed the native front's pump without a socket.

Rows are the tuples the front's per-row parser used to make:
(tag, kind, payload, global_word_count, dedup id, {quota name:
(amount, best_effort)}, traceparent)."""
from __future__ import annotations

import struct

import numpy as np

from istio_tpu.api.take import TAKE_ROW


def encode_take(items: list[tuple], batch: int = 0) -> bytes:
    index = np.zeros(len(items), TAKE_ROW)
    heap = bytearray()
    base = 8 + index.nbytes

    def put(raw: bytes) -> tuple[int, int]:
        at = base + len(heap)
        heap.extend(raw)
        return at, len(raw)

    for row, (tag, kind, payload, gwc, dedup, quotas, traceparent) in \
            zip(index, items):
        row["tag"], row["kind"], row["gwc"] = tag, kind, gwc
        row["payload_off"], row["payload_len"] = put(payload)
        row["dedup_off"], row["dedup_len"] = put(dedup.encode())
        row["traceparent_off"], row["traceparent_len"] = \
            put(traceparent.encode())
        row["quota_off"], row["quota_count"] = base + len(heap), \
            len(quotas)
        for name, (amount, best_effort) in quotas.items():
            put(struct.pack("<I", len(name.encode())) + name.encode()
                + struct.pack("<qB", amount, best_effort))
    return struct.pack("<II", batch, len(items)) + index.tobytes() \
        + bytes(heap)
