"""What a DFA bank scan has to move, counted from the deployment's
semantics and not from any implementation of the scan.

A request names one `destination.service`, and only that host's match
blocks can change its verdict: behind a false
`destination.service == X` Go's `&&` never evaluates the regex. For
each such block the scan reads, per byte of the subject it is matched
against, one transition: 4 bytes (an int32 next state), the least a
table-driven automaton reads. The subject's own bytes are read once.

    bytes = rows x sum over subjects of
              (blocks a host holds on the subject x subject bytes x 4
               + subject bytes)

The scan is bound by memory (it computes nothing but addresses), so
its roofline is that over the chip's memory bandwidth. A scan that
visits the whole bank (10 000 automata a row, not a host's ten), or
256 columns a state, moves far more than this and reads a small
share: that is what the share is for.
"""
from __future__ import annotations

TRANSITION_BYTES = 4

# Peak HBM bytes/s by jax's device_kind. v5e: Google Cloud
# documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def hbm_bytes_per_s(device_kind: str) -> float:
    """A device that is not in the table is an error, not a default."""
    if device_kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no memory bandwidth on record for "
                       f"{device_kind!r}: add it, with its source")
    return HBM_BYTES_PER_S[device_kind]


def scan_bytes(rows: float, subjects) -> float:
    """`subjects`: (blocks a host holds on the subject, mean subject
    bytes a row) per scanned subject, an absent subject counting 0
    bytes -> the bytes one batch of `rows` rows has to move."""
    return rows * sum(blocks * length * TRANSITION_BYTES + length
                      for blocks, length in subjects)


def roofline_share_pct(rows: float, subjects, scan_ms: float,
                       device_kind: str) -> float:
    """The least time the device could take to move scan_bytes, over
    the time the scan took, in per cent."""
    least_s = scan_bytes(rows, subjects) / hbm_bytes_per_s(device_kind)
    return 100.0 * least_s / (scan_ms / 1e3)
