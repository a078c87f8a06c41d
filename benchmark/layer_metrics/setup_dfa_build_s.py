"""Seconds of set-up spent turning the snapshot's constant regexes into
DFAs and packing them into banks, inside the host rule compile: the
span `build.dfa` (two intervals a compile: regex -> DFA while the
requirements are collected, then guard detection and the pack), from
process start to the first request of the window. A program without the
span is not read."""
from spans import window_spans


def begin(ctx):
    seen = (window_spans({}) or {}).get("build.dfa")
    return seen["sum_ms"] / 1e3 if seen else None


def read(ctx, seconds):
    return seconds
