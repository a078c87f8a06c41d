"""Persistent-compile-cache lookups (hits + misses) inside the window:
each is a program traced and lowered while requests waited."""
from istio_tpu.compiler import cache as compile_cache


def _lookups() -> int:
    counts = compile_cache.cache_event_counts()
    return counts["hits"] + counts["misses"]


def begin(ctx):
    return _lookups()


def read(ctx, base):
    return _lookups() - base
