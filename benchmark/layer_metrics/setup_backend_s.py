"""Seconds of set-up spent in the backend compile, which on a persistent
cache hit is the load of the executable (compile_cache.phase_seconds:
backend_s), from process start to the first request of the window."""
from istio_tpu.compiler import cache as compile_cache


def begin(ctx):
    read_phases = getattr(compile_cache, "phase_seconds", None)
    return read_phases() if read_phases else None


def read(ctx, phases):
    return None if phases is None else phases["backend_s"]
