"""Seconds of set-up spent tracing python to jaxprs and lowering them to
MLIR modules (compile_cache.phase_seconds: trace_s + lower_s), from process
start to the first request of the window. Paid on a full cache hit too."""
from istio_tpu.compiler import cache as compile_cache


def begin(ctx):
    read_phases = getattr(compile_cache, "phase_seconds", None)
    return read_phases() if read_phases else None


def read(ctx, phases):
    return None if phases is None else phases["trace_s"] + phases["lower_s"]
