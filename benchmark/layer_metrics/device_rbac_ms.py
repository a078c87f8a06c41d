"""Mean device time of one served step under its `rbac` section (the
allow-row gather over the pseudo-rules' matched plane and the verdict
merge): the trace's `XLA Ops` events whose scope path lies under
`jax.named_scope("rbac")`, over the `jit_step` programs
(scopes.scope_ms_per_step)."""
import time

from scopes import read_window


def begin(ctx):
    return time.time()


def read(ctx, since):
    return read_window(ctx, since, "rbac")
