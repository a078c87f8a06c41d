"""Mean device time of one served step under its `lists` section (the
list-entry gathers, the [B, L, E] membership compare, the verdict
merge): the trace's `XLA Ops` events whose scope path lies under
`jax.named_scope("lists")`, over the `jit_step` programs
(scopes.scope_ms_per_step)."""
import time

from scopes import read_window


def begin(ctx):
    return time.time()


def read(ctx, since):
    return read_window(ctx, since, "lists")
