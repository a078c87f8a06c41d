"""Mean device time of one served step under its `match` section (every
rule's predicate over the batch: the id and byte compares, the
conjunction and rule gathers; compiler/ruleset.py `run`): the trace's
`XLA Ops` events whose scope path lies under `jax.named_scope("match")`,
over the `jit_step` programs (scopes.scope_ms_per_step). Every step has
the section."""
import time

from scopes import read_window


def begin(ctx):
    return time.time()


def read(ctx, since):
    return read_window(ctx, since, "match")
