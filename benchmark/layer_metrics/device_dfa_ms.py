"""Mean device time of one served step under its `dfa` section (the DFA
groups of `match`: the byte-class lookup, the scan over each row's
candidate automata, the acceptance gather and the spread back to the
atoms' columns): the trace's `XLA Ops` events whose scope path lies
under `jax.named_scope("dfa")`, over the `jit_step` programs
(scopes.scope_ms_per_step). A step without the scope reads nothing."""
import time

from scopes import read_window


def begin(ctx):
    return time.time()


def read(ctx, since):
    return read_window(ctx, since, "dfa")
