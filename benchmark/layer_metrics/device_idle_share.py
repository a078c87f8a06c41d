"""Share of the traced span in which no program ran on the device."""
from observe import idle_share_pct


def read(ctx, _):
    if ctx.trace is None:
        return None
    return idle_share_pct(ctx.trace["busy_s"], ctx.trace["window_s"])
