"""Mean rows in the batches the native front formed in the window."""


def begin(ctx):
    return ctx.native.counters()


def read(ctx, base):
    now = ctx.native.counters()
    batches = now["batches_formed"] - base["batches_formed"]
    if not batches:
        return None
    return (now["batch_rows"] - base["batch_rows"]) / batches
