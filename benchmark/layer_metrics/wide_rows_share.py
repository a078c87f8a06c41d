"""Share of the served Check rows that rode the wide byte plane: the
program's `mixer_check_rows_by_width_total{width}` (rows served at each
byte-plane width, counted where a batch's programs are launched), the
rows above the plan's widest narrow tier over all rows, both since the
window opened. A traffic check, not a cost: it says the cell still
sends the subject lengths the length split was measured under (about
53 % past the 128-byte cap in `routelong10k`). A program without the
counter reads nothing."""
from istio_tpu.runtime import monitor


def rows_by_width():
    counters = getattr(monitor, "length_split_counters", None)
    return counters()["rows_by_width"] if counters else None


def rows_since(base: dict) -> dict:
    """{width: rows} served since `base`."""
    return {int(width): rows - base.get(width, 0)
            for width, rows in rows_by_width().items()}


def begin(ctx):
    return rows_by_width()


def read(ctx, base):
    if base is None:
        return None
    rows = rows_since(base)
    total = sum(rows.values())
    if not total:
        return None
    narrow = max(ctx.srv.controller.dispatcher.fused.str_tiers)
    return 100.0 * sum(n for width, n in rows.items()
                       if width > narrow) / total
