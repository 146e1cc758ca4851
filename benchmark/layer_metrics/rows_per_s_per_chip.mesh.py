"""Base-table rows read by the completed statements over the window's seconds, per chip
(``rows_per_s``'s formula, the north star's unit), read in the traced window: the
profiler slows the host, so it is a share-quality number beside the untraced
``stmt_s.geomean``, not an end-to-end one."""


def read(ctx):
    done = ctx.completed()
    if not done or not ctx.window_s:
        return None
    return sum(ctx.base_rows(r["name"]) for r in done) / ctx.window_s / ctx.cell.chips
