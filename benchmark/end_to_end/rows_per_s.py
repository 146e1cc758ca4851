"""Base-table rows read by the completed statements (the connector's row_count of each
statement's tables) over the window's seconds, per chip."""


def read(ctx):
    rows = sum(ctx.base_rows(r["name"]) for r in ctx.completed())
    return rows / ctx.window_s / ctx.cell.chips
