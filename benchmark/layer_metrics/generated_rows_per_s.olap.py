"""Base-table rows the connector generated for the window's statements
(``rows_generated`` window delta) over the window's seconds: what the generator and the
split loop sustain under joins, to be read beside ``sf10_scan``'s ``rows_per_s``.  Rows
served from a resident page are not generated and do not count.  None on a program
without the counter."""


def read(ctx):
    if "rows_generated" not in ctx.counters or not ctx.window_s:
        return None
    return ctx.counters["rows_generated"] / ctx.window_s
