"""Rows inserted into join build tables per completed statement (``join_build_rows``
window delta): the engine builds a join's table when it compiles the join's stream, so
0 in a window means that every statement probed tables left from set-up, as the cell's
``why`` says.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "join_build_rows" not in ctx.counters or not done:
        return None
    return ctx.counters["join_build_rows"] / done
