"""Seconds of a statement's root span that no span names: the ``unattributed`` bucket of
its wall breakdown (``wall_unattributed_s``, window delta over statements completed).  What
is left after dispatch, host pull, scan wait, split generation, staging, plan, compile and
exchange wait; ``unattributed_by`` of the statement's trace says under which container span
it sits.  Host clock, never a device number; None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_unattributed_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_unattributed_s"] / done
