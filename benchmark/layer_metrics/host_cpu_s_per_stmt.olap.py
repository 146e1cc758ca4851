"""CPU seconds of a statement's own thread under its root span (``host_cpu_s``:
``time.thread_time`` where the span opens and closes; window delta over statements
completed).  Against the statement's seconds it says how much of them the thread computed
and how much it waited (for the device, a queue, the interpreter lock).  None on a program
without the counter (before PR 38)."""


def read(ctx):
    done = len(ctx.completed())
    if "host_cpu_s" not in ctx.counters or not done:
        return None
    return ctx.counters["host_cpu_s"] / done
