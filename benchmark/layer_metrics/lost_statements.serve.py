"""Statements whose poll was answered 404 (the server evicted them before the client
read the answer) and that the client sent again.  Expected 0 once the server stamps a
statement's finish time before it publishes the FINISHED state."""


def read(ctx):
    return sum(r["lost"] for r in ctx.records)
