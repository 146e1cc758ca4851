"""Programs XLA really compiled inside the window (``compile_cache_misses`` delta):
``window_compiles.olap`` counts requests, which a compilation cache may serve.
Expected 0."""


def read(ctx):
    return ctx.counters.get("compile_cache_misses")
