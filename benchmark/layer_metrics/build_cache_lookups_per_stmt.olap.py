"""Join-build cache lookups (hits plus misses of ``buffer_pool.info()``) per completed
statement, window delta.  The engine executes a join's build side (or takes it from the
build cache) when it compiles the join's stream, and only then consults the cache; a
replayed text reuses its compiled stream with the build table inside.  So 0 means that
no statement of the window built anything: it probed tables left from set-up.  Above 0,
streams were compiled in the window (a new text, an evicted plan)."""


def read(ctx):
    done = len(ctx.completed())
    if not done:
        return None
    return (ctx.pool.get("build_hits", 0) + ctx.pool.get("build_misses", 0)) / done
