"""Seconds of a statement's root span that are neither dispatch nor pull: split
generation, host-to-device staging and the unattributed remainder (window delta of
``wall_split_generation_s`` + ``wall_h2d_s`` + ``wall_unattributed_s`` over statements
completed)."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_unattributed_s" not in ctx.counters or not done:
        return None
    c = ctx.counters
    return (c["wall_split_generation_s"] + c["wall_h2d_s"] + c["wall_unattributed_s"]) / done
