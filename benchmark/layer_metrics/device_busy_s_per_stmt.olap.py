"""Seconds in which an operation ran on the device (union of the device plane's
operation intervals in the traced window) per statement completed in it."""


def read(ctx):
    done = len(ctx.completed())
    if ctx.trace is None or not done or ctx.device["platform"] != "tpu":
        return None
    return ctx.trace["busy_s"] / done
