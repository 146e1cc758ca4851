"""How much busier the busiest chip is than the mean chip in the traced window, in
percent: the largest of ``busy_s_by_device`` over their mean, less 1 (0 is even; an
un-sharded build or a coordinator-side tail inside the window shows here as device 0's
excess).  None without a device trace of more than one chip (the CPU rehearsal; a
harness whose trace lists no chip)."""


def read(ctx):
    by_device = (ctx.trace or {}).get("busy_s_by_device")
    if ctx.device["platform"] != "tpu" or not by_device or len(by_device) < 2:
        return None
    mean = sum(by_device) / len(by_device)
    if not mean:
        return None
    return (max(by_device) / mean - 1.0) * 100.0
