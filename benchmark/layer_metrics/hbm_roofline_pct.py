"""The least time the chip's memory could take for the traced requests
(their needed_bytes over the peak HBM bytes/s of peaks.json) as a share
of the device's busy seconds in the slice.  Full precision: the share is
tiny today and is never rounded to 0."""


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not peaks or t["busy_s"] <= 0:
        return None
    needed = sum(ctx["needed_bytes"](r["req"]) for r in ctx["log"]
                 if r["traced"])
    if not needed:
        return None
    return 100.0 * needed / peaks["hbm_bytes_per_s"] / t["busy_s"]
