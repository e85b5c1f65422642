"""train_device_idle: share of the traced training window in which no
operation runs on the device (averaged over the chips), in %."""


def read(ctx):
    s = ctx.trace_summary
    if s is None or not s.events["devices"]:
        return None
    return {"value": 100.0 * s.idle_share()}
