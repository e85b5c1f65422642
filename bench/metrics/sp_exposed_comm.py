"""sp_exposed_comm: on the worst device, the time in which a collective
(ring KV exchange, gradient reduction) is in flight and no compute op runs,
as a share of the traced window, in %."""


def read(ctx):
    s = ctx.trace_summary
    if s is None or len(s.events["devices"]) < 2:
        return None
    worst = max(s.exposed_collective_s(d) for d in s.events["devices"])
    return {"value": 100.0 * worst / s.window_s}
