"""train_recompute_share: device time of the ops in the step's
``recompute`` phase over the traced window's busy time, in % (the phase
rule is ``scopes.phase``). Also given: the seconds of each phase, of the
``head`` and ``attn`` tags within each phase, and of the ops no rule
placed. A program whose ops carry no ``optimizer`` scope names no phases,
and the metric is left out."""
import scopes


def read(ctx):
    ops = scopes.window_ops(ctx)
    if ops is None:
        return None
    s = ctx.trace_summary
    split = scopes.phase_split(ops, s.lo, s.hi)
    if not split["seconds"]["optimizer"]:
        return None
    busy = s.busy_s * len(s.events["devices"])
    return {"value": 100.0 * split["seconds"]["recompute"] / busy,
            "busy_s": busy, **split}
