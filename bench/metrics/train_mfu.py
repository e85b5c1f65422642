"""train_mfu: model FLOPs of the window's training steps over the window's
wall time, the chips and the chip's bf16 peak, in %. Recomputed work is
not counted (``flops.train_step_flops``)."""
import flops


def read(ctx):
    n = ctx.counts
    if not n.get("steps"):
        return None
    work = n["steps"] * flops.train_step_flops(
        ctx.config, n["tokens_per_step"], n["attn_pairs_per_step"])
    peak = len(ctx.devices) * ctx.peaks["bf16_flops"]
    return {"value": 100.0 * work / (n["window_s"] * peak)}
