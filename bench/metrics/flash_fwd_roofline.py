"""flash_fwd_roofline: the flash forward kernel's share of its roofline, in
%: the least time the chip needs for the window's attention forwards (the
larger of needed FLOPs over peak FLOP/s and needed bytes over peak
bytes/s, both from the shapes and the mask) over the summed device time of
the kernel's events. Each layer's forward is needed once per step."""
import flops

KERNEL = r"flash_fwd\b"


def read(ctx):
    s, n = ctx.trace_summary, ctx.counts
    if s is None or not n.get("steps"):
        return None
    secs, events = s.kernel_s(KERNEL)
    if not events:
        return None
    c, L = ctx.config, flops.dims(ctx.config)["L"]
    work = n["steps"] * L * flops.attn_fwd_flops(c, n["attn_pairs_per_step"])
    nbytes = n["steps"] * L * flops.attn_fwd_bytes(c, n["tokens_per_step"])
    share, bound = flops.roofline(work, nbytes, secs, ctx.peaks)
    return {"value": share, "bound": bound, "events": events}
