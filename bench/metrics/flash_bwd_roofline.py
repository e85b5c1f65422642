"""flash_bwd_roofline: the flash backward kernels' (dQ and dK/dV) share of
their roofline, in %: the least time the chip needs for the window's
attention backwards (``flops.attn_bwd_flops`` and ``attn_bwd_bytes``, from
the shapes and the mask) over the summed device time of both kernels'
events."""
import flops

KERNEL = r"flash_bwd\b"


def read(ctx):
    s, n = ctx.trace_summary, ctx.counts
    if s is None or not n.get("steps"):
        return None
    secs, events = s.kernel_s(KERNEL)
    if not events:
        return None
    c, L = ctx.config, flops.dims(ctx.config)["L"]
    work = n["steps"] * L * flops.attn_bwd_flops(c, n["attn_pairs_per_step"])
    nbytes = n["steps"] * L * flops.attn_bwd_bytes(c, n["tokens_per_step"])
    share, bound = flops.roofline(work, nbytes, secs, ctx.peaks)
    return {"value": share, "bound": bound, "events": events}
