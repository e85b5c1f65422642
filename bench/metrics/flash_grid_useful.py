"""flash_grid_useful: of the grid steps the flash kernels launched in the
traced window, the share whose compute body runs, in % (a pruned call
skips the body where the static mask cannot reach the tile; tiles that
only segment IDs mask still run). Each kernel event's counts are its own
call's ``kernel_metadata`` (launched and executed grid steps, B·H × the
call's grid profile), so the share holds per scanned layer and per call
whose mask differs. Also given per kernel (``flash_fwd``,
``flash_bwd.dq``, ``flash_bwd.dkv``): events, seconds, launched and
executed steps, and ns per launched step."""
import scopes


def read(ctx):
    ops = scopes.window_ops(ctx)
    if ops is None:
        return None
    s = ctx.trace_summary
    per = scopes.grid_work(ops, s.lo, s.hi)
    launched = sum(r["launched"] for r in per.values())
    if not launched:
        return None
    executed = sum(r["executed"] for r in per.values())
    return {"value": 100.0 * executed / launched, "kernels": per}
