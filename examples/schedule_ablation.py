"""Ablation example (paper §4.5): the three schedules produce identical
losses while their communication profiles differ; prints the per-schedule
collective bytes of one attention layer from the compiled HLO.

    python examples/schedule_ablation.py           # sets its own XLA_FLAGS
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis.roofline import collective_stats  # noqa: E402
from repro.core.dist_attention import DistAttnSpec, dist_attn_fwd  # noqa
from repro.kernels.ref import full_attn_ref  # noqa: E402
from repro.core import mask as mask_lib  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh((1, 8), ("data", "model"))
    B, N, H, D = 1, 2048, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, N, H, D)) for kk in ks)
    o_ref = full_attn_ref(q, k, v, causal=True)
    print(f"{'schedule':>10} {'max err':>12} {'coll bytes/layer':>18} ops")
    for sched in ("ring", "balanced", "ulysses", "rsa"):
        spec = DistAttnSpec(axis="model", axis_size=8, schedule=sched,
                            mask=mask_lib.causal())
        f = jax.jit(lambda q, k, v: dist_attn_fwd(
            q, k, v, mesh=mesh, spec=spec, batch_axes=None)[0])
        txt = f.lower(q, k, v).compile().as_text()
        st = collective_stats(txt)
        err = float(jnp.abs(f(q, k, v) - o_ref).max())
        print(f"{sched:>10} {err:12.2e} {st.total_bytes:18,.0f} "
              f"{st.op_counts}")


if __name__ == "__main__":
    main()
