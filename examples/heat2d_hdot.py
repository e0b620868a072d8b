"""Paper §4.1 walkthrough: Heat2D with hierarchical over-decomposition.

Shows the solver converging, the two schedules agreeing bit-for-bit, and the
Pallas tile kernel (compiled on a TPU, interpreted elsewhere) matching the
jnp oracle — the three layers of the HDOT stack: mesh shards -> subdomain
schedule -> VMEM tile.

Run:  PYTHONPATH=src python examples/heat2d_hdot.py
"""
import jax
import numpy as np

from repro.core.domain import halo_fraction
from repro.core.stencil import heat2d_init, heat2d_solve
from repro.kernels.heat2d import ops as heat_ops
from repro.launch.mesh import make_mesh


def ascii_field(u: np.ndarray, width: int = 48) -> str:
    chars = " .:-=+*#%@"
    step = max(1, u.shape[0] // 16), max(1, u.shape[1] // width)
    rows = []
    lo, hi = float(u.min()), float(u.max()) + 1e-9
    for i in range(0, u.shape[0], step[0]):
        row = ""
        for j in range(0, u.shape[1], step[1]):
            v = (float(u[i, j]) - lo) / (hi - lo)
            row += chars[min(int(v * len(chars)), len(chars) - 1)]
        rows.append(row)
    return "\n".join(rows)


def main() -> None:
    # paper Table 1: the memory cost of NOT sharing memory
    print("paper Table 1 — halo share of allocated memory (128x128, 1-D):")
    for ranks in (2, 4, 8, 16, 32):
        _, _, frac = halo_fraction((128, 128), (ranks, 1))
        print(f"  {ranks:3d} ranks: {100*frac:5.1f}%")

    mesh = make_mesh((jax.device_count(),), ("data",))
    u0 = heat2d_init(128, 128)
    print("\ninitial field:")
    print(ascii_field(np.asarray(u0)))

    for iters in (25, 100):
        u_hd, res = heat2d_solve(u0, mesh, ("data",), iters, mode="hdot")
        print(f"\nafter {iters} HDOT sweeps (residual {float(res[-1]):.3e}):")
        print(ascii_field(np.asarray(u_hd)))

    u_tp, _ = heat2d_solve(u0, mesh, ("data",), 100, mode="two_phase")
    print(f"\ntwo_phase == hdot: "
          f"{np.allclose(np.asarray(u_tp), np.asarray(u_hd), atol=1e-6)}")

    # kernel layer: blocked red-black GS tile (compiled on a TPU backend,
    # interpret mode elsewhere)
    u = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    got = heat_ops.heat2d_sweep(u, tile=(128, 128), impl="pallas",
                                interpret=None)
    want = heat_ops.heat2d_sweep(u, tile=(128, 128), impl="ref")
    print(f"pallas tile kernel == jnp oracle: "
          f"{np.allclose(np.asarray(got), np.asarray(want), atol=1e-6)}")


if __name__ == "__main__":
    main()
