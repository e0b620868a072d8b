"""jit'd wrapper for the blocked red-black Gauss-Seidel sweep."""
from __future__ import annotations


import jax
import jax.numpy as jnp


from repro.kernels.heat2d import ref as _ref


def heat2d_sweep(u: jax.Array, tile=(256, 256), sweeps: int = 1,
                 impl: str = "auto", interpret: bool | None = None,
                 halo=None) -> jax.Array:
    """Red-black GS sweep over a local block. Tiles update block-Jacobi style
    (halo from the previous sweep). `halo=(north, south, west, east)` supplies
    the block's outer ghost ring — shapes (1, ny)/(1, ny)/(nx, 1)/(nx, 1) —
    for use as one subdomain of a 2-D process mesh; None means the global
    Dirichlet-0 boundary."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return _ref_blocked(u, tile, sweeps, halo)
    if impl == "pallas":
        from repro.kernels.heat2d import heat2d as _k

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _k.heat2d_sweep_pallas(u, tile, sweeps, interpret=interpret,
                                      halo=halo)
    raise ValueError(f"unknown impl {impl!r}")


def heat2d_sweep_sharded(u: jax.Array, mesh, axis_names=("rows", "cols"),
                         tile=(256, 256), sweeps: int = 1, impl: str = "auto",
                         interpret: bool | None = None) -> jax.Array:
    """The tile kernel as one level of a 2-D hierarchy: the GLOBAL grid is
    block-decomposed over a (rows x cols) process mesh, each shard exchanges
    both axes' width-1 edge strips (corner-free ppermutes — the 5-point star
    never reads corners), and the kernel stages those strips as its halo ring
    exactly like it stages neighbor-tile strips. Tiles stay the task-level
    subdomains; shards are the process-level ones — the same partition
    scheme, two levels (paper §3.2)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.halo import exchange_halo_nd

    ar, ac = axis_names

    def local(ul):
        (north, south), (west, east) = exchange_halo_nd(
            ul, ((ar, 0), (ac, 1)), width=1, periodic=False)
        return heat2d_sweep(ul, tile, sweeps, impl, interpret,
                            halo=(north, south, west, east))

    # replication check off: jax has no replication rule for pallas_call yet
    f = jax.shard_map(local, mesh=mesh, in_specs=P(ar, ac),
                      out_specs=P(ar, ac), check_vma=False)
    return jax.jit(f)(u)


def _ref_blocked(u: jax.Array, tile, sweeps: int, halo=None) -> jax.Array:
    """Oracle with identical block semantics to the kernel: per-tile red-black
    GS with halos frozen at sweep start (block-Jacobi across tiles). The
    outer ghost ring is zeros (Dirichlet) or the supplied `halo` strips;
    corner ghosts stay zero — the 5-point star never reads them."""
    nx, ny = u.shape
    tx, ty = min(tile[0], nx), min(tile[1], ny)
    gx, gy = nx // tx, ny // ty
    up = jnp.pad(u, 1)
    if halo is not None:
        north, south, west, east = halo
        up = up.at[0, 1:-1].set(north[0])
        up = up.at[-1, 1:-1].set(south[0])
        up = up.at[1:-1, 0].set(west[:, 0])
        up = up.at[1:-1, -1].set(east[:, 0])
    out = jnp.zeros_like(u)
    for i in range(gx):
        for j in range(gy):
            blk = jax.lax.dynamic_slice(up, (i * tx, j * ty), (tx + 2, ty + 2))
            out = jax.lax.dynamic_update_slice(
                out, _ref.heat2d_sweep_ref(blk, sweeps), (i * tx, j * ty))
    return out
