"""HDOT core — the paper's contribution as composable JAX modules.

- :mod:`repro.core.domain`            hierarchical domain over-decomposition
- :mod:`repro.core.halo`              halo exchange with interior/boundary overlap
- :mod:`repro.core.overlap`           two-phase vs HDOT communication schedules
- :mod:`repro.core.collective_matmul` ppermute-ring collective matmuls (TP chunk tasks)
- :mod:`repro.core.reduction`         hierarchical task->process reductions
- :mod:`repro.core.stencil`           paper applications (Heat2D / RK3 / HPCCG) on the core
"""

from repro.core.domain import (Box, Domain, SubDomain, decompose_grid,
                               halo_cells, interior_boxes)

__all__ = [
    "Box",
    "Domain",
    "SubDomain",
    "decompose_grid",
    "halo_cells",
    "interior_boxes",
]
