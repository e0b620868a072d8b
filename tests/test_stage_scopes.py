"""The HDOT stage scopes (``hdot.*`` named scopes in core/halo.py,
core/stencil.py and core/reduction.py): every stage a solver runs is named
in its compiled program's ``op_name`` metadata, in both schedules, on one
device and on a 4-device mesh (HPCCG's z slabs too, which run its chain
with one axis); and the solver entry points record a host span.

The 4-device cases compile in one child process on forced host devices
(``python tests/test_stage_scopes.py`` prints their stage sets)."""
from __future__ import annotations

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import halo, stencil
from repro.launch.mesh import GRID_AXES, GRID_AXES_3D, make_grid_mesh, make_mesh

STAGES = (halo.FACES, halo.INTERIOR, halo.ASSEMBLE, halo.EXCHANGE,
          halo.REDUCE, halo.UPDATE)
HDOT = {halo.FACES, halo.INTERIOR, halo.ASSEMBLE, halo.EXCHANGE, halo.REDUCE}
TWO_PHASE = {halo.INTERIOR, halo.EXCHANGE, halo.REDUCE}
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# (app, mesh shape, mode) -> the stages its compiled solve names. On one
# device Heat2D's hdot exchange carries zero halos over size-1 axes and the
# compiler folds it away; HPCCG's chain still pads p there, unless it has
# one axis (a z slab), where it pads nothing and only exchanges z planes.
# Heat2D's hdot tasks write their cells in place under their own scopes, so
# nothing of its solve is named `assemble`.
CASES = {
    ("heat2d", (1, 1), "hdot"): HDOT - {halo.EXCHANGE, halo.ASSEMBLE},
    ("heat2d", (1, 1), "two_phase"): TWO_PHASE,
    ("heat2d", (2, 2), "hdot"): HDOT - {halo.ASSEMBLE},
    ("heat2d", (2, 2), "two_phase"): TWO_PHASE,
    ("hpccg", (1, 1, 1), "hdot"): HDOT | {halo.UPDATE},
    ("hpccg", (1, 1, 1), "two_phase"): TWO_PHASE | {halo.UPDATE},
    ("hpccg", (1, 2, 2), "hdot"): HDOT | {halo.UPDATE},
    ("hpccg", (1, 2, 2), "two_phase"): TWO_PHASE | {halo.UPDATE},
    ("hpccg", (1,), "hdot"): HDOT - {halo.EXCHANGE} | {halo.UPDATE},
    ("hpccg", (4,), "hdot"): HDOT | {halo.UPDATE},
    # CREAMS's flux tasks, CFL max and stage updates take the same six names
    ("creams", (1, 1), "hdot"): HDOT | {halo.UPDATE},
    ("creams", (1, 1), "two_phase"): TWO_PHASE | {halo.UPDATE},
}
MULTI = [c for c in CASES if np.prod(c[1]) > 1]


def _case_id(case):
    app, shape, mode = case
    return f"{app}-{'x'.join(map(str, shape))}-{mode}"


def innermost_stages(hlo_text: str) -> set:
    """The innermost ``hdot.*`` scope of each op name in `hlo_text`."""
    found = set()
    for m in _OP_NAME.finditer(hlo_text):
        scopes = [c for c in m.group(1).split("/") if c.startswith("hdot.")]
        if scopes:
            found.add(scopes[-1])
    return found


def compiled_stages(app: str, shape, mode: str) -> set:
    """Stages named in the compiled solve of `app` on a mesh of `shape`, at
    a tiny size (each chip holds 32^2 cells, 8^3, or 5 fields of 8x16x16).
    A one-axis `shape` is HPCCG's z slabs."""
    axes = {1: ("z",), 2: GRID_AXES, 3: GRID_AXES_3D}[len(shape)]
    mesh = make_grid_mesh(*shape, axes=axes,
                          devices=jax.devices()[:int(np.prod(shape))])
    if app == "heat2d":
        fn = stencil._heat2d_solver(mesh, axes, 4, mode, 4, None)
        local = (32, 32)
    elif app == "creams":
        fn = stencil._rk3_solver(mesh, axes, 2, (1.0,) * 3, mode)
        local = (5, 8, 16, 16)
    else:
        fn = stencil._hpccg_solver(mesh, axes, 3, mode, 4)
        local = (8, 8, 8)
    # the mesh splits the trailing dims (a leading field axis stays whole)
    split = (1,) * (len(local) - len(shape)) + tuple(shape)
    spec = P(*((None,) * (len(local) - len(shape)) + axes))
    arg = jax.ShapeDtypeStruct(tuple(n * m for n, m in zip(local, split)),
                               jnp.float32, sharding=NamedSharding(mesh, spec))
    return innermost_stages(fn.lower(arg).compile().as_text())


@pytest.mark.parametrize("case", list(CASES), ids=_case_id)
def test_compiled_solve_names_its_stages(case, request):
    app, shape, mode = case
    if case in MULTI:
        found = set(request.getfixturevalue("child_results")[_case_id(case)])
    else:
        found = compiled_stages(app, shape, mode)
    assert found == CASES[case]
    assert found <= set(STAGES)


def _host_spans(log_dir: str) -> list:
    from jax.profiler import ProfileData

    [path] = list(Path(log_dir).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    return [e.name for p in data.planes if p.name == "/host:CPU"
            for ln in p.lines for e in ln.events]


@pytest.mark.parametrize("app", ["heat2d", "hpccg", "creams"])
def test_solver_entry_records_host_span(app, tmp_path):
    if app == "heat2d":
        mesh = make_grid_mesh(1, 1, devices=jax.devices()[:1])
        run = lambda: stencil.heat2d_solve(jnp.ones((16, 16)), mesh, GRID_AXES, 2)
    elif app == "creams":
        mesh = make_grid_mesh(1, 1, devices=jax.devices()[:1])
        run = lambda: stencil.rk3_solve(stencil.euler_tgv_init((8, 16, 16)),
                                        mesh, GRID_AXES, 2)
    else:
        mesh = make_grid_mesh(1, 1, 1, devices=jax.devices()[:1])
        run = lambda: stencil.hpccg_solve(jnp.ones((8, 8, 8)), mesh,
                                          GRID_AXES_3D, 2)
    jax.block_until_ready(run())                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(run())
    finally:
        jax.profiler.stop_trace()
    assert _host_spans(str(tmp_path)).count(stencil.SOLVE_SPAN) == 1


if __name__ == "__main__":
    print(json.dumps({_case_id(c): sorted(compiled_stages(*c)) for c in MULTI}))
