"""`ops/solve.py::solve_spd`: the one place that decides how an ALS
half-iteration's SPD batch is solved. Each branch of its policy against
`numpy.linalg.solve`, and which of them hold a Pallas kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.solve import solve_spd
from predictionio_tpu.parallel.mesh import DATA_AXIS, make_mesh
from tests.test_pallas_solve import _pallas_calls, _spd_batch


# mesh, row_sharded
DISPATCH = {
    "no_mesh": (False, True),
    "mesh_rows": (True, True),
    # the [U] split accumulators: replicated, any size -> Cholesky
    "mesh_replicated": (True, False),
}


@pytest.mark.parametrize("kernel", [False, True], ids=["chol", "gj"])
@pytest.mark.parametrize("dispatch", list(DISPATCH))
def test_solve_spd_matches_numpy(kernel, dispatch):
    meshed, row_sharded = DISPATCH[dispatch]
    mesh = make_mesh({DATA_AXIS: 8}) if meshed else None
    # 40 = 8 devices x 5 rows; 13 rows divide by nothing
    r, k = (40, 16) if row_sharded else (13, 16)
    a, b = _spd_batch(np.random.default_rng(r), r, k)

    def solve(a_, b_):
        return solve_spd(a_, b_, kernel=kernel, interpret=True, mesh=mesh,
                         row_sharded=row_sharded)

    x = np.asarray(jax.jit(solve)(jnp.asarray(a), jnp.asarray(b)))
    ref = np.linalg.solve(a, b[..., None])[..., 0]
    assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-4
    calls = _pallas_calls(jax.make_jaxpr(solve)(a, b).jaxpr)
    # one kernel where it is asked for and the rows are a device's own
    # (under the mesh it is inside the `shard_map`, 5 rows a device)
    assert len(calls) == (1 if kernel and row_sharded else 0)
