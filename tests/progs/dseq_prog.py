"""Multi-device DSeq algebra checks (run in a subprocess: needs 8 fake devices)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.core.compat import make_mesh
from repro.core import DSeq, spmd, make_grid_mesh
from repro.core.dseq import scan_d

mesh = make_grid_mesh((8,), ("x",))
x = jnp.arange(8.0 * 4).reshape(8, 4)


def body(xl):
    s = DSeq(xl[0], "x")
    return (s.reduceD("sum"), s.reduceD(lambda a, b: a + b),
            s.reduceD(jnp.minimum), s.shiftD(3).local[None],
            s.allGatherD(), s.apply(5), s.scanD().local[None])


f = spmd(body, mesh, in_specs=P("x", None),
         out_specs=(P(None), P(None), P(None), P("x", None), P(None, None),
                    P(None), P("x", None)))
rs, rt, rm, sh, g, bc, sc = f(x)
np.testing.assert_allclose(rs, x.sum(0))
np.testing.assert_allclose(rt, x.sum(0))
np.testing.assert_allclose(rm, x.min(0))
np.testing.assert_allclose(np.asarray(sh), np.roll(np.asarray(x), 3, axis=0))
np.testing.assert_allclose(g, x)
np.testing.assert_allclose(bc, x[5])
np.testing.assert_allclose(np.asarray(sc), np.concatenate(
    [np.zeros((1, 4)), np.cumsum(np.asarray(x), 0)[:-1]]))

# reduceD to a specific root: non-root entries are zero
def body2(xl):
    return DSeq(xl[0], "x").reduceD(lambda a, b: a + b, root=3)[None]

r = spmd(body2, mesh, in_specs=P("x", None), out_specs=P("x", None))(x)
np.testing.assert_allclose(np.asarray(r)[3], x.sum(0))
assert np.all(np.asarray(r)[[0, 1, 2, 4, 5, 6, 7]] == 0)

# allToAllD == transpose of the process-data mapping
def body3(xl):
    return DSeq(xl.reshape(8, 1), "x").allToAllD().local.reshape(1, 8)

y = spmd(body3, mesh, in_specs=P("x", None), out_specs=P("x", None))(
    jnp.arange(64.0).reshape(8, 8))
np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.arange(64.0).reshape(8, 8)).T)

# non-power-of-two group (tree reduce remainder handling)
mesh6 = make_mesh((6,), ("x",), devices=jax.devices()[:6])
x6 = jnp.arange(6.0 * 3).reshape(6, 3)
r6 = spmd(lambda xl: DSeq(xl[0], "x").reduceD(lambda a, b: a + b), mesh6,
          in_specs=P("x", None), out_specs=P(None))(x6)
np.testing.assert_allclose(r6, x6.sum(0), rtol=1e-6)

print("DSEQ_OK")
