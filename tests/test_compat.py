"""repro.parallel.compat must import and actually shard a computation:
it holds the repo's one `jax.shard_map` call."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.compat import shard_map


def test_shard_map_shim_runs():
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = shard_map(lambda a: a * 2.0, mesh=mesh,
                  in_specs=P("x"), out_specs=P("x"))
    out = f(jnp.arange(4, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(4) * 2.0)


def test_shim_is_the_only_shard_map_entry():
    # the module exports exactly shard_map; call sites import this,
    # never jax.experimental directly (enforced by reprolint compat-shim)
    import repro.parallel.compat as compat
    assert compat.__all__ == ["shard_map"]
