"""bench.py helper tests: the honest-timing primitive every timed
window starts and stops on."""
import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fetch_sync_forces_on_ndarray_and_trees():
    """_fetch_sync is the honest-timing primitive (every timed window
    starts and stops on it): it must unwrap NDArray handles and pytree
    containers down to a fetchable leaf without error."""
    import numpy as _np
    import jax.numpy as _jnp
    import mxnet_tpu as _mx
    b = _load_bench()
    b._fetch_sync(_jnp.ones((3,)))
    b._fetch_sync([_jnp.zeros((2, 2)), _jnp.ones(())])
    b._fetch_sync(_mx.nd.array(_np.eye(2)))
    b._fetch_sync((_mx.nd.ones((1,)),))
