"""Worker for the `tools/launch.py --mesh N` end-to-end smoke.

Launched with the ``MXNET_MESH_{COORDINATOR,NUM_PROCESSES,PROCESS_ID}``
triple (and NO ``DMLC_*`` vars — launch.py scrubs them); boots the
global mesh via ``distributed_init_from_env()`` and runs the SAME
``Module.fit`` script shape the PS modes run, with the backend picked
by the kvstore string alone: ``kvstore='dist_mesh'`` routes down the
one-SPMD-step fast path with the bucketed in-graph reduction.

Prints ``DIST_MESH_OK rank=<r>`` on success.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 4 local devices per process BEFORE jax configures the backend
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import mesh as mesh_mod

    assert not any(k.startswith("DMLC_") for k in os.environ), \
        "launch.py --mesh must scrub PS role vars"
    assert mesh_mod.distributed_init_from_env(), \
        "MXNET_MESH_COORDINATOR not set — run via tools/launch.py --mesh"
    n = jax.process_count()
    rank = jax.process_index()
    assert len(jax.devices()) == 4 * n, jax.devices()

    X = np.random.RandomState(0).randn(64, 12).astype("float32")
    y = (X.sum(axis=1) > 0).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc"), name="softmax")
    mod = mx.Module(net, context=[mx.cpu(i) for i in range(4)])
    mod.fit(it, num_epoch=2, kvstore="dist_mesh", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    args, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in args.values())
    print("DIST_MESH_OK rank=%d" % rank, flush=True)


if __name__ == "__main__":
    main()
