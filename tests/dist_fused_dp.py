"""Worker for the multi-process fused-DP parity test.

Role: SURVEY §5 "dist_* over DCN ≡ multi-slice all-reduce" — the fused
``DataParallelTrainer`` step composed across OS processes through
``jax.distributed`` (the CPU stand-in for a multi-host TPU slice; on
real hardware the same program rides ICI/DCN collectives).  Each
process owns 4 virtual CPU devices; the global mesh spans all 8 across
both processes, so the in-graph gradient mean is a genuinely
cross-process all-reduce.  The resulting weights must match the
closed-form SGD recursion — computed independently in every process —
like ``dist_sync_kvstore.py`` asserts the PS protocol's closed form.

Usage: dist_fused_dp.py <process_id> <num_processes> <coord_port>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 4 local devices per process BEFORE jax configures the backend
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

import numpy as np


def main():
    pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    jax.distributed.initialize("127.0.0.1:%s" % port, num_processes=n,
                               process_id=pid)
    assert len(jax.local_devices()) == 4
    assert len(jax.devices()) == 4 * n, jax.devices()

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer

    BATCH, FEAT, LR, STEPS = 16, 3, 0.05, 5
    data = mx.sym.Variable("data")
    net = mx.sym.LinearRegressionOutput(
        mx.sym.FullyConnected(data, num_hidden=1, no_bias=True,
                              name="fc"), name="lro")
    trainer = DataParallelTrainer(
        net, data_shapes={"data": (BATCH, FEAT)},
        label_shapes={"lro_label": (BATCH, 1)},
        optimizer="sgd",
        optimizer_params={"learning_rate": LR, "momentum": 0.0,
                          "wd": 0.0},
        initializer=mx.initializer.Zero())
    # the global mesh must span both processes, or the "distributed"
    # trainer silently degrades to per-process training
    assert trainer.mesh.devices.size == 4 * n, trainer.mesh

    # identical full global batch in every process; device_put lays it
    # out over the cross-process dp sharding
    rs = np.random.RandomState(3)
    X = rs.randn(BATCH, FEAT).astype(np.float32)
    y = rs.randn(BATCH, 1).astype(np.float32)
    for _ in range(STEPS):
        trainer.step(X, y)

    # replicated params: every process can read its addressable copy
    w = np.asarray(trainer.params["fc_weight"]).reshape(-1)

    # closed-form SGD recursion (grad of LinearRegressionOutput is
    # pred - label; trainer defaults rescale_grad = 1/global_batch)
    wr = np.zeros((1, FEAT), np.float32)
    for _ in range(STEPS):
        gw = (X @ wr.T - y).T @ X
        wr = wr - LR * (gw / BATCH)
    np.testing.assert_allclose(w, wr.ravel(), rtol=1e-4)

    # ZeRO-1 across processes: momentum state sharded over the SAME
    # cross-process mesh must stay numerically identical to the
    # replicated path (here: the closed-form recursion with momentum)
    mom = 0.9
    tz = DataParallelTrainer(
        net, data_shapes={"data": (BATCH, FEAT)},
        label_shapes={"lro_label": (BATCH, 1)},
        optimizer="sgd",
        optimizer_params={"learning_rate": LR, "momentum": mom,
                          "wd": 0.0},
        initializer=mx.initializer.Zero(),
        shard_optimizer_state=True)
    for _ in range(STEPS):
        tz.step(X, y)
    wz = np.asarray(tz.params["fc_weight"]).reshape(-1)
    wm = np.zeros((1, FEAT), np.float32)
    vm = np.zeros((1, FEAT), np.float32)
    for _ in range(STEPS):
        g = ((X @ wm.T - y).T @ X) / BATCH
        vm = mom * vm - LR * g
        wm = wm + vm
    np.testing.assert_allclose(wz, wm.ravel(), rtol=1e-4)

    print("DIST_FUSED_DP_OK rank=%d w=%s" % (pid, w.tolist()),
          flush=True)


if __name__ == "__main__":
    main()
