#!/usr/bin/env python
"""Measure gradient-aggregation (all-reduce) bandwidth over the device mesh.

Reference: ``tools/bandwidth/measure.py`` — pushes a model's gradient-sized
arrays through the kvstore and reports per-GPU bandwidth, with an ``error``
column validating the reduction numerically (README: 11.1 GB/s for 2-GPU
device kvstore on resnet-200's 258 MB of grads).

TPU-native version: the reduction is one XLA ``psum`` over the mesh's ICI
links inside a compiled program (what kvstore='device' lowers to here).
Bandwidth uses the standard all-reduce model 2(n-1)/n · bytes / time per
device.  On CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
to exercise the code path on a virtual mesh.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import numpy as np

curr_path = os.path.abspath(os.path.dirname(__file__))
sys.path.insert(0, os.path.join(curr_path, "..", ".."))
sys.path.insert(0, os.path.join(curr_path, "..", "..", "examples",
                                "image-classification"))

import mxnet_tpu  # noqa: E402,F401  (applies the JAX_PLATFORMS env var)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402


def parse_args():
    parser = argparse.ArgumentParser(
        description="benchmark mesh all-reduce (kvstore='device' path)")
    parser.add_argument("--network", type=str, default="resnet",
                        help="model whose gradient sizes to use")
    parser.add_argument("--num-layers", type=int, default=50)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--num-batches", type=int, default=5)
    parser.add_argument("--disp-batches", type=int, default=1)
    parser.add_argument("--test-results", type=int, default=1)
    parser.add_argument("--sizes", type=str, default=None,
                        help="comma-separated float32 counts to reduce "
                             "instead of a model's gradient sizes")
    args = parser.parse_args()
    logging.info(args)
    return args


def grad_sizes(args):
    """Gradient array sizes of the chosen model (via symbol shape
    inference, like the reference binds the real network)."""
    import mxnet_tpu as mx
    from common.modelzoo import get_network
    net = get_network(args.network, num_classes=args.num_classes,
                      num_layers=args.num_layers)
    shape = tuple(int(x) for x in args.image_shape.split(","))
    arg_shapes, _, _ = net.infer_shape(data=(1,) + shape,
                                       softmax_label=(1,))
    sizes = [int(np.prod(s)) for n, s in zip(net.list_arguments(),
                                             arg_shapes)
             if n not in ("data", "softmax_label")]
    return sizes


def make_bench(sizes, test_results=True):
    """Build the jitted all-reduce + buffers ONCE; returns a closure that
    times num_batches chained reductions (reference warms up once, then
    times batches)."""
    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    total = sum(sizes)

    @jax.jit
    def allreduce(x):
        return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P("dp"))(x)

    # one flat buffer per device-shard (n, total): row i = device i's grads
    rs = np.random.RandomState(0)
    host = rs.uniform(-1, 1, (n, total)).astype(np.float32)
    x = jax.device_put(jnp.asarray(host), NamedSharding(mesh, P("dp")))

    # warmup/compile: the chained form (mul/add + collective) AND the
    # fetch-slice program, so the first timed window compiles nothing
    out = allreduce(x * 0 + x)
    np.asarray(out[:1, :1])
    err = 0.0
    if test_results:
        expect = host.sum(axis=0)
        got = np.asarray(out)[0]
        err = float(np.abs(got - expect).max() /
                    max(1e-12, np.abs(expect).max()))

    nbytes = total * 4

    def run(num_batches):
        tic = time.perf_counter()
        o = x
        for _ in range(num_batches):
            o = allreduce(o * 0 + x)  # chained: forces sequential exec
        # fetch-forced sync: block_until_ready over a remote PJRT
        # device can return at enqueue-ack (docs/perf.md)
        np.asarray(o[:1, :1])
        elapsed = (time.perf_counter() - tic) / num_batches
        algo_bw = 2 * (n - 1) / max(n, 1) * nbytes / elapsed / 1e9 \
            if n > 1 else nbytes / elapsed / 1e9
        return elapsed, algo_bw, err

    return run


def main():
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    args = parse_args()
    if args.sizes:
        sizes = [int(s) for s in args.sizes.split(",")]
    else:
        sizes = grad_sizes(args)
    total_mb = sum(sizes) * 4 / 1e6
    logging.info("devices: %d, total gradient bytes: %.1f MB",
                 len(jax.devices()), total_mb)
    logging.info("%10s %12s %14s %10s", "iter", "time(ms)",
                 "algo BW (GB/s)", "error")
    run = make_bench(sizes, args.test_results)
    for i in range(args.num_batches // args.disp_batches or 1):
        t, bw, err = run(args.disp_batches)
        logging.info("%10d %12.3f %14.3f %10.2e", i, t * 1e3, bw, err)
        if args.test_results:
            assert err < 1e-4, "all-reduce produced wrong values"


if __name__ == "__main__":
    main()
