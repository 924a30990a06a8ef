"""Fast-gradient-sign adversarial examples (reference example/adversary/
adversary_generation.ipynb: train a small MNIST CNN, bind with
``grad_req='write'`` on the *data* input, perturb by
``eps * sign(dL/dx)`` and watch accuracy collapse).

Self-contained: synthetic "digits" are class-coded blob images that a
2-conv CNN learns to near-perfect accuracy; the FGSM attack then drives
accuracy far below clean accuracy at a perturbation invisible to the
class structure.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np

CURR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(CURR, "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def make_digits(rs, n, num_classes=10, side=16):
    """Blob images: class k lights a kth grid cell (plus noise)."""
    y = rs.randint(0, num_classes, n)
    X = rs.rand(n, 1, side, side).astype(np.float32) * 0.2
    cell = side // 4
    for i, k in enumerate(y):
        r, c = divmod(int(k), 4)
        X[i, 0, r * cell:(r + 1) * cell, c * cell:(c + 1) * cell] += 0.8
    return X, y.astype(np.float32)


def cnn(num_classes):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=16, pad=(1, 1))
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=32, pad=(1, 1))
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=num_classes)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def main():
    parser = argparse.ArgumentParser(description="FGSM adversary")
    parser.add_argument("--num-examples", type=int, default=2048)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--num-epochs", type=int, default=4)
    parser.add_argument("--eps", type=float, default=0.3)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    # the shuffle and the initializer draw from the global generators:
    # seeded, so that the accuracies printed are the same every run
    np.random.seed(3)
    mx.random.seed(3)
    rs = np.random.RandomState(3)
    X, y = make_digits(rs, args.num_examples)
    n_train = int(0.75 * args.num_examples)
    train = mx.io.NDArrayIter(X[:n_train], y[:n_train],
                              batch_size=args.batch_size, shuffle=True)
    net = cnn(10)
    mod = mx.Module(net, context=mx.current_context())
    mod.fit(train, num_epoch=args.num_epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier(), eval_metric="accuracy",
            kvstore="local")

    # attack executor: same weights, gradient flows into the data input
    Xv, yv = X[n_train:], y[n_train:]
    bs = len(Xv)
    ex = net.simple_bind(mx.current_context(), data=Xv.shape,
                         softmax_label=(bs,), grad_req="write")
    arg_params, aux_params = mod.get_params()
    for k, v in arg_params.items():
        ex.arg_dict[k][:] = v
    for k, v in aux_params.items():
        ex.aux_dict[k][:] = v
    ex.arg_dict["data"][:] = Xv
    ex.arg_dict["softmax_label"][:] = yv
    ex.forward(is_train=True)
    clean_pred = ex.outputs[0].asnumpy().argmax(axis=1)
    clean_acc = float((clean_pred == yv).mean())
    ex.backward()
    grad_sign = np.sign(ex.grad_dict["data"].asnumpy())

    # FGSM step and re-score
    ex.arg_dict["data"][:] = Xv + args.eps * grad_sign
    ex.forward(is_train=False)
    adv_pred = ex.outputs[0].asnumpy().argmax(axis=1)
    adv_acc = float((adv_pred == yv).mean())
    print("clean accuracy %.4f adversarial accuracy %.4f (eps=%g)"
          % (clean_acc, adv_acc, args.eps))


if __name__ == "__main__":
    main()
