"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: image classification on MNIST (the stochastic-depth run is
most of this file's seconds).

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
from _examples_common import _run


def test_train_mnist_mlp_synthetic():
    import re
    p = _run("examples/image-classification/train_mnist.py",
             "--num-examples", "512", "--num-epochs", "2",
             "--batch-size", "64", "--data-dir", "/nonexistent")
    # the synthetic digits are separable: accuracy must move well past
    # the 10% chance level within 2 epochs
    accs = [float(m) for m in re.findall(
        r"Validation-accuracy=([0-9.]+)", p.stderr + p.stdout)]
    assert accs, (p.stdout[-500:], p.stderr[-500:])
    assert accs[-1] > 0.8, accs


def test_stochastic_depth():
    """Randomly-dropped residual blocks via a stateful CustomOp
    (reference example/stochastic-depth); also guards the
    callbacks-in-fused-program deadlock regression."""
    import re
    p = _run("examples/stochastic-depth/sd_mnist.py",
             "--num-examples", "2048", "--num-epochs", "12",
             "--death-rate", "0.3", timeout=480)
    m = re.findall(r"val accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.6, (p.stderr + p.stdout)[-500:]
