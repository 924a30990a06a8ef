"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: autoencoder, GAN, clustering, recommender, SGLD and DQN.

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
from _examples_common import _run


def test_autoencoder():
    import re
    p = _run("examples/autoencoder/mnist_sae.py",
             "--num-examples", "512", "--num-epochs", "8")
    m = re.findall(r"final reconstruction mse ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m and float(m[-1]) < 0.05, (p.stderr + p.stdout)[-500:]


def test_gan_mlp():
    """Adversarial dynamics through the two-module inputs_need_grad
    protocol, run end to end (a full GAN convergence bar would be
    flaky).  60 iterations and not 600: the bar below reads 0.647 after
    ONE iteration and 0.62 after 60, so the other 540 held nothing and
    cost 280 s, the classic updater compiling Adam's step anew for
    every parameter every iteration (ROADMAP.md D16)."""
    import re
    p = _run("examples/gan/gan_mlp.py", "--iters", "60", timeout=480)
    out = p.stderr + p.stdout
    m = re.findall(r"mean distance to nearest mode ([0-9.]+)", out)
    assert m and float(m[-1]) < 0.9, out[-500:]


def test_recommenders_matrix_fact():
    """Embedding-based matrix factorization (reference
    example/recommenders/matrix_fact.py): held-out RMSE beats the
    rating std by a wide margin."""
    import re
    p = _run("examples/recommenders/matrix_fact.py",
             "--num-ratings", "20000", "--num-epochs", "10")
    m = re.findall(r"rating std ([0-9.]+) final val rmse ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m, (p.stderr + p.stdout)[-500:]
    std, rmse = float(m[-1][0]), float(m[-1][1])
    assert rmse < 0.5 * std, m


def test_bayesian_sgld():
    """SGLD posterior sampling (reference example/bayesian-methods):
    MC-averaged predictive beats chance decisively."""
    import re
    p = _run("examples/bayesian-methods/sgld_mnist.py",
             "--num-examples", "2048", "--num-epochs", "8",
             "--burn-in-epochs", "4")
    m = re.findall(r"mc-averaged acc ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.8, (p.stderr + p.stdout)[-500:]


def test_dqn_chain():
    """DQN with target-network parameter sync (reference
    example/reinforcement-learning/dqn): returns improve to
    near-optimal."""
    import re
    p = _run("examples/reinforcement-learning/dqn_chain.py",
             "--episodes", "200", timeout=480)
    m = re.findall(r"last-50 ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.7, (p.stderr + p.stdout)[-500:]


def test_dec_clustering():
    """Reference example/dec/dec.py: DEC refinement must beat its own
    k-means initialization."""
    import re
    p = _run("examples/dec/dec.py", "--num-examples", "1024",
             timeout=480)
    m = re.findall(r"cluster acc: kmeans ([0-9.]+) final ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m, (p.stderr + p.stdout)[-500:]
    km, final = float(m[-1][0]), float(m[-1][1])
    assert final > 0.75 and final > km + 0.03, m
