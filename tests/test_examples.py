"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: custom operators, the Module API demos, the torch bridge and
the profiler, memory and sweep tools (with the one example that stays in
the quick tier).

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
import pytest

from _examples_common import _run


def test_multi_task():
    import re
    p = _run("examples/multi-task/multitask_mlp.py",
             "--num-examples", "1024", "--num-epochs", "5")
    m = re.findall(r"mean task accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.85, (p.stderr + p.stdout)[-500:]


def test_numpy_ops_custom_softmax():
    import re
    p = _run("examples/numpy-ops/custom_softmax.py", "--num-epochs", "6")
    m = re.findall(r"numpy-op training accuracy ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_profiler_example(tmp_path):
    """Chrome-trace profiling around a bind+train loop (reference
    example/profiler): events land in the dump with sane timestamps."""
    import json
    out = str(tmp_path / "prof.json")
    _run("examples/profiler/profiler_executor.py", "--iters", "8",
         "--out", out)
    with open(out) as f:
        trace = json.load(f)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    names = {e.get("name") for e in events if isinstance(e, dict)}
    assert "executor_forward_train" in names, names
    assert "executor_backward" in names, names


def test_module_api_demos():
    """Reference example/module family: manual loop + checkpoint,
    SequentialModule chaining, PythonLossModule numpy gradient."""
    import re
    p = _run("examples/module/mnist_mlp.py", "--num-epochs", "4",
             "--num-examples", "2048")
    m = re.findall(r"manual-loop acc ([0-9.]+) reloaded acc ([0-9.]+) "
                   r"fit acc ([0-9.]+)", p.stderr + p.stdout)
    assert m, (p.stderr + p.stdout)[-500:]
    assert all(float(v) > 0.9 for v in m[-1]), m
    assert m[-1][0] == m[-1][1], m  # checkpoint roundtrip exactness
    p = _run("examples/module/sequential_module.py", "--num-epochs", "4",
             "--num-examples", "2048")
    m = re.findall(r"sequential-module acc ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]
    p = _run("examples/module/python_loss.py", "--num-epochs", "4",
             "--num-examples", "2048")
    m = re.findall(r"python-loss training accuracy ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_memcost():
    """Reference example/memcost: reports XLA memory analysis for the
    fused train step, plain vs mirrored."""
    import re
    p = _run("examples/memcost/memcost.py", "--num-layers", "20",
             "--batch-size", "8")
    out = p.stderr + p.stdout
    m = re.findall(r"mirror temp ratio ([0-9.]+)", out)
    assert m, out[-500:]
    assert "plain    temp" in out


def test_torch_layers_native_head():
    """Reference example/torch/torch_module.py: torch modules as graph
    layers, native softmax head."""
    import re
    pytest.importorskip("torch")
    p = _run("examples/torch/torch_module.py",
             "--num-examples", "1024", "--num-epochs", "3", timeout=480)
    m = re.findall(r"final accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_torch_criterion_path():
    """use_torch_criterion=True path: TorchCriterion drives backward and
    metric.Torch tracks the loss."""
    import re
    pytest.importorskip("torch")
    p = _run("examples/torch/torch_module.py",
             "--num-examples", "1024", "--num-epochs", "3",
             "--torch-criterion", timeout=480)
    m = re.findall(r"final accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_benchmark_sweep_driver(tmp_path):
    """Reference example/image-classification/benchmark.py: the sweep
    driver launches benchmark cells and collects images/sec rows."""
    import csv
    out = str(tmp_path / "sweep")
    _run("examples/image-classification/benchmark.py",
         "--networks", "mlp::64", "--num-examples", "256",
         "--image-shape", "1,28,28", "--num-classes", "10",
         "--kv-store", "local", "--out", out, timeout=480)
    with open(out + ".csv") as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 1 and rows[0]["ok"] == "True"
    assert float(rows[0]["images_per_sec"]) > 0
