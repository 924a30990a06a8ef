"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: the other image models: ImageNet-style training, fine-tuning,
SVM output, adversarial examples, segmentation, style transfer and a
Kaggle pipeline.

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
import os

from _examples_common import REPO, _run


def test_train_imagenet_benchmark_tiny():
    _run("examples/image-classification/train_imagenet.py",
         "--benchmark", "1", "--num-examples", "64", "--batch-size", "8",
         "--num-epochs", "1", "--network", "resnet", "--num-layers", "18",
         "--image-shape", "3,64,64", "--num-classes", "100",
         "--kv-store", "local")


def test_fine_tune_transfers_backbone(tmp_path):
    """fine-tune.py cuts at the named layer, transfers backbone weights
    from the checkpoint, and trains a new head (reference
    example/image-classification/fine-tune.py)."""
    prefix = str(tmp_path / "base")
    _run("examples/image-classification/train_mnist.py",
         "--network", "lenet", "--num-examples", "256",
         "--num-epochs", "1", "--batch-size", "32",
         "--data-dir", "/nonexistent", "--model-prefix", prefix)
    p = _run("examples/image-classification/fine-tune.py",
             "--pretrained-model", prefix, "--pretrained-epoch", "1",
             "--layer-before-fullc", "flatten0",
             "--num-classes", "5", "--num-examples", "256",
             "--num-epochs", "1", "--image-shape", "1,28,28",
             "--benchmark", "1", timeout=300)
    out = p.stderr + p.stdout
    assert "Train-accuracy" in out

    # the backbone genuinely transfers: the surgically cut graph keeps
    # exactly the checkpoint weights that remain arguments, byte-equal
    import importlib.util
    import numpy as np
    import mxnet_tpu as mx
    spec = importlib.util.spec_from_file_location(
        "ft", os.path.join(REPO, "examples", "image-classification",
                           "fine-tune.py"))
    # import only the function without running main: read + exec the def
    import ast, types
    tree = ast.parse(open(spec.origin).read())
    mod = types.ModuleType("ft")
    mod.mx = mx
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and                 node.name == "get_fine_tune_model":
            exec(compile(ast.Module([node], []), "ft", "exec"),
                 mod.__dict__)
    sym, arg_params, _ = mx.model.load_checkpoint(prefix, 1)
    net, new_args = mod.get_fine_tune_model(sym, arg_params, 5,
                                            "flatten0")
    assert "convolution0_weight" in new_args
    np.testing.assert_array_equal(
        new_args["convolution0_weight"].asnumpy(),
        arg_params["convolution0_weight"].asnumpy())
    # old classifier weights are NOT carried into the new graph
    assert "fullyconnected1_weight" not in new_args
    assert "fc_finetune_weight" in net.list_arguments()


def test_svm_mnist():
    """SVMOutput margin objectives (reference example/svm_mnist)."""
    import re
    p = _run("examples/svm_mnist/svm_mnist.py",
             "--num-examples", "2048", "--num-epochs", "5")
    m = re.findall(r"final svm accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]
    p = _run("examples/svm_mnist/svm_mnist.py", "--use-linear",
             "--num-examples", "2048", "--num-epochs", "5")
    m = re.findall(r"final svm accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_adversary_fgsm():
    """FGSM through grad_req='write' on the data input (reference
    example/adversary): adversarial accuracy collapses from clean."""
    import re
    p = _run("examples/adversary/fgsm_mnist.py",
             "--num-examples", "1024", "--num-epochs", "4")
    m = re.findall(r"clean accuracy ([0-9.]+) adversarial accuracy "
                   r"([0-9.]+)", p.stderr + p.stdout)
    assert m, (p.stderr + p.stdout)[-500:]
    clean, adv = float(m[-1][0]), float(m[-1][1])
    assert clean > 0.95, m
    assert adv < clean - 0.1, m


def test_fcn_segmentation():
    """FCN with Deconvolution+Crop+multi-output softmax (reference
    example/fcn-xs): high pixel accuracy on blob segmentation."""
    import re
    p = _run("examples/fcn-xs/fcn_seg.py",
             "--num-examples", "256", "--num-epochs", "8", timeout=480)
    m = re.findall(r"pixel accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.85, (p.stderr + p.stdout)[-500:]


def test_neural_style():
    """Input-image optimization against Gram/content losses (reference
    example/neural-style): loss must collapse by orders of magnitude."""
    import re
    p = _run("examples/neural-style/nstyle.py", "--iters", "80")
    m = re.findall(r"ratio ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) < 0.01, (p.stderr + p.stdout)[-500:]


def test_kaggle_ndsb1_pipeline(tmp_path):
    """Reference example/kaggle-ndsb1: class folders -> gen_img_list ->
    im2rec -> train -> predict -> submission CSV."""
    import re
    work = str(tmp_path / "ndsb1")
    p = _run("examples/kaggle-ndsb1/train_dsb.py", "--work-dir", work,
             "--num-epochs", "12", timeout=480)
    m = re.findall(r"val accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.55, (p.stderr + p.stdout)[-500:]
    _run("examples/kaggle-ndsb1/predict_dsb.py",
         "--model-prefix", os.path.join(work, "dsb"), "--epoch", "12",
         "--rec", os.path.join(work, "dsb_val.rec"),
         "--out", os.path.join(work, "probs.npz"))
    p = _run("examples/kaggle-ndsb1/submission_dsb.py",
             "--probs", os.path.join(work, "probs.npz"),
             "--classes", os.path.join(work, "classes.txt"),
             "--out", os.path.join(work, "submission.csv"))
    m = re.findall(r"val logloss ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) < 1.2, (p.stderr + p.stdout)[-500:]
    with open(os.path.join(work, "submission.csv")) as f:
        header = f.readline().strip().split(",")
        rows = f.readlines()
    assert header[0] == "image" and len(header) == 9
    assert len(rows) > 0
    probs = [float(v) for v in rows[0].split(",")[1:]]
    assert abs(sum(probs) - 1.0) < 1e-3
