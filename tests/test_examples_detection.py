"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: detection (SSD from RecordIO, Faster-RCNN) and the second
Kaggle pipeline.

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
from _examples_common import _run


def test_ssd_train_from_records(tmp_path):
    """SSD end-to-end on real RecordIO detection data: generate a tiny
    .rec via tools/im2rec.py --pack-label, then train a couple of batches
    through ImageDetRecordIter (reference example/ssd/train.py flow)."""
    _run("examples/ssd/train.py", "--make-rec", str(tmp_path))
    rec = tmp_path / "ssd_synth.rec"
    idx = tmp_path / "ssd_synth.idx"
    assert rec.exists() and idx.exists()
    p = _run("examples/ssd/train.py",
             "--rec", str(rec), "--rec-idx", str(idx),
             "--num-classes", "3", "--batch-size", "4",
             "--num-epochs", "1", "--preprocess-threads", "2",
             timeout=480)
    out = p.stderr + p.stdout
    assert "done" in out


def test_rcnn_end2end():
    """Toy Faster-RCNN: AnchorTarget CustomOp + RPN training, then the
    Proposal -> ROIPooling -> head composition must localize+classify
    most synthetic gt boxes (reference example/rcnn/train_end2end.py)."""
    import re
    p = _run("examples/rcnn/train_end2end.py", timeout=480)
    out = p.stderr + p.stdout
    rec = re.findall(r"detection recall ([0-9.]+)", out)
    assert rec, out[-800:]
    assert float(rec[-1]) > 0.6, out[-800:]


def test_kaggle_ndsb2_crps():
    """Reference example/kaggle-ndsb2/Train.py: CDF volume regression
    scored by CRPS (chance-level CRPS for a flat 0.5 CDF is 0.25)."""
    import re
    p = _run("examples/kaggle-ndsb2/Train.py", "--num-examples", "256",
             "--num-epochs", "8", timeout=480)
    m = re.findall(r"CRPS Systole ([0-9.]+) Diastole ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m, (p.stderr + p.stdout)[-500:]
    assert float(m[-1][0]) < 0.06 and float(m[-1][1]) < 0.06, m
