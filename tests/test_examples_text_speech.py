"""Smoke-run the example scripts (reference example/ is the acceptance
suite; tests/python/train is the reference's trainer-level tier).
This file: CTC (OCR, speech), sequence sorting, text classification and
NCE.

Each test is a subprocess that imports jax and trains, so the examples
are seven files by family (``tests/test_examples*.py``, the runner in
``tests/_examples_common.py``) and ``--dist loadfile`` runs them side
by side.
"""
from _examples_common import _run


def test_warpctc_lstm_ocr():
    """LSTM+CTC toy OCR must actually learn: exact-sequence accuracy via
    greedy CTC decode well above chance (reference example/warpctc/
    toy_ctc.py protocol)."""
    import re
    p = _run("examples/warpctc/lstm_ocr.py",
             "--seq-len", "20", "--num-hidden", "64",
             "--num-epochs", "14", "--batches-per-epoch", "30",
             timeout=480)
    out = p.stderr + p.stdout
    accs = re.findall(r"final seq accuracy ([0-9.]+)", out)
    assert accs, out[-800:]
    assert float(accs[-1]) > 0.8, out[-800:]


def test_bi_lstm_sort():
    import re
    p = _run("examples/bi-lstm-sort/sort_lstm.py",
             "--num-examples", "2048", "--num-epochs", "8", timeout=480)
    m = re.findall(r"final sorted-token accuracy ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.7, (p.stderr + p.stdout)[-500:]


def test_speech_recognition_ctc():
    """Reference example/speech_recognition: DeepSpeech-style conv+LSTM
    +CTC transcribes synthetic utterances (CER near zero; an all-blank
    collapse scores CER 1.0)."""
    import re
    p = _run("examples/speech_recognition/train.py",
             "--num-epochs", "20", "--batches-per-epoch", "25",
             timeout=560)
    m = re.findall(r"final CER ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) < 0.1, (p.stderr + p.stdout)[-500:]


def test_cnn_text_classification():
    import re
    p = _run("examples/cnn_text_classification/text_cnn.py",
             "--num-examples", "1024", "--num-epochs", "4")
    m = re.findall(r"validation accuracy ([0-9.]+)", p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.9, (p.stderr + p.stdout)[-500:]


def test_nce_loss():
    """NCE over a 1000-word vocab (reference example/nce-loss/toy_nce.py):
    full-vocab scoring with NCE-trained embeddings is accurate."""
    import re
    p = _run("examples/nce-loss/toy_nce.py",
             "--num-examples", "8192", "--num-epochs", "10")
    m = re.findall(r"full-vocab nce accuracy ([0-9.]+)",
                   p.stderr + p.stdout)
    assert m and float(m[-1]) > 0.5, (p.stderr + p.stdout)[-500:]
