"""The rest of a run with the timed path broken underneath: ``correct``
comes out false.  These drive ``run.main`` in this process with
``--rehearse`` (which is what skips the look for a chip) and break the
program where it produces its answer."""
import json

import pytest


def _main(capsys, *args):
    import importlib
    run = importlib.import_module("benchmark.run")
    from benchmark import harness
    try:
        rc = run.main(list(args) + ["--rehearse"])
    finally:
        harness.REHEARSAL = False
    out = [ln for ln in capsys.readouterr().out.splitlines()
           if ln.strip()]
    return rc, json.loads(out[-1]), out


def test_a_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from mxnet_tpu.parallel.dp import DataParallelTrainer
    honest = DataParallelTrainer.step

    # copies are taken before the donating step consumes the buffers
    def frozen_step(self, data, label=None, rng=None):
        import jax
        saved = jax.tree_util.tree_map(
            lambda a: a.copy(), (self.params, self.opt_state, self.aux))
        outs = honest(self, data, label, rng)
        self.params, self.opt_state, self.aux = saved
        return outs

    monkeypatch.setattr(DataParallelTrainer, "step", frozen_step)
    rc, last, out = _main(capsys, "--workload", "resnet50.fit-b128",
                          "--seed", "31")
    assert rc == 0 and last["correct"] is False
    checks = {c["name"]: c for ln in out if "checks" in json.loads(ln)
              for c in json.loads(ln)["checks"]}
    assert not checks["delta_norm_gap"]["ok"]
    assert checks["delta_norm_gap"]["value"] == pytest.approx(1.0)


def _fault_on_leaves(monkeypatch, picks, redo):
    """After every honest step the leaves ``picks(name)`` names are set
    to ``redo(before, after)``."""
    from mxnet_tpu.parallel.dp import DataParallelTrainer
    honest = DataParallelTrainer.step

    def step(self, data, label=None, rng=None):
        before = {k: v.copy() for k, v in self.params.items()
                  if picks(k)}
        assert before
        outs = honest(self, data, label, rng)
        self.params = dict(self.params, **{
            k: redo(b, self.params[k]) for k, b in before.items()})
        return outs

    monkeypatch.setattr(DataParallelTrainer, "step", step)


def test_one_leafs_update_skipped(capsys, monkeypatch):
    _fault_on_leaves(monkeypatch, lambda k: k == "stage2_unit1_bn2_gamma",
                     lambda before, after: before)
    rc, last, out = _main(capsys, "--workload", "resnet50.fit-b128",
                          "--seed", "34")
    assert rc == 0 and last["correct"] is False
    checks = {c["name"]: c for ln in out if "checks" in json.loads(ln)
              for c in json.loads(ln)["checks"]}
    assert checks["leaves_unchanged"]["value"] == 1
    # the median leaf does not see it
    assert checks["delta_norm_gap"]["ok"]


def test_an_update_doubled_on_the_batchnorm_shifts(capsys, monkeypatch):
    _fault_on_leaves(monkeypatch, lambda k: k.endswith("_beta"),
                     lambda before, after: after + (after - before))
    rc, last, out = _main(capsys, "--workload", "resnet50.fit-b128",
                          "--seed", "35")
    assert rc == 0 and last["correct"] is False
    checks = {c["name"]: c for ln in out if "checks" in json.loads(ln)
              for c in json.loads(ln)["checks"]}
    assert not checks["grad_norm_gap_p90"]["ok"]
    assert checks["grad_norm_gap"]["ok"]         # a third of the leaves
    assert checks["leaves_unchanged"]["value"] == 0


def test_part_of_the_batch_left_out(capsys, monkeypatch):
    from mxnet_tpu.parallel.dp import DataParallelTrainer
    honest = DataParallelTrainer._shard_batch

    def half(self, batch):
        import numpy as np
        out = {}
        for k, v in batch.items():
            a = np.array(getattr(v, "_data", v))
            a[len(a) // 2:] = a[:len(a) - len(a) // 2]   # rows repeated
            out[k] = a
        return honest(self, out)

    monkeypatch.setattr(DataParallelTrainer, "_shard_batch", half)
    rc, last, out = _main(capsys, "--workload", "resnet50.fit-b128",
                          "--seed", "32")
    assert rc == 0 and last["correct"] is False
    checks = {c["name"]: c for ln in out if "checks" in json.loads(ln)
              for c in json.loads(ln)["checks"]}
    assert not checks["loss_gap.step1"]["ok"]


def test_a_token_altered_where_it_is_produced(capsys, monkeypatch):
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    from benchmark import harness
    vocab = harness.Cell("lm2048.serve-chat-backlog",
                         rehearse=True).config["vocab_size"]
    honest = GenerationEngine._fetch_decode
    calls = {"n": 0}

    def altered(self, arr):
        out = honest(self, arr).copy()
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            out[:] = (out + 1) % vocab
        return out

    monkeypatch.setattr(GenerationEngine, "_fetch_decode", altered)
    rc, last, out = _main(capsys, "--workload",
                          "lm2048.serve-chat-backlog", "--seed", "33")
    assert rc == 0 and last["correct"] is False
    checks = {c["name"]: c for ln in out if "checks" in json.loads(ln)
              for c in json.loads(ln)["checks"]}
    assert not checks["token_gap_max"]["ok"]
