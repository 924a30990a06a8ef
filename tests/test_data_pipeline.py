"""Checkpointable sharded streaming data pipeline tests
(docs/architecture/data_pipeline.md).

Covers the `mxnet_tpu/data/` plane end to end: deterministic seeded
global shuffle + (part_index, num_parts) sharding, the
state_dict()/load_state() round-trip property over every shipped
DataIter (NDArrayIter, CSVIter, ImageRecordIter±idx, ImageDetRecordIter,
Resize/Prefetching wrappers, DeviceStager-fronted, BucketSentenceIter
time-major), consumer-frontier semantics through the threaded stages,
the checkpoint envelope beside params, mid-epoch fit resume with a
byte-identical remaining stream (the acceptance pin, also under
num_parts=2), and the seeded subprocess SIGKILL-mid-epoch scenario
(mirrors the PR-2 server-death test)."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.data import ShardedRecordDataset
from mxnet_tpu.io import recordio

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------
def _write_rec(path, idx_path=None, n=24, size=12, label_width=1,
               start_id=0):
    """Records whose pixel content and label encode the record id."""
    from mxnet_tpu.io.image_util import encode_image
    w = recordio.MXIndexedRecordIO(idx_path, path, "w") if idx_path \
        else recordio.MXRecordIO(path, "w")
    for i in range(n):
        rid = start_id + i
        img = np.full((size, size, 3), (rid * 7) % 255, np.uint8)
        img[0, 0] = rid % 255
        if label_width == 1:
            label = float(rid)
        else:
            label = np.arange(label_width, dtype=np.float32) + rid
        buf = recordio.pack(recordio.IRHeader(0, label, rid, 0),
                            encode_image(img, fmt=".png"))
        if idx_path:
            w.write_idx(rid, buf)
        else:
            w.write(buf)
    w.close()


def _sig(batch):
    """Byte-level identity of one batch: data + label + pad."""
    parts = [a.asnumpy().tobytes() for a in batch.data]
    parts += [a.asnumpy().tobytes() for a in (batch.label or [])]
    return (hashlib.sha1(b"".join(parts)).hexdigest(),
            int(batch.pad or 0), getattr(batch, "bucket_key", None))


def _epoch_sigs(it):
    return [_sig(b) for b in it]


def _labels_of_epoch(it):
    out = []
    for b in it:
        keep = b.label[0].shape[0] - (b.pad or 0)
        out.extend(b.label[0].asnumpy().reshape(
            b.label[0].shape[0], -1)[:keep, 0].astype(int).tolist())
    return out


# ---------------------------------------------------------------------------
# ShardedRecordDataset: shuffle / sharding / state
# ---------------------------------------------------------------------------
def test_sharded_seeded_shuffle_identical_across_instances(tmp_path):
    rec, idx = str(tmp_path / "a.rec"), str(tmp_path / "a.idx")
    _write_rec(rec, idx, n=30)

    def order(epochs):
        ds = ShardedRecordDataset(rec, idx, shuffle=True, seed=13)
        out = []
        for _ in range(epochs):
            ords = []
            while True:
                item = ds.read()
                if item is None:
                    break
                ords.append(item[1]["ordinal"])
            out.append(ords)
            ds.reset()
        ds.close()
        return out

    e1 = order(2)
    e2 = order(2)
    assert e1 == e2, "same seed must give the identical epoch plan"
    assert e1[0] != e1[1], "epochs must reshuffle"
    assert sorted(e1[0]) == list(range(30))


def test_sharded_partition_disjoint_exhaustive_and_global(tmp_path):
    rec, idx = str(tmp_path / "p.rec"), str(tmp_path / "p.idx")
    _write_rec(rec, idx, n=20)

    def part_orders(num_parts):
        outs = []
        for pi in range(num_parts):
            ds = ShardedRecordDataset(rec, idx, shuffle=True, seed=5,
                                      part_index=pi, num_parts=num_parts)
            ords = []
            while True:
                item = ds.read()
                if item is None:
                    break
                ords.append(item[1]["ordinal"])
            ds.close()
            outs.append(ords)
        return outs

    p0, p1 = part_orders(2)
    assert not set(p0) & set(p1), "parts must be disjoint"
    assert sorted(p0 + p1) == list(range(20)), "parts must be exhaustive"
    # both parts are strided slices of ONE global permutation
    (g,) = part_orders(1)
    assert p0 == g[0::2] and p1 == g[1::2]


def test_sharded_multifile_global_index(tmp_path):
    rec1, idx1 = str(tmp_path / "f1.rec"), str(tmp_path / "f1.idx")
    rec2, idx2 = str(tmp_path / "f2.rec"), str(tmp_path / "f2.idx")
    _write_rec(rec1, idx1, n=8, start_id=0)
    _write_rec(rec2, idx2, n=8, start_id=100)
    ds = ShardedRecordDataset([rec1, rec2], [idx1, idx2], shuffle=False)
    ids = []
    while True:
        item = ds.read()
        if item is None:
            break
        raw, meta = item
        header, _ = recordio.unpack(raw)
        ids.append(int(header.id))
    ds.close()
    assert ids == list(range(8)) + list(range(100, 108))


def test_sharded_state_roundtrip_indexed_and_windowed(tmp_path):
    rec, idx = str(tmp_path / "s.rec"), str(tmp_path / "s.idx")
    _write_rec(rec, idx, n=18)
    for kwargs in ({"path_imgidx": idx}, {}):  # permutation / window
        ds = ShardedRecordDataset(rec, shuffle=True, seed=3,
                                  shuffle_window=5, **kwargs)
        ref = []
        while True:
            item = ds.read()
            if item is None:
                break
            ref.append(item[1]["ordinal"])
        ds.rewind_epoch()
        got, st = [], None
        for _ in range(7):
            got.append(ds.read()[1]["ordinal"])
        st = ds.state_dict()
        ds.close()
        fresh = ShardedRecordDataset(rec, shuffle=True, seed=3,
                                     shuffle_window=5, **kwargs)
        fresh.load_state(st)
        while True:
            item = fresh.read()
            if item is None:
                break
            got.append(item[1]["ordinal"])
        fresh.close()
        assert got == ref, "resume must replay zero and skip zero"


def test_sharded_unseeded_parity_with_legacy_rng_pattern(tmp_path):
    """MXNET_DATA_SEED unset = the legacy module-global RNG call
    pattern, bit-for-bit: indexed shuffle draws np.random.permutation
    at construction/reset; the window shuffle emits via
    np.random.randint swap-pop."""
    rec, idx = str(tmp_path / "u.rec"), str(tmp_path / "u.idx")
    _write_rec(rec, idx, n=16)

    np.random.seed(42)
    expect = list(np.random.permutation(16))
    np.random.seed(42)
    ds = ShardedRecordDataset(rec, idx, shuffle=True)
    got = []
    while True:
        item = ds.read()
        if item is None:
            break
        got.append(item[1]["ordinal"])
    ds.close()
    assert got == expect

    # window shuffle: replay the documented reservoir algorithm
    np.random.seed(7)
    buf, out, stream = [], [], list(range(16))
    k = 0
    while buf or k < 16:
        while k < 16 and len(buf) < 5:
            buf.append(stream[k])
            k += 1
        i = np.random.randint(len(buf))
        buf[i], buf[-1] = buf[-1], buf[i]
        out.append(buf.pop())
    np.random.seed(7)
    ds = ShardedRecordDataset(rec, shuffle=True, shuffle_window=5)
    got = []
    while True:
        item = ds.read()
        if item is None:
            break
        got.append(item[1]["ordinal"])
    ds.close()
    assert got == out

    # and the cursor half of the state still round-trips unseeded
    np.random.seed(9)
    ds = ShardedRecordDataset(rec, idx, shuffle=True)
    ref = []
    while True:
        item = ds.read()
        if item is None:
            break
        ref.append(item[1]["ordinal"])
    ds.rewind_epoch()   # NOTE: draws a fresh unseeded permutation
    head = [ds.read()[1]["ordinal"] for _ in range(5)]
    st = ds.state_dict()
    assert st["order"] is not None, "unseeded perm must ride the state"
    fresh = ShardedRecordDataset(rec, idx, shuffle=True)
    fresh.load_state(st)
    tail = []
    while True:
        item = fresh.read()
        if item is None:
            break
        tail.append(item[1]["ordinal"])
    ds.close()
    fresh.close()
    assert sorted(head + tail) == list(range(16))
    assert len(head) + len(tail) == 16


def test_windowed_sharded_resume_including_eof_tail(tmp_path):
    """Index-less + num_parts>1: the rebuild scan must accept trailing
    other-part ordinals before EOF (regression: a src_eof state of a
    non-last part raised 'record file shrank')."""
    rec = str(tmp_path / "w.rec")
    _write_rec(rec, n=17)   # odd tail: last ordinal belongs to part 0
    for pi in (0, 1):
        ds = ShardedRecordDataset(rec, shuffle=True, seed=5,
                                  shuffle_window=4, part_index=pi,
                                  num_parts=2)
        ref = []
        while True:
            item = ds.read()
            if item is None:
                break
            ref.append(item[1]["ordinal"])
        # capture at EVERY prefix length, including after src_eof
        for k in range(len(ref) + 1):
            ds.rewind_epoch()
            got = [ds.read()[1]["ordinal"] for _ in range(k)]
            st = json.loads(json.dumps(ds.state_dict()))
            fresh = ShardedRecordDataset(rec, shuffle=True, seed=5,
                                         shuffle_window=4, part_index=pi,
                                         num_parts=2)
            fresh.load_state(st)
            while True:
                item = fresh.read()
                if item is None:
                    break
                got.append(item[1]["ordinal"])
            fresh.close()
            assert got == ref, (pi, k)
        ds.close()


def test_unseeded_sharded_indexed_shuffle_rejected(tmp_path):
    """Indexed shuffle + num_parts>1 + no seed would give every worker
    its own permutation (overlapping, incomplete shards) — must raise,
    both at construction and through set_partition."""
    rec, idx = str(tmp_path / "us.rec"), str(tmp_path / "us.idx")
    _write_rec(rec, idx, n=8)
    with pytest.raises(MXNetError, match="MXNET_DATA_SEED"):
        ShardedRecordDataset(rec, idx, shuffle=True, num_parts=2,
                             part_index=0)
    ds = ShardedRecordDataset(rec, idx, shuffle=True)
    with pytest.raises(MXNetError, match="MXNET_DATA_SEED"):
        ds.set_partition(0, 2)
    ds.close()
    # the window shuffle partitions BEFORE shuffling: fine unseeded
    rec2 = str(tmp_path / "us2.rec")
    _write_rec(rec2, n=8)
    ShardedRecordDataset(rec2, shuffle=True, num_parts=2,
                         part_index=0).close()


def test_epoch_boundary_state_rolls_on_plain_iterators():
    """An epoch-boundary capture of the non-pipeline iterators
    (NDArrayIter / ResizeIter / BucketSentenceIter) must resume into a
    working next epoch, not a silent zero-batch one (regression: the
    exhausted cursor round-tripped verbatim)."""
    X = np.arange(80, dtype=np.float32).reshape(20, 4)
    y = np.arange(20, dtype=np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=3)
    n_ref = len(list(it))                 # exhausts the epoch
    st = json.loads(json.dumps(it.state_dict()))
    fresh = mx.io.NDArrayIter(X, y, batch_size=3)
    fresh.load_state(st)
    assert len(list(fresh)) == n_ref, "resumed epoch must not be empty"

    rit = mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=4), 3)
    assert len(list(rit)) == 3
    st = rit.state_dict()
    fresh = mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=4), 3)
    fresh.load_state(st)
    assert len(list(fresh)) == 3

    sentences = [[1, 2, 3], [2, 3], [1, 2, 3, 4, 5], [3, 4], [1, 2]] * 4
    np.random.seed(2)
    bit = mx.rnn.BucketSentenceIter(sentences, batch_size=2,
                                    buckets=[3, 6])
    n_ref = len(list(bit))
    st = bit.state_dict()
    np.random.seed(3)
    fresh = mx.rnn.BucketSentenceIter(sentences, batch_size=2,
                                      buckets=[3, 6])
    fresh.load_state(st)
    assert len(list(fresh)) == n_ref


def test_roll_over_epoch_boundary_resume_keeps_leftover_offset():
    """roll_over epoch-boundary resume must start the next epoch at the
    leftover offset, exactly like the uninterrupted run's reset()
    (regression: reset() was fed the pre-increment cursor, replaying
    the records the wrapped final batch already consumed)."""
    X = np.arange(40, dtype=np.float32).reshape(10, 4)
    y = np.arange(10, dtype=np.float32)

    def factory():
        return mx.io.NDArrayIter(X, y, batch_size=4,
                                 last_batch_handle="roll_over")

    ref = factory()
    list(ref)          # epoch 1 (final batch wraps 2 records)
    ref.reset()
    ref_next = [b.label[0].asnumpy().tolist() for b in ref]

    it = factory()
    list(it)
    st = it.state_dict()
    fresh = factory()
    fresh.load_state(st)
    got = [b.label[0].asnumpy().tolist() for b in fresh]
    assert got == ref_next


def test_prefetch_reader_error_surfaces_to_consumer():
    """An exception (not StopIteration) inside a wrapped iterator's
    next() must surface at the consumer, not hang it on an empty
    queue."""
    class _Exploding:
        provide_data = [mx.io.DataDesc("data", (2, 2))]
        provide_label = []
        batch_size = 2

        def next(self):
            raise OSError("disk gone")

        def reset(self):
            pass

    pit = mx.io.PrefetchingIter(_Exploding())
    with pytest.raises(MXNetError, match="disk gone"):
        next(pit)


def test_sharded_state_guards(tmp_path):
    rec, idx = str(tmp_path / "g.rec"), str(tmp_path / "g.idx")
    _write_rec(rec, idx, n=8)
    ds = ShardedRecordDataset(rec, idx, shuffle=True, seed=2)
    st = ds.state_dict()
    other = ShardedRecordDataset(rec, idx, shuffle=True, seed=3)
    with pytest.raises(MXNetError, match="seed"):
        other.load_state(st)
    other.close()
    part = ShardedRecordDataset(rec, idx, shuffle=True, seed=2,
                                part_index=0, num_parts=2)
    with pytest.raises(MXNetError, match="partition"):
        part.load_state(st)
    part.close()
    ds.read()
    with pytest.raises(MXNetError, match="repartition|mid-epoch"):
        ds.set_partition(0, 2)
    ds.close()


def test_eof_state_rolls_into_next_epoch(tmp_path):
    rec, idx = str(tmp_path / "eo.rec"), str(tmp_path / "eo.idx")
    _write_rec(rec, idx, n=8)
    ds = ShardedRecordDataset(rec, idx, shuffle=True, seed=4)
    while ds.read() is not None:
        pass
    st = ds.state_dict()
    st["eof"] = True     # what the pipeline stamps at epoch end
    ds.reset()           # the uninterrupted run's next epoch
    ref = []
    while True:
        item = ds.read()
        if item is None:
            break
        ref.append(item[1]["ordinal"])
    ds.close()
    fresh = ShardedRecordDataset(rec, idx, shuffle=True, seed=4)
    fresh.load_state(st)
    assert fresh.epoch == 1
    got = []
    while True:
        item = fresh.read()
        if item is None:
            break
        got.append(item[1]["ordinal"])
    fresh.close()
    assert got == ref


# ---------------------------------------------------------------------------
# per-record augmentation RNG (MXNET_DATA_SEED)
# ---------------------------------------------------------------------------
def test_seeded_augmentation_invariant_to_threads_and_batches(
        tmp_path, monkeypatch):
    """The per-record generator makes augmentation a pure function of
    (seed, epoch, ordinal): pool width and batch boundaries must not
    change a single pixel."""
    monkeypatch.setenv("MXNET_DATA_SEED", "21")
    rec, idx = str(tmp_path / "r.rec"), str(tmp_path / "r.idx")
    _write_rec(rec, idx, n=16, size=20)

    def stream(threads, batch):
        it = mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 16, 16),
            batch_size=batch, shuffle=True, rand_crop=True,
            rand_mirror=True, max_rotate_angle=15, random_h=10,
            preprocess_threads=threads)
        rows = {}
        for b in it:
            keep = b.label[0].shape[0] - (b.pad or 0)
            lab = b.label[0].asnumpy()[:keep]
            dat = b.data[0].asnumpy()[:keep]
            for l, d in zip(lab, dat):
                rows[int(l)] = d.tobytes()
        it.close()
        return rows

    a = stream(1, 4)
    b = stream(4, 8)
    assert a == b


def test_unseeded_augmentation_uses_global_numpy(tmp_path):
    """Legacy escape hatch: with the seed unset, decode_record_image
    draws from module-global np.random (same call pattern as before
    the data plane)."""
    from mxnet_tpu.io.image_util import decode_record_image, encode_image
    img = (np.arange(20 * 20 * 3) % 255).astype(np.uint8).reshape(
        20, 20, 3)
    raw = encode_image(img, fmt=".png")
    np.random.seed(3)
    a = decode_record_image(raw, (3, 16, 16), rand_crop=True,
                            rand_mirror=True, max_rotate_angle=20)
    np.random.seed(3)
    b = decode_record_image(raw, (3, 16, 16), rand_crop=True,
                            rand_mirror=True, max_rotate_angle=20)
    np.testing.assert_array_equal(a, b)
    c = decode_record_image(raw, (3, 16, 16), rand_crop=True,
                            rand_mirror=True, max_rotate_angle=20)
    assert not np.array_equal(a, c), "global RNG must advance"


# ---------------------------------------------------------------------------
# state round-trip property over the shipped iterator chain
# ---------------------------------------------------------------------------
def _csv_files(tmp_path):
    rs = np.random.RandomState(0)
    data = rs.uniform(0, 1, (20, 3)).astype(np.float32)
    labs = np.arange(20, dtype=np.float32)
    dp, lp = str(tmp_path / "d.csv"), str(tmp_path / "l.csv")
    np.savetxt(dp, data, delimiter=",", fmt="%.6f")
    np.savetxt(lp, labs, delimiter=",", fmt="%.1f")
    return dp, lp


def _chain_factories(tmp_path):
    """(name, factory) pairs; every factory builds an identically-
    configured iterator (seeding the global RNG so unseeded shuffles
    agree across instances)."""
    rec, idx = str(tmp_path / "c.rec"), str(tmp_path / "c.idx")
    _write_rec(rec, idx, n=24)
    rec2 = str(tmp_path / "c2.rec")
    _write_rec(rec2, n=24)
    drec, didx = str(tmp_path / "det.rec"), str(tmp_path / "det.idx")
    _write_det_rec(drec, didx, n=12)
    dp, lp = _csv_files(tmp_path)
    X = np.arange(80, dtype=np.float32).reshape(20, 4)
    y = np.arange(20, dtype=np.float32)
    sentences = [[1, 2, 3], [2, 3], [1, 2, 3, 4, 5], [3, 4], [1, 2]] * 4

    def nda():
        np.random.seed(5)
        return mx.io.NDArrayIter(X, y, batch_size=3, shuffle=True,
                                 last_batch_handle="pad")

    def nda_discard():
        np.random.seed(6)
        return mx.io.NDArrayIter(X, y, batch_size=3, shuffle=True,
                                 last_batch_handle="discard")

    def csv():
        return mx.io.CSVIter(data_csv=dp, data_shape=(3,), label_csv=lp,
                             batch_size=4)

    def rec_idx():
        return mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 12, 12),
            batch_size=4, shuffle=True, rand_crop=True, rand_mirror=True,
            preprocess_threads=2, seed=17)

    def rec_noidx():
        return mx.io.ImageRecordIter(
            path_imgrec=rec2, data_shape=(3, 12, 12), batch_size=4,
            shuffle=True, shuffle_buffer=6, preprocess_threads=2,
            seed=17)

    def det():
        return mx.io.ImageDetRecordIter(
            path_imgrec=drec, path_imgidx=didx, data_shape=(3, 16, 16),
            batch_size=3, shuffle=True, max_objects=4,
            preprocess_threads=2, seed=17)

    def resize():
        np.random.seed(5)
        return mx.io.ResizeIter(
            mx.io.NDArrayIter(X, y, batch_size=3, shuffle=True), 9)

    def prefetch():
        np.random.seed(5)
        return mx.io.PrefetchingIter(
            mx.io.NDArrayIter(X, y, batch_size=3, shuffle=True))

    def staged():
        import jax
        np.random.seed(5)
        return mx.io.DeviceStager(
            mx.io.NDArrayIter(X, y, batch_size=3, shuffle=True),
            jax.device_put)

    def bucket_tn():
        np.random.seed(8)
        return mx.rnn.BucketSentenceIter(sentences, batch_size=2,
                                         buckets=[3, 6], layout="TN")

    return [("NDArrayIter", nda), ("NDArrayIter-discard", nda_discard),
            ("CSVIter", csv), ("ImageRecordIter+idx", rec_idx),
            ("ImageRecordIter-noidx", rec_noidx),
            ("ImageDetRecordIter", det), ("ResizeIter", resize),
            ("PrefetchingIter", prefetch), ("DeviceStager", staged),
            ("BucketSentenceIter-TN", bucket_tn)]


def _collect(it):
    sigs = []
    while True:
        try:
            b = next(it)
        except StopIteration:
            break
        sigs.append(_sig(b))
    return sigs


def test_state_roundtrip_property_over_iterator_chain(tmp_path):
    """THE acceptance property: for every shipped DataIter, consume k
    batches, capture state, load it into a FRESH identically-built
    iterator — the remaining stream must be byte-identical to the
    uninterrupted run's, zero replayed, zero skipped."""
    for name, factory in _chain_factories(tmp_path):
        ref_it = factory()
        ref = _collect(ref_it)
        assert len(ref) >= 3, name
        k = max(1, len(ref) // 2)
        part = factory()
        got_head = [_sig(next(part)) for _ in range(k)]
        assert got_head == ref[:k], "%s: pre-state stream diverged" % name
        st = part.state_dict()
        # round-trip through JSON like the envelope does
        st = json.loads(json.dumps(st))
        fresh = factory()
        fresh.load_state(st)
        got_tail = _collect(fresh)
        assert got_tail == ref[k:], \
            "%s: resumed stream not byte-identical" % name
        for it in (ref_it, part, fresh):
            if hasattr(it, "close"):
                it.close()


def _write_det_rec(path, idx_path, n=12, size=24):
    """Synthetic detection .rec: one box per image, id-coded."""
    from mxnet_tpu.io.image_util import encode_image
    w = recordio.MXIndexedRecordIO(idx_path, path, "w")
    rs = np.random.RandomState(1)
    for i in range(n):
        img = rs.randint(0, 200, (size, size, 3)).astype(np.uint8)
        x0, y0 = 0.1 + (i % 4) * 0.1, 0.2
        label = np.array([2, 5, float(i % 3), x0, y0, x0 + 0.3, y0 + 0.4],
                         dtype=np.float32)
        w.write_idx(i, recordio.pack(recordio.IRHeader(0, label, i, 0),
                                     encode_image(img, fmt=".png")))
    w.close()


def test_det_iter_resume_on_detection_shapes(tmp_path, monkeypatch):
    """The detection surface rides the proven path: (batch, max_objects,
    object_width) label tensors stream through the checkpointable
    pipeline and resume mid-epoch with augmentation replay."""
    monkeypatch.setenv("MXNET_DATA_SEED", "9")
    drec, didx = str(tmp_path / "d.rec"), str(tmp_path / "d.idx")
    _write_det_rec(drec, didx, n=12)

    def factory():
        return mx.io.ImageDetRecordIter(
            path_imgrec=drec, path_imgidx=didx, data_shape=(3, 16, 16),
            batch_size=3, shuffle=True, max_objects=4,
            rand_mirror_prob=0.5, rand_crop_prob=0.5,
            min_crop_scales=(0.7,), max_crop_scales=(1.0,),
            preprocess_threads=2)

    it = factory()
    assert it.provide_label[0].shape == (3, 4, 5)
    ref = _collect(it)
    part = factory()
    head = [_sig(next(part)) for _ in range(2)]
    assert head == ref[:2]
    st = part.state_dict()
    fresh = factory()
    fresh.load_state(st)
    assert _collect(fresh) == ref[2:]
    for x in (it, part, fresh):
        x.close()


def test_rnn_time_major_layout_round_trips():
    """Time-major (TN) bucketed batches carry their layout through the
    protocol and replay exactly after a state round-trip."""
    sentences = [[1, 2, 3], [2, 3], [1, 2, 3, 4, 5], [3, 4], [1, 2]] * 4

    def factory():
        np.random.seed(4)
        return mx.rnn.BucketSentenceIter(sentences, batch_size=2,
                                         buckets=[3, 6], layout="TN")

    it = factory()
    b0 = next(it)
    assert b0.provide_data[0].layout == "TN"
    assert b0.data[0].shape[1] == 2   # batch on axis 1 = time-major
    ref = [_sig(b0)] + [_sig(b) for b in it]
    part = factory()
    assert [_sig(next(part)) for _ in range(2)] == ref[:2]
    st = json.loads(json.dumps(part.state_dict()))
    fresh = factory()
    fresh.load_state(st)
    assert [_sig(b) for b in fresh] == ref[2:]


# ---------------------------------------------------------------------------
# frontier semantics through the threaded stages
# ---------------------------------------------------------------------------
def test_stager_state_is_consumer_frontier_not_readahead(tmp_path):
    """The DeviceStager stages ahead of training; its state_dict must
    reflect what the consumer TOOK, never what was staged."""
    import jax
    import time
    X = np.arange(120, dtype=np.float32).reshape(30, 4)
    y = np.arange(30, dtype=np.float32)

    def factory():
        return mx.io.NDArrayIter(X, y, batch_size=3)

    stager = mx.io.DeviceStager(factory(), jax.device_put, depth=4)
    ref = _collect(mx.io.DeviceStager(factory(), jax.device_put))
    for _ in range(2):
        next(stager)
    time.sleep(0.3)          # let the producer run ahead into the queue
    st = stager.state_dict()
    assert int(st["cursor"]) == 3, \
        "state must be the 2-batches-consumed frontier (cursor=(k-1)*B)"
    fresh = mx.io.DeviceStager(factory(), jax.device_put)
    fresh.load_state(st)
    assert _collect(fresh) == ref[2:]
    stager.close()
    fresh.close()


def test_pipeline_frontier_excludes_decode_readahead(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("MXNET_DATA_SEED", "6")
    import time
    rec, idx = str(tmp_path / "f.rec"), str(tmp_path / "f.idx")
    _write_rec(rec, idx, n=32)

    def factory():
        return mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 12, 12),
            batch_size=4, shuffle=True, prefetch_buffer=4,
            preprocess_threads=2)

    it = factory()
    ref = _collect(factory())
    next(it)
    next(it)
    time.sleep(0.4)          # producer decodes well past the consumer
    st = it.state_dict()
    assert st["batches"] == 2
    fresh = factory()
    fresh.load_state(st)
    assert _collect(fresh) == ref[2:]
    it.close()
    fresh.close()


def test_faultinject_data_next_seam(tmp_path):
    """The pipeline consumer seam honors the seeded plan: a delay rule
    fires per batch, deterministically."""
    from mxnet_tpu import faultinject
    rec, idx = str(tmp_path / "fi.rec"), str(tmp_path / "fi.idx")
    _write_rec(rec, idx, n=8)
    plan = faultinject.install(
        {"seed": 3, "rules": [{"seam": "data.next", "nth": 2,
                               "action": "error"}]})
    try:
        it = mx.io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                                   data_shape=(3, 12, 12), batch_size=4,
                                   preprocess_threads=1)
        next(it)
        with pytest.raises(OSError):
            next(it)
        assert plan.log == [("data.next", "batch", None, None, "error")]
        it.close()
    finally:
        faultinject.install(None)


# ---------------------------------------------------------------------------
# checkpoint envelope
# ---------------------------------------------------------------------------
def test_data_state_envelope_roundtrip_and_guards(tmp_path):
    from mxnet_tpu.data import load_data_state, save_data_state
    prefix = str(tmp_path / "ck")
    state = {"kind": "ImageRecordIter", "batches": 3,
             "source": {"epoch": 1, "pos": 12}}
    path = save_data_state(prefix, 2, state, nbatch=3)
    assert os.path.exists(path)
    assert load_data_state(prefix, 2) == state
    assert load_data_state(prefix, 1) is None
    # version guard
    with open(path) as f:
        env = json.load(f)
    env["version"] = 99
    with open(path, "w") as f:
        json.dump(env, f)
    assert load_data_state(prefix, 2) is None
    # params-pairing guard
    env["version"] = 1
    env["params"] = "other-0002.params"
    with open(path, "w") as f:
        json.dump(env, f)
    assert load_data_state(prefix, 2) is None
    # save(None) removes a stale envelope
    save_data_state(prefix, 2, state)
    save_data_state(prefix, 2, None)
    assert load_data_state(prefix, 2) is None


def test_module_checkpoint_carries_data_state(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_DATA_SEED", "31")
    from mxnet_tpu.test_utils import smoke_mlp
    rec, idx = str(tmp_path / "m.rec"), str(tmp_path / "m.idx")
    _write_rec(rec, idx, n=16)
    it = mx.io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                               data_shape=(3, 12, 12), batch_size=4,
                               shuffle=True, preprocess_threads=2)
    prefix = str(tmp_path / "ck")
    mod = mx.Module(smoke_mlp(num_hidden=8), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="acc",
            epoch_end_callback=mx.callback.do_checkpoint(
                prefix, data_iter=it))
    bundle = mx.Module.load_latest(prefix, context=mx.cpu())
    mod2, epoch = bundle
    assert epoch == 1
    assert bundle.data_state is not None
    assert bundle.data_state["source"]["eof"] is True
    # model-level loader returns it too, as the same bundle shape
    from mxnet_tpu.model import load_latest_checkpoint
    sym, args, auxs, ep = load_latest_checkpoint(prefix)
    assert ep == 1
    assert load_latest_checkpoint(prefix).data_state == bundle.data_state
    it.close()


# ---------------------------------------------------------------------------
# mid-epoch fit resume (the acceptance pin)
# ---------------------------------------------------------------------------
class _CrashAt(Exception):
    pass


def _run_fit(factory, prefix=None, crash=None, resume=None,
             begin_epoch=0, num_epoch=2, period=2):
    """One fit run over the record iterator; returns (stream_log,
    module).  ``crash=(epoch, nbatch)`` raises after that batch
    trained; ``prefix`` arms the mid-epoch batch checkpointer."""
    from mxnet_tpu.test_utils import smoke_mlp
    mx.random.seed(0)
    np.random.seed(0)
    it = factory()
    mod = resume[0] if resume else mx.Module(smoke_mlp(num_hidden=8),
                                             context=mx.cpu())
    log = []

    def logger(param):
        b = (param.locals or {})["data_batch"]
        log.append((param.epoch,
                    tuple(b.label[0].asnumpy().astype(int).tolist()),
                    hashlib.sha1(
                        b.data[0].asnumpy().tobytes()).hexdigest()[:12]))

    def crasher(param):
        if crash is not None and (param.epoch, param.nbatch) == crash:
            raise _CrashAt()

    cbs = [logger]
    if prefix:
        cbs.append(mx.callback.batch_checkpoint(mod, prefix,
                                                period=period))
    cbs.append(crasher)
    resume_kw = {}
    if resume:
        # the reference-faithful resume protocol: loaded params go in
        # through fit(arg_params=...) (init_params would otherwise
        # re-draw from the initializer)
        resume_kw = dict(arg_params=mod._arg_params,
                         aux_params=mod._aux_params,
                         resume_data_state=resume[1])
    try:
        mod.fit(it, num_epoch=num_epoch, begin_epoch=begin_epoch,
                optimizer="sgd", optimizer_params={"learning_rate": 0.05},
                eval_metric="acc", batch_end_callback=cbs, **resume_kw)
    except _CrashAt:
        pass
    finally:
        if hasattr(it, "close"):
            it.close()
    return log, mod


def _params_bytes(mod):
    args, auxs = mod.get_params()
    return {k: v.asnumpy().tobytes() for k, v in
            list(args.items()) + list(auxs.items())}


@pytest.mark.parametrize("num_parts", [1, 2])
def test_fit_mid_epoch_resume_byte_identical(tmp_path, monkeypatch,
                                             num_parts):
    """Kill a fit mid-epoch (after a mid-epoch checkpoint), resume from
    the latest envelope: the remaining (record-id, augmentation) batch
    stream is byte-identical to the same-seed uninterrupted run — zero
    replayed, zero skipped — and the final params byte-match.  Same pin
    under num_parts=2 sharding."""
    monkeypatch.setenv("MXNET_DATA_SEED", "23")
    rec, idx = str(tmp_path / "t.rec"), str(tmp_path / "t.idx")
    _write_rec(rec, idx, n=24)

    def factory():
        return mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 12, 12),
            batch_size=4, shuffle=True, rand_crop=True, rand_mirror=True,
            max_rotate_angle=10, preprocess_threads=2,
            part_index=num_parts - 1, num_parts=num_parts)

    clean_log, clean_mod = _run_fit(factory)
    per_epoch = len(clean_log) // 2

    prefix = str(tmp_path / ("ck%d" % num_parts))
    crash_log, _ = _run_fit(factory, prefix=prefix, crash=(1, 1))
    assert len(crash_log) == per_epoch + 2  # died inside epoch 1

    bundle = mx.Module.load_latest(prefix, load_optimizer_states=True,
                                   context=mx.cpu())
    assert bundle is not None and bundle.data_state is not None
    mod2, epoch = bundle
    frontier = epoch * per_epoch + bundle.data_state["batches"]
    resume_log, mod2 = _run_fit(factory, begin_epoch=epoch,
                                resume=(mod2, bundle.data_state))
    assert resume_log == clean_log[frontier:], \
        "resumed stream must be byte-identical to the clean suffix"
    assert crash_log[:frontier] + resume_log == clean_log
    assert _params_bytes(mod2) == _params_bytes(clean_mod)


def test_fit_epoch_boundary_resume(tmp_path, monkeypatch):
    """do_checkpoint's epoch-end envelope (an eof frontier) resumes
    into the next epoch's exact stream."""
    monkeypatch.setenv("MXNET_DATA_SEED", "29")
    rec, idx = str(tmp_path / "b.rec"), str(tmp_path / "b.idx")
    _write_rec(rec, idx, n=16)

    def factory():
        return mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 12, 12),
            batch_size=4, shuffle=True, preprocess_threads=2)

    clean_log, clean_mod = _run_fit(factory)
    per_epoch = len(clean_log) // 2

    # epoch-end checkpoint only
    from mxnet_tpu.test_utils import smoke_mlp
    mx.random.seed(0)
    np.random.seed(0)
    it = factory()
    prefix = str(tmp_path / "ck")
    mod = mx.Module(smoke_mlp(num_hidden=8), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, eval_metric="acc",
            epoch_end_callback=mx.callback.do_checkpoint(
                prefix, data_iter=it))
    it.close()

    bundle = mx.Module.load_latest(prefix, context=mx.cpu())
    mod2, epoch = bundle
    assert epoch == 1
    resume_log, mod2 = _run_fit(factory, begin_epoch=epoch,
                                resume=(mod2, bundle.data_state))
    assert resume_log == clean_log[per_epoch:]
    assert _params_bytes(mod2) == _params_bytes(clean_mod)


def test_kvstore_rank_autopartitions_train_data(tmp_path, monkeypatch):
    """The fit path wires kvstore rank/size into set_partition(auto)
    — and auto never overrides an explicit user partition."""
    monkeypatch.setenv("MXNET_DATA_SEED", "37")
    from mxnet_tpu.test_utils import smoke_mlp
    rec, idx = str(tmp_path / "kv.rec"), str(tmp_path / "kv.idx")
    _write_rec(rec, idx, n=16)

    class _FakeKV:
        rank, num_workers = 1, 2

    class _Probe(mx.Module):
        def init_optimizer(self, **kwargs):
            super().init_optimizer(**kwargs)
            self._kvstore = _FakeKV()   # fused path leaves it None

    it = mx.io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                               data_shape=(3, 12, 12), batch_size=4,
                               shuffle=True, preprocess_threads=2)
    mod = _Probe(smoke_mlp(num_hidden=8), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="acc")
    assert (it._dataset.part_index, it._dataset.num_parts) == (1, 2)
    it.close()

    # explicit partition wins
    it2 = mx.io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                                data_shape=(3, 12, 12), batch_size=4,
                                shuffle=True, preprocess_threads=2,
                                part_index=2, num_parts=3)
    mod2 = _Probe(smoke_mlp(num_hidden=8), context=mx.cpu())
    mod2.fit(it2, num_epoch=1, optimizer="sgd", eval_metric="acc")
    assert (it2._dataset.part_index, it2._dataset.num_parts) == (2, 3)
    it2.close()


# ---------------------------------------------------------------------------
# subprocess SIGKILL-mid-epoch (mirrors the PR-2 server-death test)
# ---------------------------------------------------------------------------
def test_sigkill_mid_epoch_resume_subprocess(tmp_path):
    """Launch a real training process with a seeded data.next kill; the
    relaunch resumes from the mid-epoch envelope.  Final params must
    byte-match the uninterrupted run and the resumed batch stream must
    be the clean stream's exact suffix."""
    rec, idx = str(tmp_path / "s.rec"), str(tmp_path / "s.idx")
    _write_rec(rec, idx, n=24)
    script = os.path.join(_REPO, "tests", "data_resume_train.py")
    base_env = dict(os.environ, JAX_PLATFORMS="cpu",
                    MXNET_DATA_SEED="41",
                    PYTHONPATH=_REPO + os.pathsep +
                    os.environ.get("PYTHONPATH", ""))

    def launch(prefix, out, log, fault=None):
        env = dict(base_env)
        env.pop("MXNET_FAULT_INJECT", None)
        if fault:
            env["MXNET_FAULT_INJECT"] = json.dumps(fault)
        return subprocess.run(
            [sys.executable, script, rec, idx, prefix, out, log],
            capture_output=True, text=True, env=env, timeout=300)

    # uninterrupted reference
    p = launch(str(tmp_path / "clean"), str(tmp_path / "clean.params"),
               str(tmp_path / "clean.log"))
    assert p.returncode == 0, (p.stdout[-800:], p.stderr[-800:])
    clean_log = open(str(tmp_path / "clean.log")).read().splitlines()
    assert len(clean_log) == 12    # 2 epochs x 6 batches

    # killed mid-epoch by the seeded data.next die rule
    prefix = str(tmp_path / "ck")
    log = str(tmp_path / "run.log")
    fault = {"seed": 1, "rules": [{"seam": "data.next", "nth": 12,
                                   "action": "die"}]}
    p1 = launch(prefix, str(tmp_path / "run.params"), log, fault=fault)
    assert p1.returncode == 137, (p1.returncode, p1.stderr[-800:])
    n_before = len(open(log).read().splitlines())
    assert 0 < n_before < 12, "must die mid-run"

    # the envelope names the resume frontier
    import glob as _glob
    dstates = sorted(_glob.glob(prefix + "-*.dstate"))
    assert dstates, "mid-epoch envelope must exist"
    with open(dstates[-1]) as f:
        env_ = json.load(f)
    st = env_["state"]
    frontier = env_["epoch"] * 6 + \
        (0 if (st.get("source") or {}).get("eof") else st["batches"])

    # relaunch without the fault plan: resumes and completes
    p2 = launch(prefix, str(tmp_path / "run.params"), log)
    assert p2.returncode == 0, (p2.stdout[-800:], p2.stderr[-800:])
    assert json.loads(p2.stdout.strip().splitlines()[-1])["resumed"]
    lines = open(log).read().splitlines()
    resumed = lines[n_before:]
    assert resumed == clean_log[frontier:], \
        "resumed stream must be the clean stream's exact suffix"

    # final params byte-match the uninterrupted run
    import numpy.lib.npyio  # noqa: F401  (npz loader)
    a = np.load(str(tmp_path / "clean.params") + ".npz"
                if os.path.exists(str(tmp_path / "clean.params")
                                  + ".npz")
                else str(tmp_path / "clean.params"))
    b = np.load(str(tmp_path / "run.params") + ".npz"
                if os.path.exists(str(tmp_path / "run.params") + ".npz")
                else str(tmp_path / "run.params"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert a[k].tobytes() == b[k].tobytes(), k
