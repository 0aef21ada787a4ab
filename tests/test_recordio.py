"""RecordIO-equivalent data format: native C++ chunk/scanner round-trips,
CRC corruption detection, compression, reader-pipeline + DeviceLoader
integration, and a train-from-file end-to-end run.

Reference parity: paddle/fluid/recordio/ (chunk_test.cc, scanner),
recordio_writer.py, operators/reader/create_recordio_file_reader_op.cc."""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu as fluid
from paddle_tpu import recordio


def test_bytes_roundtrip_multiple_chunks(tmp_path):
    path = str(tmp_path / "r.rio")
    records = [os.urandom(np.random.RandomState(i).randint(1, 4000))
               for i in range(200)]
    with recordio.Writer(path, max_chunk_bytes=8192) as w:
        for r in records:
            w.write(r)
    got = list(recordio.Scanner(path))
    assert got == records
    # multiple chunks were actually written (8KB cap, ~400KB of data)
    assert os.path.getsize(path) > 8192


def test_compression_none_vs_deflate(tmp_path):
    comp = str(tmp_path / "c.rio")
    raw = str(tmp_path / "n.rio")
    rec = (b"abc" * 1000,)
    data = [rec[0]] * 50
    for path, compressor in ((comp, recordio.COMPRESSOR_DEFLATE),
                             (raw, recordio.COMPRESSOR_NONE)):
        with recordio.Writer(path, compressor=compressor) as w:
            for r in data:
                w.write(r)
    assert list(recordio.Scanner(comp)) == data
    assert list(recordio.Scanner(raw)) == data
    # highly repetitive payload must compress well
    assert os.path.getsize(comp) < os.path.getsize(raw) / 5


def test_crc_detects_corruption(tmp_path):
    path = str(tmp_path / "x.rio")
    with recordio.Writer(path) as w:
        w.write(b"hello world" * 100)
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0xFF                     # flip a payload byte
    open(path, "wb").write(bytes(blob))
    with pytest.raises(IOError):
        list(recordio.Scanner(path))


def test_truncated_file_errors(tmp_path):
    path = str(tmp_path / "t.rio")
    with recordio.Writer(path) as w:
        w.write(b"x" * 500)
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) // 2])
    with pytest.raises(IOError):
        list(recordio.Scanner(path))


def test_sample_codec_numpy_and_scalars():
    sample = (np.arange(6, dtype=np.float32).reshape(2, 3),
              np.array([1, 2, 3], np.int64), 7, 2.5)
    back = recordio.decode_sample(recordio.encode_sample(sample))
    np.testing.assert_array_equal(back[0], sample[0])
    np.testing.assert_array_equal(back[1], sample[1])
    assert back[2] == 7 and abs(back[3] - 2.5) < 1e-12
    assert isinstance(back[2], int)


def test_convert_reader_and_read_back(tmp_path):
    path = str(tmp_path / "ds.rio")
    rng = np.random.RandomState(0)
    xs = rng.rand(37, 4).astype(np.float32)
    ys = rng.randint(0, 3, 37).astype(np.int64)

    def creator():
        for i in range(37):
            yield xs[i], int(ys[i])

    n = recordio.convert_reader_to_recordio_file(path, creator)
    assert n == 37
    back = list(recordio.reader(path)())
    assert len(back) == 37
    np.testing.assert_allclose(back[5][0], xs[5])
    assert back[5][1] == ys[5]

    # composes with the reader-decorator pipeline
    batches = list(paddle.batch(
        paddle.reader.shuffle(recordio.reader(path), 37),
        batch_size=10)())
    assert sum(len(b) for b in batches) == 37


def test_train_from_recordio_file(tmp_path):
    # the data-plane integration a review asked for: file -> reader ->
    # DataFeeder -> compiled step, loss converges
    path = str(tmp_path / "train.rio")
    rng = np.random.RandomState(0)
    w_true = rng.rand(4, 1).astype(np.float32)

    def creator():
        for _ in range(64):
            x = rng.rand(4).astype(np.float32)
            yield x, float((x @ w_true).item() + 0.5)

    recordio.convert_reader_to_recordio_file(path, creator)

    x = fluid.layers.data("x", [4])
    y = fluid.layers.data("y", [1])
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feeder = fluid.DataFeeder([x, y], fluid.CPUPlace())

    first = last = None
    for epoch in range(30):
        for batch in paddle.batch(recordio.reader(path), batch_size=16)():
            lv, = exe.run(feed=feeder.feed(batch), fetch_list=[loss])
            if first is None:
                first = float(lv)
            last = float(lv)
    assert last < first * 0.05, (first, last)


def test_device_loader_prefetch_from_recordio(tmp_path):
    from paddle_tpu.reader.device_loader import DeviceLoader
    path = str(tmp_path / "dl.rio")

    def creator():
        for i in range(20):
            yield (np.full((2, 2), i, np.float32),)

    recordio.convert_reader_to_recordio_file(path, creator)
    feed_dicts = ({"x": np.stack([s[0] for s in b])}
                  for b in paddle.batch(recordio.reader(path),
                                        batch_size=4)())
    loader = DeviceLoader(feed_dicts, capacity=2)
    seen = list(loader)
    assert len(seen) == 5
    import jax
    assert isinstance(seen[0]["x"], jax.Array)
    np.testing.assert_allclose(np.asarray(seen[-1]["x"])[-1],
                               np.full((2, 2), 19.0))


def test_scanner_safe_after_exhaustion(tmp_path):
    path = str(tmp_path / "s.rio")
    with recordio.Writer(path) as w:
        w.write(b"one")
    s = recordio.Scanner(path)
    assert list(s) == [b"one"]
    # re-iterating an exhausted scanner must raise StopIteration, not
    # touch the freed native handle
    assert list(s) == []
    with pytest.raises(StopIteration):
        next(s)


def test_corrupt_header_lengths_raise_ioerror(tmp_path):
    # corruption in the LENGTH bytes of the header (not payload) must be
    # an IOError, not a multi-GB allocation/abort
    path = str(tmp_path / "h.rio")
    with recordio.Writer(path) as w:
        w.write(b"payload" * 50)
    blob = bytearray(open(path, "rb").read())
    blob[12] = 0xFF   # raw_len high byte
    blob[20] = 0xFF   # comp_len high byte
    open(path, "wb").write(bytes(blob))
    with pytest.raises(IOError):
        list(recordio.Scanner(path))


def test_reader_early_abandon_does_not_leak_fds(tmp_path):
    import gc
    path = str(tmp_path / "fd.rio")
    recordio.convert_reader_to_recordio_file(
        path, lambda: ((np.zeros(2, np.float32),) for _ in range(50)))
    n0 = len(os.listdir("/proc/self/fd"))
    for _ in range(20):
        it = recordio.reader(path)()
        next(it)          # read one record, abandon the pass
        it.close()        # generator close triggers the finally
    gc.collect()
    assert len(os.listdir("/proc/self/fd")) <= n0 + 1


def test_understated_record_count_detected(tmp_path):
    # num_records is outside the payload CRC; an understated count must
    # raise instead of silently dropping trailing records
    path = str(tmp_path / "cnt.rio")
    with recordio.Writer(path, compressor=recordio.COMPRESSOR_NONE) as w:
        for i in range(5):
            w.write(b"rec%d" % i)
    blob = bytearray(open(path, "rb").read())
    assert blob[6] == 5            # num_records low byte
    blob[6] = 3
    open(path, "wb").write(bytes(blob))
    s = recordio.Scanner(path)
    with pytest.raises(IOError):
        list(s)
