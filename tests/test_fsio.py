"""Remote-filesystem data path (fsio): the HDFS-training equivalence.

The reference trains from HDFS (``dfutil.py:44-81`` TFRecord loads,
``examples/mnist/keras/mnist_tf.py:23-27`` tf.data file reads); the TPU-first
deployment reads ``gs://`` shards on a v5e pod.  These tests drive the whole
FILES data path — TFRecord write, shard listing, FileFeed streaming, an
actual training loop — against fsspec's ``memory://`` store so no byte ever
touches the local filesystem.
"""

import uuid

import numpy as np
import pytest

from tensorflowonspark_tpu import data as data_mod
from tensorflowonspark_tpu import dfutil, fsio, tfrecord


@pytest.fixture
def memdir():
    # unique per test: the memory filesystem is process-global.  Triple
    # slash = fsspec's canonical form (paths are rooted at "/"), so string
    # comparisons against glob output round-trip exactly.
    return "memory:///tfos-test-{}".format(uuid.uuid4().hex)


class TestPrimitives:
    def test_scheme_detection(self):
        assert fsio.is_remote("gs://bucket/dir")
        assert fsio.is_remote("hdfs://nn:9000/user/x")
        assert fsio.is_remote("memory://x")
        assert not fsio.is_remote("/abs/local/path")
        assert not fsio.is_remote("relative/path")
        assert not fsio.is_remote("file:///abs/path")
        assert not fsio.is_remote("dir/odd://name")  # scheme can't contain /

    def test_file_scheme_strips_to_local(self):
        assert fsio.strip_file_scheme("file:///a/b") == "/a/b"
        assert fsio.strip_file_scheme("file:/a/b") == "/a/b"
        assert fsio.strip_file_scheme("/a/b") == "/a/b"

    def test_join_preserves_scheme(self):
        assert fsio.join("gs://b/base", "x", "y") == "gs://b/base/x/y"
        assert fsio.join("gs://b/base/", "x") == "gs://b/base/x"

    def test_open_glob_exists_roundtrip(self, memdir):
        path = fsio.join(memdir, "sub", "a.bin")
        fsio.makedirs(fsio.join(memdir, "sub"))
        with fsio.open_file(path, "wb") as f:
            f.write(b"payload")
        assert fsio.exists(path)
        assert not fsio.exists(fsio.join(memdir, "sub", "missing"))
        with fsio.open_file(path, "rb") as f:
            assert f.read() == b"payload"
        assert fsio.glob(fsio.join(memdir, "sub", "*.bin")) == [path]
        assert fsio.isdir(fsio.join(memdir, "sub"))

    def test_local_paths_use_stdlib(self, tmp_path):
        p = tmp_path / "x.txt"
        with fsio.open_file(str(p), "w") as f:
            f.write("hi")
        assert fsio.glob(str(tmp_path / "*.txt")) == [str(p)]
        assert fsio.isdir(str(tmp_path))


class TestTFRecordRemote:
    def test_writer_reader_roundtrip(self, memdir):
        path = fsio.join(memdir, "recs.tfrecord")
        records = [bytes([i]) * (i + 1) for i in range(10)]
        with tfrecord.TFRecordWriter(path) as w:
            for r in records:
                w.write(r)
        assert list(tfrecord.tfrecord_iterator(path)) == records

    def test_corruption_detected_remote(self, memdir):
        path = fsio.join(memdir, "bad.tfrecord")
        with tfrecord.TFRecordWriter(path) as w:
            w.write(b"hello world")
        with fsio.open_file(path, "rb") as f:
            blob = bytearray(f.read())
        blob[14] ^= 0xFF  # flip a payload byte
        with fsio.open_file(path, "wb") as f:
            f.write(bytes(blob))
        with pytest.raises(IOError):
            list(tfrecord.tfrecord_iterator(path))

    def test_dfutil_shards_roundtrip(self, memdir):
        rows = dfutil.Rows(
            [{"id": i, "val": float(i) * 0.5} for i in range(50)],
            schema={"id": "int64", "val": "float32"})
        out = fsio.join(memdir, "tfr")
        paths = dfutil.save_as_tfrecords(rows, out, num_shards=3)
        assert all(p.startswith("memory:///") for p in paths)
        back = dfutil.load_tfrecords(out)
        assert sorted(int(r["id"]) for r in back) == list(range(50))


class TestTrainFromRemoteStore:
    @pytest.fixture
    def mnist_shards(self, memdir):
        rng = np.random.default_rng(0)
        rows = dfutil.Rows(
            [{"image": rng.integers(0, 256, 784).tolist(),
              "label": int(rng.integers(0, 10))} for _ in range(256)],
            schema={"image": "array<int64>", "label": "int64"})
        out = fsio.join(memdir, "mnist")
        dfutil.save_as_tfrecords(rows, out, num_shards=4)
        return out

    def test_list_shards_and_filefeed_stream(self, mnist_shards):
        files = data_mod.list_shards(mnist_shards)
        assert len(files) == 4 and all(
            f.startswith("memory:///") for f in files)
        feed = data_mod.FileFeed(files, shard=False)
        seen = 0
        while not feed.should_stop():
            arrays, count = feed.next_batch_arrays(64)
            if count == 0:
                break
            assert set(arrays.keys()) == {"image", "label"}
            seen += count
        assert seen == 256

    def test_mnist_trains_from_memory_store(self, mnist_shards):
        """End-to-end: the mnist model trains on shards living in a
        non-local store."""
        import jax
        import jax.numpy as jnp
        import optax

        from tensorflowonspark_tpu import train as train_mod
        from tensorflowonspark_tpu.models import mnist as mnist_mod
        from tensorflowonspark_tpu.parallel import build_mesh
        from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

        mesh = build_mesh()
        model = mnist_mod.build_mnist()
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 28, 28, 1)))["params"]
        trainer = train_mod.Trainer(
            mnist_mod.loss_fn(model), params, optax.sgd(0.01), mesh=mesh,
            batch_size=64)

        def transform(arrays):
            return {"image": np.asarray(arrays["image"], np.float32)
                    .reshape(-1, 28, 28, 1) / 255.0,
                    "label": np.asarray(arrays["label"], np.int32)}

        feed = data_mod.FileFeed(
            data_mod.list_shards(mnist_shards), shard=False, num_epochs=2)
        sharded = ShardedFeed(feed, mesh, 64, transform=transform)
        trainer.fit_feed(sharded)
        assert int(trainer.state.step) == 8  # 256 rows x 2 epochs / 64
