"""Measure ImageNet JPEG decode throughput.

Answers: at what rate can this host turn JPEG TFRecord shards into uint8
224x224x3 training rows, per core and scaled across cores?  The 50%-MFU
ResNet-50 bar on one v5e chip consumes ~8k img/s; the reference rode
tf.data's C++ decode pool (``imagenet_preprocessing.py:87-175``).

Legs (each timed on synthetic shards staged in a temp dir):

- ``engine``: raw decode-engine rates on one core — PIL full decode vs
  cv2 full vs cv2 reduced-resolution, on naturalistic and noise JPEGs
  (the bounds of real photo entropy).
- ``pipeline1``: the actual ``imagenet_reader`` end-to-end on one core
  (TFRecord framing + Example parse + decode + crop + resize), train and
  eval paths.
- ``pool N``: ``data.ProcessPoolFeed`` with N worker processes draining
  the same reader — the scaling story (on a 1-core dev box N>1 shows
  IPC overhead only; on a pod host it scales with cores).

Prints one JSON line; use --rows/--image_px to resize the workload.
"""

import argparse
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "resnet"))


def _natural_jpeg(w, h, seed, quality=90):
    from PIL import Image

    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    base = np.stack([(xx + yy) % 256, xx * 255 / max(w, 1),
                     yy * 255 / max(h, 1)], -1)
    noise = rng.normal(0, 12, (h, w, 3))
    arr = np.clip(base + noise, 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def _noise_jpeg(w, h, seed, quality=90):
    from PIL import Image

    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, (h, w, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def _rate(fn, secs=2.0):
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < secs:
        fn()
        n += 1
    return round(n / (time.perf_counter() - t0), 1)


def leg_engine(px):
    import imagenet_input
    from PIL import Image

    out = {}
    for name, data in (("natural", _natural_jpeg(500, 375, 0)),
                       ("noise", _noise_jpeg(500, 375, 0))):
        def pil_full():
            img = Image.open(io.BytesIO(data))
            img.convert("RGB").load()

        out[name] = {
            "jpeg_kb": round(len(data) / 1024, 1),
            "pil_full_per_sec": _rate(pil_full),
            "cv2_full_per_sec": _rate(
                lambda: imagenet_input._decode_rgb(data, 1)),
            "cv2_reduced2_per_sec": _rate(
                lambda: imagenet_input._decode_rgb(data, 2)),
        }
    return out


def _stage_shards(tmp, rows, px):
    from tensorflowonspark_tpu import example_proto, tfrecord

    shards = []
    per = max(1, rows // 8)
    i = 0
    for s in range(8):
        path = os.path.join(tmp, "train-%05d-of-00008" % s)
        with tfrecord.TFRecordWriter(path) as w:
            for _ in range(per):
                data = _natural_jpeg(500, 375, i)
                w.write(example_proto.encode_example({
                    "image/encoded": ("bytes", [data]),
                    "image/class/label": ("int64", [1 + (i % 1000)])}))
                i += 1
        shards.append(path)
    return shards, i


def leg_pipeline1(shards, total, px):
    import imagenet_input

    out = {}
    for mode, train in (("train", True), ("eval", False)):
        reader = imagenet_input.imagenet_reader(train=train, image_size=px)
        t0 = time.perf_counter()
        n = 0
        for path in shards:
            for _ in reader(path):
                n += 1
        out[mode + "_rows_per_sec"] = round(n / (time.perf_counter() - t0), 1)
    return out


def leg_pool(shards, total, px, procs):
    import imagenet_input

    from tensorflowonspark_tpu import data as data_mod

    feed = data_mod.ProcessPoolFeed(
        shards, row_reader=imagenet_input.imagenet_reader(
            train=True, image_size=px),
        num_procs=procs, shard=False)
    t_start = time.perf_counter()
    t0 = None
    startup = None
    n = 0
    while not feed.should_stop():
        _, count = feed.next_batch_arrays(64)
        if count == 0:
            break
        if t0 is None:
            # steady-state rate: spawn + interpreter imports (~3 s/worker)
            # are a one-time cost, reported separately
            t0 = time.perf_counter()
            startup = round(t0 - t_start, 2)
            continue  # first batch is warmup
        n += count
    rate = round(n / (time.perf_counter() - t0), 1) if n else 0.0
    feed.terminate()
    return {"procs": procs, "rows_per_sec": rate, "rows": n,
            "startup_secs": startup}


def leg_predecoded(shards, px, store_px):
    """Read rate of the decode-free path: pre-decode the staged JPEG shards
    once (offline cost, reported), then drain ``predecoded_reader`` through
    a FileFeed on ONE core — the hot-path rate a training worker would see.
    This is the extrapolation-free answer to the 8k img/s bar on hosts
    whose cores can't sustain JPEG decode."""
    import imagenet_input

    from tensorflowonspark_tpu import data as data_mod

    # inside the caller's staging dir so the TemporaryDirectory cleanup
    # sweeps the ~200 KB/row raw shards too
    pre_dir = os.path.join(os.path.dirname(shards[0]), "predecoded")
    t0 = time.perf_counter()
    raw_shards = imagenet_input.predecode_shards(
        shards, pre_dir, store_px=store_px)
    predecode_secs = time.perf_counter() - t0

    def drain(device_crop):
        feed = data_mod.FileFeed(
            raw_shards, row_reader=imagenet_input.predecoded_reader(
                train=True, image_size=px, store_px=store_px,
                device_crop=device_crop),
            num_epochs=3)
        n = 0
        t0 = time.perf_counter()
        while not feed.should_stop():
            _, count = feed.next_batch_arrays(64)
            if count == 0:
                break
            n += count
        rate = round(n / (time.perf_counter() - t0), 1)
        feed.terminate()
        return rate, n

    host_rate, n = drain(False)
    dev_rate, _ = drain(True)
    return {"rows_per_sec_1core": host_rate,
            "rows_per_sec_1core_device_crop": dev_rate, "rows": n,
            "store_px": store_px,
            "offline_predecode_secs": round(predecode_secs, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--image_px", type=int, default=224)
    ap.add_argument("--store_px", type=int, default=256)
    # scaling curve to 16 procs by default; on a
    # 1-core host the tail of the curve measures IPC overhead only --
    # rows_per_sec_per_core is the honest cross-host number
    ap.add_argument("--pool_sizes", default="1,2,4,8,16")
    args = ap.parse_args()

    ncpu = os.cpu_count()
    out = {"metric": "imagenet_decode_rows_per_sec", "host_cores": ncpu}
    out["engine"] = leg_engine(args.image_px)
    with tempfile.TemporaryDirectory() as tmp:
        shards, total = _stage_shards(tmp, args.rows, args.image_px)
        out["pipeline_1core"] = leg_pipeline1(shards, total, args.image_px)
        out["pool"] = [leg_pool(shards, total, args.image_px, int(p))
                       for p in args.pool_sizes.split(",")]
        for p in out["pool"]:
            p["rows_per_sec_per_core"] = round(
                p["rows_per_sec"] / min(p["procs"], ncpu), 1)
        out["predecoded"] = leg_predecoded(shards, args.image_px,
                                           args.store_px)
    best = max(p["rows_per_sec"] for p in out["pool"])
    out["value"] = max(best, out["pipeline_1core"]["train_rows_per_sec"],
                       out["predecoded"]["rows_per_sec_1core"])
    # the consumption bar: ~8k img/s feeds one v5e chip at 50% MFU
    out["rate_needed_50mfu_1chip"] = 8000
    out["extrapolated_host_rate"] = round(
        out["pipeline_1core"]["train_rows_per_sec"] * max(ncpu - 4, 1), 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
