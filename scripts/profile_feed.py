"""Stage-by-stage microbenchmark of the SPARK-mode data plane.

Times each hop a feed row takes (serialization, queue/ring IPC, batch
assembly, driver pipe ship) in isolation for the MNIST workload shape:
host counts of the box it runs on, no device rate.  Run on any host:

    python scripts/profile_feed.py
"""
import os, pickle, sys, time
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROWS = 60000
BATCH = 1024
CHUNK = 256
rng = np.random.default_rng(0)
images = (rng.random((ROWS, 784)) * 255).astype(np.float32)
labels = rng.integers(0, 10, (ROWS,), np.int64)
data = [(images[i], int(labels[i])) for i in range(ROWS)]

def report(name, secs, n_items):
    per_batch = secs / n_items * BATCH * 1000
    print(f"{name:45s} {n_items/secs:>12.0f} items/s  {per_batch:8.2f} ms/1024-batch")

# A. pickle a 256-row block of (ndarray, int) tuples (feeder -> ring)
blocks = [data[i:i+CHUNK] for i in range(0, 20480, CHUNK)]
t0 = time.perf_counter()
bl = [pickle.dumps(b, protocol=pickle.HIGHEST_PROTOCOL) for b in blocks]
t1 = time.perf_counter()
report("A pickle row-blocks (256 tuples)", t1-t0, 20480)

# A2. unpickle
t0 = time.perf_counter()
ub = [pickle.loads(b) for b in bl]
t1 = time.perf_counter()
report("A2 unpickle row-blocks", t1-t0, 20480)

# B. columnar pack: np.stack per block then pickle
t0 = time.perf_counter()
cb = []
for b in blocks:
    imgs = np.stack([r[0] for r in b])
    labs = np.asarray([r[1] for r in b], np.int64)
    cb.append(pickle.dumps((imgs, labs), protocol=pickle.HIGHEST_PROTOCOL))
t1 = time.perf_counter()
report("B columnar pack+pickle (stack+dumps)", t1-t0, 20480)

t0 = time.perf_counter()
ucb = [pickle.loads(b) for b in cb]
t1 = time.perf_counter()
report("B2 unpickle columnar blocks", t1-t0, 20480)

# C. consumer assembly: 1024 list-appends + np.stack (current next_batch+preprocess)
items = data[:BATCH*8]
t0 = time.perf_counter()
for s in range(8):
    out = []
    for it in items[s*BATCH:(s+1)*BATCH]:
        out.append(it)
    imgs = np.stack([r[0] for r in out]).astype(np.float32)
    labs = np.asarray([r[1] for r in out], np.int32)
t1 = time.perf_counter()
report("C per-item assembly + np.stack", t1-t0, BATCH*8)

# C2. columnar assembly: concat 4 blocks of (256,784)
colblocks = [(np.stack([r[0] for r in b]), np.asarray([r[1] for r in b])) for b in blocks[:32]]
t0 = time.perf_counter()
for s in range(8):
    bs = colblocks[s*4:(s+1)*4]
    imgs = np.concatenate([b[0] for b in bs])
    labs = np.concatenate([b[1] for b in bs])
t1 = time.perf_counter()
report("C2 columnar concat assembly", t1-t0, BATCH*8)

# D. manager-queue chunk round trip (proxy IPC per chunk token)
from tensorflowonspark_tpu import manager as manager_mod
from tensorflowonspark_tpu import marker
mgr = manager_mod.start(b"prof", ["input"])
q = mgr.get_queue("input")
t0 = time.perf_counter()
N = 40
for i in range(N):
    q.put(marker.Chunk(blocks[i % len(blocks)]), block=True)
for i in range(N):
    c = q.get(block=True)
    q.task_done()
t1 = time.perf_counter()
report("D manager-queue Chunk round trip", t1-t0, N*CHUNK)

# D2. queue with just a small token (ShmChunk path token cost)
t0 = time.perf_counter()
for i in range(200):
    q.put(marker.ShmChunk("x", CHUNK), block=True)
for i in range(200):
    q.get(block=True); q.task_done()
t1 = time.perf_counter()
report("D2 manager-queue token round trip", t1-t0, 200*CHUNK)
mgr.shutdown()

# E. shm ring put/get of pickled row-block vs columnar
from tensorflowonspark_tpu import shmring
if shmring.available():
    ring = shmring.get_ring("profring", create=True)
    t0 = time.perf_counter()
    for i in range(64):
        ring.put_bytes(bl[i % len(bl)], timeout_secs=10)
        ring.get_bytes(10)
    t1 = time.perf_counter()
    report("E shm ring rt (row-block bytes)", t1-t0, 64*CHUNK)
    t0 = time.perf_counter()
    for i in range(64):
        ring.put_bytes(cb[i % len(cb)], timeout_secs=10)
        ring.get_bytes(10)
    t1 = time.perf_counter()
    report("E2 shm ring rt (columnar bytes)", t1-t0, 64*CHUNK)

    # E3. colv1 frame: vectored gather-write + two-phase peek/decode/consume
    # (same payload as E2 but no pickle and no pop-side staging buffer)
    from tensorflowonspark_tpu import wire
    colchunks = [marker.ColChunk(
        (np.stack([r[0] for r in b]),
         np.asarray([r[1] for r in b], np.int64)), CHUNK, True)
        for b in blocks[:64]]
    t0 = time.perf_counter()
    for i in range(64):
        ring.put_vectored(wire.encode_chunk(colchunks[i]), timeout_secs=10)
        ck = wire.decode_chunk(ring.peek(10), copy=True)
        ring.consume()
    t1 = time.perf_counter()
    report("E3 shm ring rt (colv1 writev/peek)", t1-t0, 64*CHUNK)

    # G/G2. the acceptance comparison — full hop, rows in to batch columns
    # out (pack -> write -> read -> assemble), pickled vs framed
    t0 = time.perf_counter()
    for i in range(64):
        ck = marker.pack_columnar(blocks[i % len(blocks)])
        ring.put_bytes(pickle.dumps(ck, protocol=pickle.HIGHEST_PROTOCOL),
                       timeout_secs=10)
        out = pickle.loads(ring.get_bytes(10))
        imgs, labs = out.columns
    pickled_secs = time.perf_counter() - t0
    report("G pickled full hop (pack+dumps+ring+loads)", pickled_secs,
           64*CHUNK)
    t0 = time.perf_counter()
    for i in range(64):
        ck = marker.pack_columnar(blocks[i % len(blocks)])
        ring.put_vectored(wire.encode_chunk(ck), timeout_secs=10)
        out = wire.decode_chunk(ring.peek(10), copy=True)
        ring.consume()
        imgs, labs = out.columns
    framed_secs = time.perf_counter() - t0
    report("G2 framed full hop (pack+writev+decode)", framed_secs, 64*CHUNK)
    print(f"   framed vs pickled full ring hop: "
          f"{pickled_secs/framed_secs:.2f}x")

    shmring.unlink("profring")
else:
    print("shmring unavailable")

# H. disaggregated data service: local FileFeed vs ServiceFeed with 1 and 2
# feed workers on localhost (docs/DATA_SERVICE.md) — same synthetic MNIST
# row shape, identical reader everywhere, so the deltas are transport +
# worker-count scaling, not reader differences.
from tensorflowonspark_tpu import data as data_mod
from tensorflowonspark_tpu import dataservice

H_SPLITS, H_SPLIT_ROWS = 16, 1024

def synth_reader(path):
    """Row reader keyed on a synthetic split path (no disk: the leg measures
    the feed planes, not the filesystem)."""
    base = int(path.rsplit("-", 1)[1]) * H_SPLIT_ROWS
    for i in range(H_SPLIT_ROWS):
        j = (base + i) % ROWS
        yield (images[j], int(labels[j]))

h_paths = ["synth-{}".format(i) for i in range(H_SPLITS)]

def drain_columnar(feed):
    t0 = time.perf_counter()
    n = 0
    while not feed.should_stop():
        _, cnt = feed.next_batch_arrays(BATCH)
        n += cnt
    return time.perf_counter() - t0, n

ff = data_mod.FileFeed(h_paths, row_reader=synth_reader, reader_threads=2,
                       shard=False)
h_secs, h_n = drain_columnar(ff)
report("H local FileFeed drain", h_secs, h_n)

for n_workers in (1, 2):
    disp = dataservice.DispatcherServer(heartbeat_interval=1.0,
                                        host="127.0.0.1")
    addr = disp.start()
    ws = [dataservice.FeedWorker(addr, row_reader=synth_reader,
                                 worker_id="prof{}-{}".format(n_workers, i))
          .start() for i in range(n_workers)]
    sf = dataservice.ServiceFeed(addr, h_paths,
                                 job_name="prof-{}".format(n_workers),
                                 mode=dataservice.SHARD_DYNAMIC, prefetch=4,
                                 timeout=120.0)
    h_secs, h_n = drain_columnar(sf)
    report("H%d ServiceFeed (%d worker%s, colv1/TCP)"
           % (n_workers + 1, n_workers, "s" if n_workers > 1 else ""),
           h_secs, h_n)
    # negotiated wire compression on the links (1.0 = every column stayed
    # raw — the pay-off sampler declined, e.g. random float mantissas)
    h_snap = sf.counters_snapshot()
    print("   wire_compress_ratio: {}  formats: {}".format(
        h_snap.get("wire_compress_ratio_max", 1.0), dict(sf.wire_formats)))
    sf.terminate()
    for w in ws:
        w.stop()
    disp.stop()

# F. driver pipe ship of a 7500-row partition (multiprocessing Pipe)
import multiprocessing as mp
ctx = mp.get_context("spawn")
a, b = ctx.Pipe()
part = data[:7500]
import threading
def rx():
    for _ in range(4):
        b.recv()
t = threading.Thread(target=rx); t.start()
t0 = time.perf_counter()
for _ in range(4):
    a.send((0, b"fn", part))
t.join()
t1 = time.perf_counter()
report("F driver pipe ship (7500-row part)", t1-t0, 7500*4)

print("\nper-1024-batch budget at 310 ms/step: where does it go?")
