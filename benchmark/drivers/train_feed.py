"""Driver of the training cells: one executor of ``cluster.run`` holds the
chip and trains the configuration's model from the cell's feed ->
``ShardedFeed`` -> ``Trainer.fit_feed``.

The traffic mix names only the transport (``input_mode``: ``spark`` = this
process's partitions -> feeders -> shm ring -> ``ctx.get_data_feed``;
``files`` = shards written in set-up -> ``data.FileFeed``), its sizes, and
optionally the ``mesh`` layout.  What a row is belongs to the configuration:
its adapter (``benchmark/adapters/<adapter>.py``) gives ``row_dtype``,
``make_row`` (row ``index`` from the seed, by the benchmark's generator) and
``to_batch``, and either transport carries any adapter's rows.

``run`` is the parent's side (it never imports jax); ``main_fun`` runs in the
executor.  One ``fit_feed`` call carries everything: the first
``check_steps`` steps (followed afterwards by the plain reference), the
warm-up, the measured window, which an ``on_steps`` hook opens and closes at
device-synced instants, and in a traced run some profiled steps after it.
"""

import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class _WindowClosed(Exception):
    """Raised by the hook to leave ``fit_feed`` when the window's time is
    up: ``fit_feed`` stops by step count only (PERF.md, open questions)."""


# ---------------------------------------------------------------------------
# In the executor
# ---------------------------------------------------------------------------

class _Rows(object):
    """What the infeed's host thread saw: every row's index (and label or
    token checksum) in arrival order, and the whole rows of the first
    ``keep`` batches."""

    def __init__(self, keep):
        self.keep = keep
        self.first = []
        self.index = []
        self.tag = []

    def note(self, batch, index, tag):
        import numpy as np

        if len(self.first) < self.keep:
            self.first.append({k: np.array(v) for k, v in batch.items()})
        self.index.append(np.array(index, np.int64))
        self.tag.append(np.array(tag, np.int64))


def _field_names(adapter, cfg):
    return list(adapter.row_dtype(cfg).names)


def _transform(adapter, names, rows):
    """The infeed's host transform: columns as the transport hands them (a
    list in the rows' field order from the ring, a dict from ``FileFeed``)
    -> the adapter's batch; notes what arrived."""
    from benchmark import harness

    def transform(cols):
        with harness.span("transform"):
            if not isinstance(cols, dict):
                cols = dict(zip(names, cols))
            batch, tag = adapter.to_batch(cols)
            rows.note(batch, cols["index"], tag)
            return batch

    return transform


def _shard_reader(dtype):
    """FileFeed row reader of the benchmark's raw shards (whole rows of the
    adapter's ``row_dtype``)."""
    import numpy as np

    def reader(path):
        for row in np.fromfile(path, dtype):
            yield {k: (row[k].item() if row[k].ndim == 0 else row[k])
                   for k in dtype.names}

    return reader


def _write_shards(adapter, cfg, seed, traffic, directory):
    """The FILES transport's input: ``shards`` raw files of whole rows."""
    import numpy as np

    os.makedirs(directory, exist_ok=True)
    dtype = adapter.row_dtype(cfg)
    per = traffic["rows"] // traffic["shards"]
    paths = []
    for s in range(traffic["shards"]):
        table = np.empty(per, dtype)
        for j in range(per):
            table[j] = adapter.make_row(cfg, seed, s * per + j)
        path = os.path.join(directory, "rows-%05d.bin" % s)
        table.tofile(path)
        paths.append(path)
    return paths


def _remake(adapter, cfg, seed, index):
    """(batch, tags) of the rows ``index``, made again from the seed."""
    import numpy as np

    names = _field_names(adapter, cfg)
    made = [adapter.make_row(cfg, seed, int(i)) for i in index]
    return adapter.to_batch({k: np.stack([np.asarray(r[j]) for r in made])
                             for j, k in enumerate(names)})


def _flat(tree, names):
    """flax tree -> {reference leaf name: host array}."""
    import jax
    from flax import traverse_util

    flat = traverse_util.flatten_dict(jax.device_get(tree), sep="/")
    return {names[k]: v for k, v in flat.items()}


def _conservation(rows, steps_total, batch, traffic, remake):
    """Exact checks on what arrived: enough rows for the steps taken; in
    every whole pass over a partition (SPARK) each index once, or every
    reader's rows read epoch after epoch (FILES, up to the rows the shuffle
    holds); every tag
    (label, token checksum) what the seed generates for that index."""
    import numpy as np

    index = np.concatenate(rows.index)
    tag = np.concatenate(rows.tag)
    problems = []
    if len(index) < steps_total * batch:
        problems.append("{} rows arrived for {} steps of {}".format(
            len(index), steps_total, batch))
    n = traffic["rows"]
    if index.min() < 0 or index.max() >= n:
        problems.append("row index out of the table")
    if traffic["input_mode"] == "spark":
        per = n // traffic["partitions"]
        whole = len(index) // per * per
        passes = np.sort(index[:whole].reshape(-1, per), axis=1)
        ok = (passes - passes[:, :1] == np.arange(per)).all() and (
            passes[:, 0] % per == 0).all()
        if not ok:
            problems.append("a pass over a partition did not hold each of "
                            "its rows once")
    else:
        # each reader thread owns shards[t::threads] and emits them whole,
        # epoch after epoch; between a reader and the transform sit the
        # shuffle's reservoir and the rows not yet batched (under ``held``
        # rows).  So, exactly: no row arrived more often than its reader's
        # emitted epochs, and the arrivals that whole epochs still owe are
        # rows in flight
        counts = np.bincount(index, minlength=n)
        per = n // traffic["shards"]
        threads = min(traffic["reader_threads"], traffic["shards"])
        reader = (np.arange(n) // per) % threads
        held = traffic["shuffle_buffer"] + 4 * 256 + batch
        for t in range(threads):
            mine = counts[reader == t]
            arrived = int(mine.sum())
            most = -(-(arrived + held) // len(mine))
            owed = int(np.maximum(arrived // len(mine) - mine, 0).sum())
            if mine.max() > most or owed > held:
                problems.append(
                    "reader {}'s rows were not read epoch after epoch: {} "
                    "arrived of {} rows, rows by times read {}".format(
                        t, arrived, len(mine), np.bincount(mine).tolist()))
    sample = np.unique(index[:2048])
    expected = dict(zip(sample.tolist(), remake(sample)[1].tolist()))
    if any(expected[int(i)] != int(t) for i, t in zip(index, tag)
           if int(i) in expected):
        problems.append("a row's tag (label, token checksum) is not the "
                        "seed's")
    return problems


def _reference_batches(rows, remake, steps):
    """The first ``steps`` batches made again from the seed by index, and
    whether they equal what arrived."""
    import numpy as np

    batches, same = [], True
    for got, index in zip(rows.first[:steps], rows.index[:steps]):
        batch = remake(index)[0]
        same = same and all(np.array_equal(batch[k], got[k]) for k in batch)
        batches.append(batch)
    return batches, same


def main_fun(args, ctx):
    sys.path.insert(0, ROOT)
    from benchmark import harness

    with harness.reporting(args.result_path) as report:
        _train(args, ctx, report)


def _train(args, ctx, report):
    import gc

    import numpy as np

    from benchmark import correctness, flops, harness

    cfg, traffic, seed = args.config, args.traffic, args.seed
    watch = harness.CompileWatch()
    device = report["device"] = harness.open_device(args.chips)

    import jax

    from tensorflowonspark_tpu import shmring
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import infeed, mesh as mesh_mod

    ctx.initialize_distributed()
    mesh = mesh_mod.build_mesh(traffic.get("mesh"))
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    reference = importlib.import_module(
        "benchmark.references." + cfg["reference"])
    batch = cfg["batch_size"]
    flops_example = flops.train_flops_per_example(cfg)
    built = adapter.build(cfg, seed, mesh)
    names = built["names"]
    trainer = train_mod.Trainer(
        built["loss"], built["params"], built["optimizer"], mesh=mesh,
        extra_state=built["extra"], compute_dtype=built["compute_dtype"],
        batch_size=batch, log_steps=1,
        param_sharding=built.get("param_sharding"),
        # the benchmark's own count from shapes; also spares the second
        # compile that the cost analysis of an un-stated count would make
        step_flops_override=flops_example * batch / len(jax.devices()))
    first_gradient = built["first_gradient"]
    extra_names = built.get("extra_names")
    start_params = _flat(trainer.state.params, names)
    start_extra = (_flat(trainer.state.extra, extra_names)
                   if extra_names else None)
    del built

    check_steps, warm_steps = traffic["check_steps"], traffic["warm_steps"]
    rows = _Rows(check_steps)
    if traffic["input_mode"] == "spark":
        feed = ctx.get_data_feed(train_mode=True)
    else:
        from tensorflowonspark_tpu import data as data_mod

        feed = data_mod.FileFeed(
            sorted(args.shards),
            row_reader=_shard_reader(adapter.row_dtype(cfg)),
            shuffle_buffer=traffic["shuffle_buffer"],
            num_epochs=traffic["epochs"], seed=seed % (2 ** 31),
            reader_threads=traffic["reader_threads"])
    sharded = infeed.ShardedFeed(
        feed, mesh, batch,
        transform=_transform(adapter, _field_names(adapter, cfg), rows))

    def counters():
        snap = {"trainer": trainer.counters_snapshot(),
                "infeed": sharded.counters_snapshot(),
                "ring": shmring.counters_snapshot()}
        if hasattr(feed, "counters_snapshot"):
            snap["feed"] = feed.counters_snapshot()
        return snap

    seconds = traffic["trace_seconds"] if args.trace else args.seconds
    program = {"losses": []}
    window = {}
    trace = harness.WindowTrace(args.trace_dir) if args.trace else None
    open_at = check_steps + warm_steps
    # the profiler slows a host-bound cell for as long as it runs (the
    # SPARK-fed ResNet-50 to two fifths of its rate): a traced run times its
    # window and reads the program's counters with the profiler off, and
    # only then profiles ``trace_profile_steps`` more steps for what the
    # trace alone can say (device-busy seconds a step, the operations)
    profile_steps = traffic.get("trace_profile_steps", 8)
    # the SPARK feed delivers in lumps (a partition, replayed): the window
    # closes on a whole number of them, so that every run times whole lumps
    lump = 1
    if traffic["input_mode"] == "spark":
        replayed = (traffic["rows"] // traffic["partitions"]
                    * traffic["epochs_per_feed"])
        lump = replayed // batch if replayed % batch == 0 else 1

    def hook(steps_done):
        with harness.span("on_steps"):
            if steps_done <= check_steps:
                # log_steps is 1 here: the step's loss was read back
                program["losses"].append(
                    float(trainer.history.last_synced_value))
            if steps_done == 1:
                program["first_gradient"] = _flat(
                    first_gradient(trainer.state.opt_state), names)
                if extra_names:
                    after = _flat(trainer.state.extra, extra_names)
                    program["extra_delta"] = {
                        k: after[k] - start_extra[k] for k in after}
            if steps_done == check_steps:
                end = _flat(trainer.state.params, names)
                program["delta_norms"] = {
                    k: float(np.linalg.norm((end[k] - start_params[k])
                                            .ravel())) for k in end}
                start_params.clear()
                trainer.log_steps = traffic["log_steps"]
                trainer.reset_history()
            if "t0" in window and "counters0" not in window:
                # one call after the opening: the trainer has by now booked
                # the opening call's own time (its sync) on its counters
                window["counters0"] = counters()
            if steps_done == open_at:
                jax.block_until_ready(trainer.state.step)
                window["compiles0"] = watch.mark()
                window["steps0"] = steps_done
                window["wall0"] = time.time()
                window["t0"] = time.perf_counter()
            elif "t1" not in window and "t0" in window and \
                    (steps_done - open_at) % lump == 0 and \
                    time.perf_counter() - window["t0"] >= seconds:
                window["counters1"] = counters()
                jax.block_until_ready(trainer.state.step)
                window["t1"] = time.perf_counter()
                window["steps1"] = steps_done
                if trace:
                    trace.start()
                    trace.open()
            if "t1" in window and \
                    steps_done - window["steps1"] >= (profile_steps if trace
                                                      else 0):
                if trace:
                    jax.block_until_ready(trainer.state.step)
                window["steps_end"] = steps_done
                window["compiles1"] = watch.mark()
                raise _WindowClosed()

    try:
        with harness.span("fit_feed"):
            trainer.fit_feed(sharded, steps_per_call=traffic["steps_per_call"],
                             on_steps=hook)
        raise harness.BenchError("the feed ended before the window closed")
    except _WindowClosed:
        pass
    if trace:
        report["trace"] = trace.stop()
        if report["trace"]:
            report["trace"]["steps"] = window["steps_end"] - window["steps1"]
    sharded.terminate()
    report["memory"] = harness.memory_report(
        trainer._train_step, trainer.state, rows.first[0], batch)
    report["memory_peak_bytes"] = report["memory"]["peak_bytes"]
    steps = window["steps1"] - window["steps0"]
    span_s = window["t1"] - window["t0"]
    report["window"] = {
        "seconds": span_s, "steps": steps, "examples": steps * batch,
        "chips": len(jax.devices()),
        "setup_s": window["wall0"] - args.t_start,
        "compiles": {k: window["compiles1"][k] - window["compiles0"][k]
                     for k in window["compiles0"]},
        "counters0": window["counters0"], "counters1": window["counters1"]}
    report["process_compiles"] = watch.mark()
    report["model"] = {"flops_per_example": flops_example,
                       "batch_size": batch, "kernels": flops.kernels(cfg)}
    print("perfbench: window {:.3f} s, {} steps of {}, {:.2f} examples/s/chip,"
          " model FLOP utilisation {:.2f}%".format(
              span_s, steps, batch, steps * batch / span_s / len(jax.devices()),
              100.0 * flops_example * steps * batch / span_s
              / len(jax.devices()) / device.get("peaks", {}).get(
                  "bf16_flops_per_s", float("inf"))), flush=True)

    # free the program's device state, then follow its first steps in float32
    steps_total = window["steps_end"]
    trainer.state = None
    del trainer, sharded
    gc.collect()
    def remake(index):
        return _remake(adapter, cfg, seed, index)

    problems = _conservation(rows, steps_total, batch, traffic, remake)
    batches, same = _reference_batches(rows, remake, check_steps)
    if not same:
        problems.append("a row of the first batches is not the seed's")
    if not np.isfinite(program["losses"]).all():
        problems.append("a loss is not finite")
    t0 = time.perf_counter()
    ref = reference.train_steps(cfg, seed, batches)
    numbers = correctness.training_numbers(program, ref)
    report["reference_secs"] = time.perf_counter() - t0
    report["numbers"] = numbers
    report["losses"] = {"program": program["losses"],
                        "reference": ref["losses"]}
    if args.control:
        ctl = reference.train_steps(cfg, seed, batches, precision="fp8")
        report["control_numbers"] = correctness.training_numbers(ctl, ref)
        report["losses"]["control"] = ctl["losses"]
    report["problems"] = problems
    report["attempted"] = steps
    report["failed"] = 0


# ---------------------------------------------------------------------------
# In the parent (never imports jax)
# ---------------------------------------------------------------------------

def run(args, workdir):
    """Start the cluster, feed it, wait for the executor's report; returns
    the report (a dict).  ``args`` is the namespace ``run.py`` made."""
    from tensorflowonspark_tpu import backend, cluster

    args.result_path = os.path.join(workdir, "report.json")
    args.trace_dir = os.path.join(workdir, "trace")
    traffic, cfg = args.traffic, args.config
    adapter = importlib.import_module("benchmark.adapters." + cfg["adapter"])
    spark = traffic["input_mode"] == "spark"
    if spark:
        partitions = backend.partition(
            [adapter.make_row(cfg, args.seed, i)
             for i in range(traffic["rows"])], traffic["partitions"])
    else:
        args.shards = _write_shards(adapter, cfg, args.seed, traffic,
                                    os.path.join(workdir, "shards"))
    deadline = time.time() + args.deadline_secs
    b = backend.LocalBackend(1)
    try:
        # stopping a profile of the SPARK-fed process takes 33-37 s, and the
        # whole process has been silent for longer than the liveness deadline
        # (5 s x 3) meanwhile: a traced run is not fenced for the profiler's
        # stall.  An untraced run keeps the program's default
        liveness = {"heartbeat_misses": 10 ** 6} if args.trace else {}
        c = cluster.run(
            b, main_fun, args, num_executors=1,
            input_mode=(cluster.InputMode.SPARK if spark
                        else cluster.InputMode.FILES), **liveness)
        if spark:
            while not os.path.exists(args.result_path) and not c.server.done:
                if time.time() > deadline:
                    raise RuntimeError("no report within the time allowed")
                c.train(partitions, num_epochs=traffic["epochs_per_feed"],
                        chunk_size=traffic["chunk_size"])
        while not os.path.exists(args.result_path):
            if time.time() > deadline:
                raise RuntimeError("no report within the time allowed")
            time.sleep(0.1)
        c.shutdown(grace_secs=1)
    finally:
        b.stop()
    with open(args.result_path) as f:
        return json.load(f)
