"""Standalone batch-inference CLI (reference ``Inference.scala`` + ``TFModel.scala``).

The reference ships a JVM-only serving path: a ``spark-submit``-able main
that loads TFRecords (with an optional ``--schema_hint``), feeds them
through a cached SavedModel session with JSON input/output mappings, and
writes predictions as JSON (reference ``Inference.scala:27-79``,
``TFModel.scala:245-292``).  This is its first-party equivalent over the
framework export: TFRecords via the C++ codec, the model rebuilt from the
export descriptor, batched jit inference, JSON-lines output — no JVM, no
user code on the serving host.

Usage:
    python -m tensorflowonspark_tpu.inference_cli \
        --export_dir /path/to/export --input /path/to/tfrecords \
        --schema_hint 'struct<image:array<float>,label:bigint>' \
        --input_mapping '{"image": "image"}' \
        --output /path/to/preds.jsonl

``--serve`` switches to ONLINE mode: instead of draining a TFRecord set,
the process becomes one continuous-batching gateway replica
(:class:`~tensorflowonspark_tpu.gateway.GatewayServer`) and runs until
SIGTERM/SIGINT, mirroring ``dataservice_worker.py``'s lifecycle.  Pass
``--roster host:port`` to join a replica fleet behind the reservation
server (failover via the elastic-recovery plane):

    python -m tensorflowonspark_tpu.inference_cli \
        --export_dir /path/to/export --serve --port 8500 \
        --max-batch 64 --max-wait-ms 5 --roster driver:41111
"""

import argparse
import json
import logging
import sys

import numpy as np

from tensorflowonspark_tpu import dfutil, schema as schema_mod

logger = logging.getLogger(__name__)


def _json_default(o):
    """Numpy scalars/arrays (vectorized TFRecord decode) serialize as plain
    JSON numbers/lists; anything else still fails loudly."""
    if isinstance(o, (np.ndarray, np.generic)):
        return o.tolist()
    raise TypeError(
        "Object of type {} is not JSON serializable".format(type(o).__name__))


def run_inference(export_dir, rows, input_mapping=None, output_name=None,
                  output_mapping=None, batch_size=128):
    """Yield one output row dict per input row (1:1 contract, reference
    ``TFModel.scala:265-281`` / ``pipeline.py:509-512``).

    N input tensors via ``input_mapping`` ``{column: tensor}`` and M output
    columns via ``output_mapping`` ``{tensor: column}`` — the full
    multi-tensor serving surface (see
    :class:`~tensorflowonspark_tpu.serving.ModelServer`).  ``output_name``
    is the single-output shorthand (kept for CLI/back compatibility): it
    renames a single-output model's ``prediction`` column.
    """
    from tensorflowonspark_tpu import serving

    server = serving.ModelServer(export_dir, batch_size)
    for row in server.run_rows_dict(iter(rows), input_mapping=input_mapping,
                                    output_mapping=output_mapping):
        if output_name and output_name != "prediction" and "prediction" in row:
            # single-output shorthand: rename the default column
            row[output_name] = row.pop("prediction")
        yield row


def run_inference_native(export_dir, rows, plugin_path, input_mapping=None,
                         output_mapping=None):
    """Serve through the C++ PJRT runner (``native/pjrt_runner``): batches
    are padded to the embedded module's fixed batch size, fed as raw
    buffers, and the runner's outputs zip back into one dict per input row.
    Requires the export to carry the ``embedded_mlir`` artifact
    (``export_model(..., embed_batch_size=...)``).
    """
    import os

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.checkpoint import _fs_path

    with open(os.path.join(_fs_path(export_dir), "export.json")) as f:
        desc = json.load(f)
    emb = desc.get("embedded_mlir")
    if not emb:
        raise ValueError(
            "export has no embedded_mlir artifact; re-export with "
            "embed_batch_size set to use --pjrt_plugin serving")
    bsz = emb["batch_size"]
    col_for = {t: c for c, t in (input_mapping or {}).items()}
    out_col = dict(output_mapping or {})
    rows = list(rows)
    # Build every padded chunk first, then serve them through ONE runner
    # invocation (--batches): the module compiles once instead of per chunk.
    chunks = []
    feeds = []
    for lo in range(0, len(rows), bsz):
        chunk = rows[lo:lo + bsz]
        count = len(chunk)
        feed = {}
        for spec in emb["inputs"]:
            tensor = spec["name"]
            col = col_for.get(tensor, tensor)
            vals = np.asarray([r[col] for r in chunk])
            vals = vals.reshape([-1] + list(spec["shape"][1:]))
            if count < bsz:
                pad = [(0, bsz - count)] + [(0, 0)] * (vals.ndim - 1)
                vals = np.pad(vals, pad)
            feed[tensor] = vals
        chunks.append(chunk)
        feeds.append(feed)
    all_outs = serving.run_embedded_native_many(export_dir, feeds,
                                                plugin_path)
    for chunk, outs in zip(chunks, all_outs):
        for i in range(len(chunk)):
            row = dict(chunk[i])
            for tensor, arr in outs.items():
                cell = arr[i]
                row[out_col.get(tensor, tensor)] = (
                    cell.tolist() if cell.ndim else cell.item())
            yield row


def _replica_report(server):
    """What this replica serves with, for whoever launched it: the device
    JAX gave the process, whether the export's own StableHLO artifact is the
    serving program (and why not, when it is not), and what warming the
    bucket ladder cost."""
    import jax

    from tensorflowonspark_tpu import compilecache

    devices = jax.devices()
    warm = server.warmup_report or {"buckets": []}
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "from_stablehlo": server.from_stablehlo,
        "stablehlo_fallback": server.stablehlo_fallback,
        "warmup_secs": round(
            sum(r["micros"] for r in warm["buckets"]) / 1e6, 3),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_hit": compilecache.stats.cache_hit,
        "compile_cache_miss": compilecache.stats.cache_miss,
    }


def serve_forever(args):
    """``--serve``: run one gateway replica until SIGTERM/SIGINT (the
    ``dataservice_worker.py`` lifecycle — print a ready line, wait on a
    signal-set event, drain on the way out)."""
    import signal
    import threading

    from tensorflowonspark_tpu import gateway, serving, telemetry

    telemetry.configure_from_meta({})
    telemetry.install_sigusr1()
    model_version = getattr(args, "model_version", None)
    if getattr(args, "registry", None):
        # fleet mode: resolve --model NAME[@VERSION] through the model
        # registry instead of pinning an export path; the registry entry
        # also supplies the version label and (absent an explicit flag)
        # the shared AOT warm dir
        from tensorflowonspark_tpu import fleet

        registry = fleet.ModelRegistry(args.registry)
        name, _, pinned = (args.model or "").partition("@")
        if not name:
            raise SystemExit("--registry requires --model NAME[@VERSION]")
        entry = registry.resolve(name, pinned or model_version or None)
        args.export_dir = entry["export_dir"]
        model_version = entry["version"]
        if entry.get("warm_dir") and not args.warm_cache_dir:
            args.warm_cache_dir = entry["warm_dir"]
        logger.info("registry %s resolved %s@%s -> %s", args.registry,
                    name, model_version, args.export_dir)
    elif not args.export_dir:
        raise SystemExit("--serve needs --export_dir or --registry/--model")
    # Warm-start compile plane: persistent XLA cache (placed by
    # --warm-cache-dir or from outside by the environment; inert when
    # nothing names one) + with --warm-cache-dir the serialized bucket-rung
    # executables under the same root, so a restarted replica reaches first
    # prediction in seconds with compile_count == 0.
    # register_feed=False: gateway beats merge the counters themselves
    # (heartbeat_metrics), there is no node heartbeat here.
    from tensorflowonspark_tpu import compilecache

    compilecache.configure(args.warm_cache_dir, register_feed=False)
    server = serving.ModelServer(args.export_dir, args.max_batch,
                                 warm_cache_dir=args.warm_cache_dir)
    gw = gateway.GatewayServer(
        server, host=args.host, port=args.port,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue, roster_addr=args.roster,
        replica_id=args.replica_id, task_index=args.task_index,
        heartbeat_interval=args.heartbeat,
        slo_latency_us=args.slo_latency_us,
        model_version=model_version)
    host, port = gw.start()
    print("serving replica {} ready on {}:{} (buckets {})".format(
        gw.replica_id, host, port, list(server.buckets)), flush=True)
    print("serving replica {} report {}".format(
        gw.replica_id, json.dumps(_replica_report(server))), flush=True)
    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    gw.stop()
    # flush request-flow trace events before exit so a clean SIGTERM drain
    # leaves trace-<host>-<pid>.json behind for the merged timeline
    telemetry.get_tracer().flush()


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Batch inference over TFRecords with a framework export "
                    "(reference Inference.scala); --serve runs an online "
                    "continuous-batching gateway replica instead")
    parser.add_argument("--export_dir", default=None,
                        help="export directory (required for batch mode; "
                             "--serve can resolve one via --registry/--model "
                             "instead)")
    parser.add_argument("--input", default=None,
                        help="TFRecord directory (required unless --serve)")
    parser.add_argument("--schema_hint", default=None,
                        help="struct<name:type,...> (reference --schema_hint)")
    parser.add_argument("--input_mapping", default=None,
                        help='JSON {"column": "tensor"} (reference -i)')
    parser.add_argument("--output_mapping", default=None,
                        help='JSON {"tensor": "column"}, one entry per '
                             "output tensor (reference -o)")
    parser.add_argument("--batch_size", type=int, default=128)
    parser.add_argument("--pjrt_plugin", default=None,
                        help="serve through the native C++ PJRT runner with "
                             "this plugin .so (e.g. libtpu.so); needs an "
                             "export with the embedded_mlir artifact")
    parser.add_argument("--output", default=None,
                        help="output JSON-lines path (stdout when omitted)")
    serve = parser.add_argument_group("online serving (--serve)")
    serve.add_argument("--serve", action="store_true",
                       help="run as a gateway replica instead of batch mode")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=0,
                       help="listen port (0 = ephemeral, printed on ready)")
    serve.add_argument("--max-batch", type=int, default=None, dest="max_batch",
                       help="batch coalescing cap (default: --batch_size)")
    serve.add_argument("--max-wait-ms", type=float, default=5.0,
                       dest="max_wait_ms",
                       help="batching latency budget per request")
    serve.add_argument("--max-queue", type=int, default=None, dest="max_queue",
                       help="admission-control queue bound "
                            "(default 4 * max_batch)")
    serve.add_argument("--roster", default=None,
                       help="reservation server host:port to register with")
    serve.add_argument("--replica-id", default=None, dest="replica_id")
    serve.add_argument("--task-index", type=int, default=0, dest="task_index")
    serve.add_argument("--heartbeat", type=float, default=1.0,
                       help="roster heartbeat interval seconds")
    serve.add_argument("--slo-latency-us", type=float, default=0.0,
                       dest="slo_latency_us",
                       help="availability+latency SLO threshold in "
                            "microseconds: completed requests at or under "
                            "it count as serving_slo_good (0 = latency "
                            "leg disarmed; sheds always burn budget)")
    serve.add_argument("--registry", default=None,
                       help="model-fleet registry root (fleet.ModelRegistry): "
                            "resolve the export through the registry instead "
                            "of --export_dir")
    serve.add_argument("--model", default=None,
                       help="with --registry: model NAME or NAME@VERSION "
                            "(default version = the model's live default)")
    serve.add_argument("--model-version", default=None, dest="model_version",
                       help="version label override for serving metrics / "
                            "roster meta (set automatically by --registry)")
    serve.add_argument("--warm-cache-dir", default=None,
                       dest="warm_cache_dir",
                       help="warm-start root: persistent XLA compile cache "
                            "+ serialized bucket-rung executables; a "
                            "replica restart then warms by deserializing "
                            "(compile_count stays 0)")
    args = parser.parse_args(argv)

    if args.serve:
        if args.max_batch is None:
            args.max_batch = args.batch_size
        serve_forever(args)
        return
    if not args.export_dir:
        parser.error("--export_dir is required in batch mode")
    if not args.input:
        parser.error("--input is required (or pass --serve for online mode)")

    hint = schema_mod.parse(args.schema_hint) if args.schema_hint else None
    input_mapping = json.loads(args.input_mapping) if args.input_mapping else None
    output_mapping = (json.loads(args.output_mapping)
                      if args.output_mapping else None)

    rows = dfutil.load_tfrecords(args.input, schema=hint)
    logger.info("loaded %d rows from %s (schema %s)",
                len(rows), args.input, rows.schema)

    if args.pjrt_plugin:
        results = run_inference_native(
            args.export_dir, rows, args.pjrt_plugin,
            input_mapping=input_mapping, output_mapping=output_mapping)
    else:
        results = run_inference(args.export_dir, rows,
                                input_mapping=input_mapping,
                                output_mapping=output_mapping,
                                batch_size=args.batch_size)
    out_f = open(args.output, "w") if args.output else sys.stdout
    try:
        n = 0
        for out in results:
            out_f.write(json.dumps(out, default=_json_default) + "\n")
            n += 1
        logger.info("wrote %d predictions", n)
    finally:
        if args.output:
            out_f.close()


if __name__ == "__main__":
    main()
