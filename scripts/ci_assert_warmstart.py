"""CI gate: warm-start compile plane — a replacement node must rejoin WARM.

Boots a real 2-node in-process cluster with a cluster-shared compile cache
(persistent XLA cache + AOT executable store), SIGKILLs one worker's node
process mid-run, and asserts the replacement rejoins on the warm path:

1. every node trains a real (tiny, CPU) jitted step, so
   ``compilecache.stats`` counts each node's actual compile debt: what
   resolving its step program cost (``compile_cache_aot_compile_us`` +
   ``compile_cache_aot_load_us``),
2. the replacement's step program resolves to verdict ``loaded`` — it
   deserialized a fingerprint-matched executable and NEVER traced,
3. the replacement's compile debt is a small fraction of the cold nodes',
4. ``tfos_compile_cache_hit_total`` is nonzero on a live ``/metrics``
   scrape (the counters ride heartbeats into the observatory),
5. every fed element is accounted for exactly once (the elastic-recovery
   guarantee survives the new plumbing).

Run next to the elastic gate in run_tests.sh.  Exit 0 = warm rejoin proven;
any assertion names the stage that broke.
"""

import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes

N_ITEMS = 40   # 4 partitions of 10: the kill (after 5) always interrupts
               # executor 0 MID-partition, so its feed task fails its join
               # and the partition is re-fed wholesale (exactly-once math)
WARM_FRACTION = 3      # replacement compile debt must be <= cold / this
SCRAPE_DEADLINE_SECS = 30.0


def _node_fn(args, ctx):
    """Train a few real jitted steps (compile debt + AOT resolution), then
    consume this node's feed for the exactly-once total.  The steps run
    BEFORE the feed loop so the replacement — which may receive no
    re-dispatched partitions — still proves its warm step path."""
    import time as _time

    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import compilecache
    from tensorflowonspark_tpu import train as train_mod

    cache_root = (compilecache.configured_dir()
                  or os.environ[compilecache.CACHE_DIR_ENV])

    def loss(params, batch, mask):
        pred = jnp.tanh(jnp.asarray(batch["x"]) @ params["w1"]) @ params["w2"]
        err = (pred - jnp.asarray(batch["y"])) ** 2 * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    trainer = train_mod.Trainer(
        loss, {"w1": jnp.zeros((8, 16)), "w2": jnp.zeros((16,))},
        optax.adam(1e-2), batch_size=4, log_steps=2,
        aot_cache=os.path.join(cache_root, "aot"))
    batch = {"x": jnp.ones((4, 8)), "y": jnp.ones((4,))}
    mask = jnp.ones((4,), jnp.float32)

    def report(total):
        cache = compilecache.stats.counters_snapshot()
        doc = {
            "executor_id": ctx.executor_id,
            "total": int(total),
            "compile_debt_us": (cache["compile_cache_aot_compile_us"]
                                + cache["compile_cache_aot_load_us"]),
            "verdicts": dict(trainer._aot_verdicts),
            "cache": cache,
        }
        tmp = "report.json.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, "report.json")   # SIGKILL-safe: never half-written

    for _ in range(3):
        trainer.step(batch, mask)
    report(0)

    feed = ctx.get_data_feed()
    total = 0
    while not feed.should_stop():
        for x in feed.next_batch(2):
            total += int(x)
        report(total)
    report(total)
    # Stay registered across a few beats so the driver's /metrics scrape
    # catches the compile-cache counters while the cluster is live.
    _time.sleep(3.0)


def _scrape_metric(base, name, deadline_secs):
    """Poll /metrics until ``name`` shows a positive sample; returns the
    value (summed over label sets) or None on deadline."""
    deadline = time.time() + deadline_secs
    while time.time() < deadline:
        try:
            text = urllib.request.urlopen(
                base + "/metrics", timeout=5).read().decode()
        except Exception:
            time.sleep(0.3)
            continue
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name) and not line.startswith("#"):
                try:
                    total += float(line.rsplit(None, 1)[-1])
                except ValueError:
                    pass
        if total > 0:
            return total
        time.sleep(0.3)
    return None


def main():
    from tensorflowonspark_tpu import backend, cluster, fault
    from tensorflowonspark_tpu.cluster import InputMode

    cache_dir = tempfile.mkdtemp(prefix="ci_warmstart_cache_")
    spec = json.dumps({"kill_after_items": 5})
    b = backend.LocalBackend(
        2, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None])
    try:
        c = cluster.run(b, _node_fn, tf_args=[], num_executors=2,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2,
                        telemetry=True,
                        telemetry_dir=os.path.join(cache_dir, "telemetry"),
                        observatory=True, log_dir=cache_dir,
                        compile_cache_dir=cache_dir)
        policy = fault.RetryPolicy(max_attempts=5, initial_backoff=1.5,
                                   multiplier=1.5, jitter=0.3)
        t0 = time.time()
        c.train(backend.partition(range(N_ITEMS), 4), retry_policy=policy)
        elapsed = time.time() - t0

        # Stage 1: the elastic chain closed (death -> replacement).
        dead = c.tf_status.get("dead_nodes")
        assert dead and "executor 0" in dead[0], \
            "liveness monitor missed the death: {}".format(c.tf_status)
        assert c.tf_status.get("replacements"), \
            "no replacement admitted: {}".format(c.tf_status)
        assert "replacement_errors" not in c.tf_status, \
            "replacement start task failed: {}".format(c.tf_status)
        assert "error" not in c.tf_status, c.tf_status["error"]

        # Stage 2: compile-cache counters reached /metrics while live.
        assert c.observatory is not None and c.observatory.addr, \
            "observatory did not start"
        hits = _scrape_metric("http://%s:%d" % c.observatory.addr,
                              "tfos_compile_cache_hit_total",
                              SCRAPE_DEADLINE_SECS)
        assert hits, "tfos_compile_cache_hit_total never nonzero on /metrics"

        c.shutdown(grace_secs=1)

        # Stage 3: per-node compile debt from the on-disk reports.
        reports = {}
        for i in (0, 1, 2):
            path = os.path.join(b.workdir_root,
                                "executor-{}".format(i), "report.json")
            if os.path.exists(path):
                with open(path) as f:
                    reports[i] = json.load(f)
        print("per-node reports:", {
            i: {"total": r["total"], "compile_us": r["compile_debt_us"],
                "verdicts": r["verdicts"]}
            for i, r in sorted(reports.items())})
        assert 2 in reports, \
            "replacement wrote no report: {}".format(sorted(reports))
        cold_us = max(reports[i]["compile_debt_us"]
                      for i in (0, 1) if i in reports)
        warm = reports[2]
        warm_us = warm["compile_debt_us"]
        assert warm["verdicts"].get("step") == "loaded", \
            "replacement retraced its step program: {}".format(
                warm["verdicts"])
        assert warm_us * WARM_FRACTION <= cold_us, \
            "warm rejoin compile debt not a small fraction of cold: " \
            "{}us warm vs {}us cold".format(warm_us, cold_us)
        assert warm["cache"]["compile_cache_hit"] > 0, \
            "replacement saw no persistent-cache hits: {}".format(
                warm["cache"])

        # Stage 4: exactly-once totals across the survivors (executor 0's
        # partial progress is re-fed wholesale after the kill).
        total = sum(reports[i]["total"] for i in (1, 2) if i in reports)
        assert total == sum(range(N_ITEMS)), \
            "partitions lost or double-fed: {} != {}".format(
                total, sum(range(N_ITEMS)))

        print("warm start OK: replacement rejoined with loaded step "
              "executable, {}us compile debt vs {}us cold ({:.1f}x), "
              "{} cache hit(s) on /metrics, run completed in {:.1f}s".format(
                  warm_us, cold_us, cold_us / max(warm_us, 1), int(hits),
                  elapsed))
        return 0
    finally:
        b.stop()


if __name__ == "__main__":
    sys.exit(main())
