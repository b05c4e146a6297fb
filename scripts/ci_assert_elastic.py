"""CI gate: the elastic-recovery loop must actually close.

Boots a real 3-node in-process cluster on the built-in backend, SIGKILLs one
worker's node process mid-run, and asserts the full detect → reclaim →
replace chain within the heartbeat deadline:

1. the liveness monitor declares the node dead (seconds, not timeouts),
2. its roster slot is released and a FRESH executor is provisioned into it,
3. the replacement registers and the roster generation bumps,
4. the run completes with every partition accounted for exactly once.

Run next to the graft dry-run gate in run_tests.sh.  Exit 0 = the loop
closed; any assertion names the stage that broke.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU gate: several jax processes


def _node_fn(args, ctx):
    """Consume this node's feed and persist the running total (no jax: the
    gate exercises the control plane, not the math)."""
    feed = ctx.get_data_feed()
    total = 0
    while not feed.should_stop():
        for x in feed.next_batch(2):
            total += x
    with open("sum.txt", "w") as f:
        f.write(str(total))


def main():
    from tensorflowonspark_tpu import backend, cluster, fault
    from tensorflowonspark_tpu.cluster import InputMode

    spec = json.dumps({"kill_after_items": 5})
    b = backend.LocalBackend(
        3, env_per_executor=[{fault.FAULT_SPEC_ENV: spec}, None, None])
    try:
        c = cluster.run(b, _node_fn, tf_args=[], num_executors=3,
                        input_mode=InputMode.SPARK,
                        heartbeat_interval=0.5, heartbeat_misses=2)
        policy = fault.RetryPolicy(max_attempts=5, initial_backoff=1.5,
                                   multiplier=1.5, jitter=0.3)
        t0 = time.time()
        c.train(backend.partition(range(30), 3), retry_policy=policy)
        elapsed = time.time() - t0

        dead = c.tf_status.get("dead_nodes")
        assert dead and "executor 0" in dead[0], \
            "liveness monitor missed the death: {}".format(c.tf_status)
        assert c.tf_status.get("replacements"), \
            "no replacement admitted: {}".format(c.tf_status)
        assert "replacement_errors" not in c.tf_status, \
            "replacement start task failed: {}".format(c.tf_status)
        assert c.server.reservations.generation >= 1, \
            "roster generation did not bump"
        roster = sorted(n["executor_id"] for n in c.cluster_info)
        assert 0 not in roster and 3 in roster, \
            "replacement did not claim the freed slot: {}".format(roster)
        assert "error" not in c.tf_status, c.tf_status["error"]

        c.shutdown(grace_secs=1)
        total = 0
        for i in (1, 2, 3):
            path = os.path.join(b.workdir_root,
                                "executor-{}".format(i), "sum.txt")
            if os.path.exists(path):
                with open(path) as f:
                    total += int(f.read())
        assert total == sum(range(30)), \
            "partitions lost or double-fed: {} != {}".format(
                total, sum(range(30)))
        print("elastic recovery OK: death detected, slot reclaimed, "
              "replacement admitted (generation {}), run completed in "
              "{:.1f}s".format(c.server.reservations.generation, elapsed))
        return 0
    finally:
        b.stop()


if __name__ == "__main__":
    sys.exit(main())
