"""Observatory tests: Prometheus exposition conformance, scrape consistency
under node death, and runtime-MFU vs closed-window-MFU agreement (CPU
mesh)."""

import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import metrics as metrics_mod
from tensorflowonspark_tpu import observatory
from tensorflowonspark_tpu.train import Trainer
from tensorflowonspark_tpu.parallel import build_mesh, batch_sharding

# text exposition 0.0.4: metric names and one sample line
NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
SAMPLE_RE = re.compile(
    r'([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.e+-]+|[+-]Inf|NaN)\Z')


def _parse_exposition(text):
    """Returns (families, samples): families maps name -> type, samples is
    [(family_name, line)] in exposition order.  Raises AssertionError on any
    line that is neither a well-formed comment nor a well-formed sample."""
    families = {}
    helped = set()
    samples = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert NAME_RE.match(name), line
            helped.add(name)
        elif line.startswith("# TYPE "):
            parts = line.split()
            name, mtype = parts[2], parts[3]
            assert NAME_RE.match(name), line
            assert mtype in ("counter", "gauge", "histogram"), line
            assert name not in families, "duplicate TYPE for %s" % name
            families[name] = mtype
        else:
            m = SAMPLE_RE.match(line)
            assert m, "unparseable sample line: %r" % line
            samples.append((m.group(1), line))
    assert helped == set(families), "HELP/TYPE mismatch"
    return families, samples


def _family_of(sample_name, families):
    """Histogram samples use _bucket/_count/_sum suffixes on the family."""
    for suffix in ("_bucket", "_count", "_sum"):
        if sample_name.endswith(suffix) and sample_name[:-len(suffix)] \
                in families:
            return sample_name[:-len(suffix)]
    return sample_name


SNAPSHOT = {
    "nodes": {
        "executor-0": {
            "chunks": 41, "rows": 820, "depth_hwm": 7,
            "dispatch_gap_us": 1200, "dispatch_gap_us_hwm": 300,
            "train_mfu_pct_max": 37.5, "train_flops_per_sec_max": 3.7e10,
            "goodput_dispatch_us": 900000, "goodput_infeed_starved_us": 1000,
            "step_ms_le_5": 3, "step_ms_le_10": 9, "step_ms_le_25": 9,
            "step_ms_count": 10, "step_ms_sum_us": 88000,
            "weird key!": 5,           # name needs sanitizing
            "ignored_str": "not-a-number",
        },
        "executor-1": {"chunks": 7, "events_dropped": 2},
    },
    "aggregate": {"chunks": 48},
}


class TestPrometheusConformance:
    def test_exposition_parses_and_types_are_correct(self):
        text = observatory.render_prometheus(SNAPSHOT, scrapes=3)
        families, samples = _parse_exposition(text)
        # counter vs gauge typing follows the _hwm/_max suffix convention
        assert families["tfos_chunks_total"] == "counter"
        assert families["tfos_events_dropped_total"] == "counter"
        assert families["tfos_depth_hwm"] == "gauge"
        assert families["tfos_dispatch_gap_us_hwm"] == "gauge"
        assert families["tfos_train_mfu_pct_max"] == "gauge"
        assert families["tfos_nodes"] == "gauge"
        assert families["tfos_scrapes_total"] == "counter"
        assert families["tfos_step_ms"] == "histogram"
        # every counter family name carries the _total suffix
        for name, mtype in families.items():
            if mtype == "counter":
                assert name.endswith("_total"), name
        # sanitized name made it through, string value did not
        assert "tfos_weird_key__total" in families
        assert "ignored_str" not in text

    def test_family_samples_are_contiguous(self):
        text = observatory.render_prometheus(SNAPSHOT, scrapes=1)
        families, samples = _parse_exposition(text)
        seen_done = set()
        current = None
        for sample_name, _ in samples:
            fam = _family_of(sample_name, families)
            assert fam in families, sample_name
            if fam != current:
                assert fam not in seen_done, \
                    "family %s interleaved" % fam
                if current is not None:
                    seen_done.add(current)
                current = fam

    def test_histogram_is_cumulative_with_inf_bucket(self):
        text = observatory.render_prometheus(SNAPSHOT)
        bucket_re = re.compile(
            r'tfos_step_ms_bucket\{executor="executor-0",le="([^"]+)"\} '
            r'(\d+)')
        buckets = bucket_re.findall(text)
        assert buckets, text
        assert buckets[-1][0] == "+Inf"
        counts = [int(c) for _, c in buckets]
        assert counts == sorted(counts), "buckets not cumulative"
        count_re = re.compile(
            r'tfos_step_ms_count\{executor="executor-0"\} (\d+)')
        assert int(count_re.search(text).group(1)) == counts[-1] == 10
        # sum is milliseconds (counters carry microseconds)
        assert 'tfos_step_ms_sum{executor="executor-0"} 88.0' in text

    def test_ring_rates_skip_gauges_and_clamp_resets(self):
        import time as _time
        ring = observatory.SampleRing()
        now = _time.time()
        ring.record("n0", {"chunks": 100, "depth_hwm": 9}, ts=now - 10)
        ring.record("n0", {"chunks": 40, "depth_hwm": 5}, ts=now)
        rates = ring.rates(window_secs=60.0)
        # counter reset (restart) clamps to zero, never negative
        assert rates["n0"]["chunks"] == 0.0
        # gauges have no meaningful rate
        assert "depth_hwm" not in rates["n0"]


class TestScrapeDuringNodeDeath:
    def test_concurrent_scrapes_stay_consistent(self):
        """Nodes appearing/dying between and during scrapes must never
        produce a torn or unparseable exposition."""
        full = dict(SNAPSHOT["nodes"])
        state = {"nodes": dict(full), "aggregate": {}}
        lock = threading.Lock()

        def snapshot_fn():
            with lock:
                return {"nodes": dict(state["nodes"]), "aggregate": {}}

        srv = observatory.ObservatoryServer(
            snapshot_fn, status_fn=lambda: {"state": "running"},
            host="127.0.0.1")
        host, port = srv.start()
        stop = threading.Event()

        def churn():
            flip = False
            while not stop.is_set():
                with lock:
                    state["nodes"] = ({"executor-1": full["executor-1"]}
                                      if flip else dict(full))
                flip = not flip

        churner = threading.Thread(target=churn, daemon=True)
        churner.start()
        try:
            base = "http://%s:%d" % (host, port)
            for _ in range(25):
                text = urllib.request.urlopen(
                    base + "/metrics", timeout=5).read().decode()
                families, _ = _parse_exposition(text)
                n = int(re.search(r"tfos_nodes (\d+)", text).group(1))
                assert n in (1, 2)
                # one consistent snapshot per scrape: tfos_chunks_total
                # has exactly n executor samples
                assert text.count("tfos_chunks_total{") == n
                status = json.loads(urllib.request.urlopen(
                    base + "/status", timeout=5).read().decode())
                assert status["tf_status"] == {"state": "running"}
                assert len(status["metrics_snapshot"]["nodes"]) in (1, 2)
        finally:
            stop.set()
            churner.join(timeout=2)
            srv.stop()

    def test_snapshot_failure_yields_valid_exposition(self):
        def bad_snapshot():
            raise RuntimeError("node registry torn down")

        srv = observatory.ObservatoryServer(bad_snapshot, host="127.0.0.1")
        host, port = srv.start()
        try:
            text = urllib.request.urlopen(
                "http://%s:%d/metrics" % (host, port),
                timeout=5).read().decode()
        finally:
            srv.stop()
        _parse_exposition(text)
        assert "tfos_nodes 0" in text


def _linear_loss(params, batch, mask):
    pred = batch["x"] @ params["w"] + params["b"]
    err = (pred - batch["y"]) ** 2 * mask
    return err.sum() / jnp.maximum(mask.sum(), 1.0), pred


class TestRuntimeMfuAgreement:
    def test_runtime_mfu_matches_closed_window_mfu_within_5pct(
            self, cpu_peaks):
        """The Trainer's runtime MFU gauge must agree with TimeHistory.mfu
        over a closed window within 5% on a tiny jitted step — they share
        formula AND clock, so disagreement means the accountant folded the
        wrong window."""
        mesh = build_mesh()
        # a matmul big enough that a 5-step window is not pure noise
        rng = np.random.RandomState(0)
        x = rng.rand(256, 128).astype(np.float32)
        w = jnp.zeros((128, 1))

        def loss_fn(params, batch, mask):
            pred = (batch["x"] @ params["w"])[:, 0]
            err = (pred - batch["y"]) ** 2 * mask
            return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

        sharding = batch_sharding(mesh)
        batch = {"x": jax.device_put(x, sharding),
                 "y": jax.device_put(rng.rand(256).astype(np.float32),
                                     sharding)}
        # the count, stated from shapes: 6 a parameter and example, per device
        tr = Trainer(loss_fn, {"w": w}, optax.sgd(0.01), mesh=mesh,
                     batch_size=256, log_steps=5,
                     step_flops_override=6 * 128 * 256 / mesh.size)
        # warm up, reset, measure
        for _ in range(3):
            tr.step(batch)
        tr.reset_history()
        for _ in range(20):
            loss, _ = tr.step(batch)
        tr._account_windows()
        snap = tr.counters_snapshot()
        assert snap.get("train_mfu_pct_max") is not None, snap
        runtime_mfu = snap["train_mfu_pct_max"] / 100.0

        log = tr.history.timestamp_log
        assert len(log) >= 2, log
        (s0, t0), (s1, t1) = log[-2], log[-1]
        window_mfu = tr.history.mfu((t1 - t0) / (s1 - s0))
        assert window_mfu is not None
        assert runtime_mfu == pytest.approx(window_mfu, rel=0.05)
        # achieved FLOP/s gauge agrees with the same window too
        assert snap["train_flops_per_sec_max"] == pytest.approx(
            metrics_mod.achieved_flops_per_sec(
                tr.history.step_flops, (t1 - t0) / (s1 - s0)), rel=0.05)
        # histogram accounting covered every closed-window step
        assert snap["step_ms_count"] == s1
        bucket_keys = [k for k in snap if k.startswith("step_ms_le_")]
        assert bucket_keys
        bounds = sorted(int(k[len("step_ms_le_"):]) for k in bucket_keys)
        cum = [snap["step_ms_le_%s" % b] for b in bounds]
        assert cum == sorted(cum), "cumulative buckets must be monotone"
        assert cum[-1] <= snap["step_ms_count"]

    def test_whole_run_mfu_same_ballpark(self, cpu_peaks):
        """build_stats' whole-run mfu and the runtime gauge's latest-window
        mfu measure the same steady loop — generous 2x band only to absorb
        CPU scheduler jitter."""
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        # a count large enough that the gauge's four decimal places of a
        # percent hold it on a slow box; its size is not what is compared
        tr = Trainer(_linear_loss, params, optax.sgd(0.01), mesh=mesh,
                     batch_size=64, log_steps=5, step_flops_override=1e6)
        batch = {"x": jnp.ones((64, 2)), "y": jnp.ones((64,))}
        for _ in range(3):
            tr.step(batch)
        tr.reset_history()
        loss = None
        for _ in range(20):
            loss, _ = tr.step(batch)
        tr.history.on_train_end(loss)
        tr._account_windows()
        stats = tr.history.build_stats(loss=float(loss))
        snap = tr.counters_snapshot()
        runtime = snap["train_mfu_pct_max"] / 100.0
        assert stats["mfu"] / 2 <= runtime <= stats["mfu"] * 2, \
            (stats["mfu"], runtime)


# ---------------------------------------------------------------------------
# request-plane exposition: serving stage histograms, shed reasons, tfos_up,
# and the /slow exemplar endpoint
# ---------------------------------------------------------------------------

SERVING_SNAPSHOT = {
    "nodes": {
        "replica-0": {
            "serving_requests": 12, "serving_shed": 2,
            "serving_shed_overload": 1, "serving_shed_deadline": 1,
            "serving_shed_shutdown": 0, "serving_shed_internal": 0,
            "serving_slo_good": 9, "serving_slo_total": 12,
            "serving_model": "linear", "serving_model_version": "3",
            "serving_queue_us_le_50": 2, "serving_queue_us_le_100": 7,
            "serving_queue_us_le_250": 10, "serving_queue_us_count": 12,
            "serving_queue_us_sum_us": 3100,
            "serving_latency_us_le_500": 4, "serving_latency_us_le_1000": 11,
            "serving_latency_us_count": 12,
            "serving_latency_us_sum_us": 8800,
            "serving_slow": [
                {"req": "c0-4", "flow": 9, "latency_us": 900.0,
                 "queue_us": 100.0, "coalesce_us": 50.0,
                 "dispatch_us": 700.0, "serialize_us": 50.0,
                 "rows": 1, "batch_rows": 4, "time": 1.0,
                 "model": "linear", "version": "3"},
                {"req": "c1-2", "flow": 11, "latency_us": 400.0,
                 "queue_us": 40.0, "coalesce_us": 20.0,
                 "dispatch_us": 320.0, "serialize_us": 20.0,
                 "rows": 1, "batch_rows": 2, "time": 1.2,
                 "model": "linear", "version": "3"},
            ],
        },
        "replica-1": {
            "serving_requests": 3,
            "serving_slow": [
                {"req": "c2-0", "flow": 21, "latency_us": 600.0,
                 "queue_us": 50.0, "coalesce_us": 30.0,
                 "dispatch_us": 500.0, "serialize_us": 20.0,
                 "rows": 1, "batch_rows": 1, "time": 1.1,
                 "model": "linear", "version": "3"}],
        },
    },
    "aggregate": {"serving_requests": 15},
}


class TestServingExposition:
    def test_stage_histogram_with_model_version_labels(self):
        text = observatory.render_prometheus(SERVING_SNAPSHOT)
        families, _ = _parse_exposition(text)
        assert families["tfos_serving_queue_us"] == "histogram"
        assert families["tfos_serving_latency_us"] == "histogram"
        bucket_re = re.compile(
            r'tfos_serving_queue_us_bucket\{executor="replica-0",'
            r'model="linear",version="3",le="([^"]+)"\} (\d+)')
        buckets = bucket_re.findall(text)
        assert buckets and buckets[-1][0] == "+Inf"
        counts = [int(c) for _, c in buckets]
        assert counts == sorted(counts), "buckets not cumulative"
        assert counts[-1] == 12
        # sum divisor 1.0: microseconds survive as-is
        assert ('tfos_serving_queue_us_sum{executor="replica-0",'
                'model="linear",version="3"} 3100.0') in text
        assert ('tfos_serving_queue_us_count{executor="replica-0",'
                'model="linear",version="3"} 12') in text
        # flat raw keys never leak as their own families
        assert "serving_queue_us_le_50" not in families
        assert "tfos_serving_queue_us_sum_us_total" not in families

    def test_shed_reasons_become_one_labeled_family(self):
        text = observatory.render_prometheus(SERVING_SNAPSHOT)
        families, _ = _parse_exposition(text)
        assert families["tfos_serving_shed_total"] == "counter"
        for reason, val in (("overload", 1), ("deadline", 1),
                            ("shutdown", 0), ("internal", 0)):
            assert ('tfos_serving_shed_total{executor="replica-0",'
                    'reason="%s",model="linear",version="3"} %d'
                    % (reason, val)) in text
        # the legacy unsplit serving_shed counter is superseded: it must
        # not render as a second, double-counting family
        assert re.search(
            r'tfos_serving_shed_total\{executor="replica-0"\} ', text) \
            is None

    def test_slo_counters_render(self):
        text = observatory.render_prometheus(SERVING_SNAPSHOT)
        assert 'tfos_serving_slo_good_total{executor="replica-0"} 9' in text
        assert 'tfos_serving_slo_total_total{executor="replica-0"} 12' \
            in text
        # the model/version strings ride heartbeats but are not numbers:
        # they must never become sample lines
        assert "serving_model" not in text

    def test_tfos_up_liveness_gauge(self):
        text = observatory.render_prometheus(
            SERVING_SNAPSHOT, beat_ages={"replica-0": 0.2})
        families, _ = _parse_exposition(text)
        assert families["tfos_up"] == "gauge"
        assert 'tfos_up{executor="replica-0"} 1' in text
        # known to the snapshot but absent from beat_ages = fenced/silent
        assert 'tfos_up{executor="replica-1"} 0' in text

    def test_collect_slow_flattens_and_sorts(self):
        slow = observatory.collect_slow(SERVING_SNAPSHOT)
        assert [r["req"] for r in slow] == ["c0-4", "c2-0", "c1-2"]
        assert [r["executor"] for r in slow] == \
            ["replica-0", "replica-1", "replica-0"]
        assert observatory.collect_slow(SERVING_SNAPSHOT, limit=1)[0][
            "req"] == "c0-4"
        assert observatory.collect_slow({}) == []


class TestSlowEndpoint:
    def test_slow_json_schema_limit_and_concurrency(self):
        srv = observatory.ObservatoryServer(
            lambda: SERVING_SNAPSHOT, host="127.0.0.1")
        host, port = srv.start()
        base = "http://%s:%d" % (host, port)
        try:
            doc = json.loads(urllib.request.urlopen(
                base + "/slow", timeout=5).read().decode())
            assert set(doc) == {"time", "count", "slow"}
            assert doc["count"] == 3
            lats = [r["latency_us"] for r in doc["slow"]]
            assert lats == sorted(lats, reverse=True)
            for key in ("req", "flow", "latency_us", "queue_us",
                        "coalesce_us", "dispatch_us", "serialize_us",
                        "rows", "batch_rows", "model", "version",
                        "executor"):
                assert key in doc["slow"][0], key
            # count stays the fleet total; limit truncates the list only
            doc = json.loads(urllib.request.urlopen(
                base + "/slow?limit=1", timeout=5).read().decode())
            assert doc["count"] == 3 and len(doc["slow"]) == 1
            assert doc["slow"][0]["req"] == "c0-4"
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/slow?limit=bogus",
                                       timeout=5)
            assert exc.value.code == 400

            errs = []

            def hammer():
                try:
                    for _ in range(10):
                        d = json.loads(urllib.request.urlopen(
                            base + "/slow", timeout=5).read().decode())
                        assert d["count"] == 3
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=hammer) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not errs, errs
            # the index advertises the endpoint
            index = urllib.request.urlopen(
                base + "/", timeout=5).read().decode()
            assert "/slow" in index
        finally:
            srv.stop()
