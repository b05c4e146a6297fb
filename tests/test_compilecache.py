"""Warm-start compile plane tests: fingerprint gating, corruption
tolerance, trainer/serving AOT round trips (CPU mesh).

The invariant under test everywhere: a warm start is an optimization,
never a correctness dependency — every mismatched, corrupt, or drifted
artifact must degrade to plain JIT with ``compile_cache_fallback``
incremented, identical numerics, and no exception.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import checkpoint, compilecache, serving
from tensorflowonspark_tpu.models import get_model
from tensorflowonspark_tpu.parallel import build_mesh
from tensorflowonspark_tpu.train import Trainer


def _loss(params, batch, mask):
    pred = batch["x"] @ params["w"]
    err = (pred - batch["y"]) ** 2 * mask
    return err.sum() / jnp.maximum(mask.sum(), 1.0), pred


def _batch(n=8, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 2).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(x @ [1.0, -1.0])}


def _fresh_trainer(cache_dir, batch_size=8):
    return Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                   batch_size=batch_size, log_steps=1000,
                   aot_cache=cache_dir)


class TestAOTStore:
    def test_cold_then_warm_roundtrip(self, tmp_path):
        """Cold store compiles + persists; a second process-equivalent
        (fresh AOTCache over the same dir) loads without tracing and
        computes the same numbers."""
        cache = compilecache.AOTCache(str(tmp_path))
        fn = jax.jit(lambda x: x * 2 + 1)
        args = (jnp.arange(4, dtype=jnp.float32),)
        fp = compilecache.fingerprint(avals=args, extra={"program": "t"})

        before = compilecache.stats.aot_save
        compiled, verdict, _ = compilecache.load_or_compile(
            cache, "t", fp, fn, args)
        assert verdict == "compiled"
        assert compilecache.stats.aot_save == before + 1
        assert os.path.exists(cache.path("t"))

        warm = compilecache.AOTCache(str(tmp_path))
        loaded, verdict2, _ = compilecache.load_or_compile(
            warm, "t", fp, fn, args)
        assert verdict2 == "loaded"
        np.testing.assert_allclose(np.asarray(loaded(*args)),
                                   np.asarray(compiled(*args)))

    def test_absent_artifact_is_silent_miss(self, tmp_path):
        """A cold store is not a fallback: the counter must not move."""
        cache = compilecache.AOTCache(str(tmp_path))
        before = compilecache.stats.fallback
        assert cache.load("nope", {"format": 1}) is None
        assert compilecache.stats.fallback == before

    def test_aval_mismatch_falls_back(self, tmp_path):
        """Same program name, different batch aval -> the stored artifact
        is rejected (diff names 'avals') and the caller recompiles."""
        cache = compilecache.AOTCache(str(tmp_path))
        fn = jax.jit(lambda x: x.sum())
        small = (jnp.zeros((4,), jnp.float32),)
        big = (jnp.zeros((16,), jnp.float32),)
        fp_small = compilecache.fingerprint(avals=small)
        fp_big = compilecache.fingerprint(avals=big)
        assert fp_small != fp_big

        compilecache.load_or_compile(cache, "p", fp_small, fn, small)
        before = compilecache.stats.fallback
        compiled, verdict, _ = compilecache.load_or_compile(
            cache, "p", fp_big, fn, big)
        assert verdict == "compiled"          # clean recompile, no crash
        assert compilecache.stats.fallback == before + 1
        assert float(compiled(*big)) == 0.0

    def test_jaxlib_version_drift_falls_back(self, tmp_path):
        """An artifact from a different jaxlib must never deserialize:
        rewrite the stored JSON fingerprint header to a fabricated version
        — and replace the pickled payload with garbage, proving the load
        path rejects on the header BEFORE touching the payload."""
        cache = compilecache.AOTCache(str(tmp_path))
        fn = jax.jit(lambda x: x + 1)
        args = (jnp.zeros((2,), jnp.float32),)
        fp = compilecache.fingerprint(avals=args)
        compilecache.load_or_compile(cache, "v", fp, fn, args)

        with open(cache.path("v"), "rb") as f:
            blob = f.read()
        magic = compilecache._MAGIC
        header_end = blob.index(b"\n", len(magic))
        doc = json.loads(blob[len(magic):header_end])
        doc["jaxlib"] = "9.9.9-fake"
        with open(cache.path("v"), "wb") as f:
            f.write(magic + json.dumps(doc, sort_keys=True).encode()
                    + b"\n" + b"\x80\x04 not a pickle at all")

        before = compilecache.stats.fallback
        assert cache.load("v", fp) is None
        assert compilecache.stats.fallback == before + 1

    def test_remote_directory_rejected(self):
        """The store is local-filesystem only: a remote URL must raise
        instead of being abspath-mangled into a bogus local dir (which
        would LOOK shared while never warming another node)."""
        with pytest.raises(ValueError, match="remote"):
            compilecache.AOTCache("gs://bucket/ckpt/aot_executables")

    def test_program_identity_sees_closure_values(self):
        """The structural hash must separate programs an aval fingerprint
        cannot: a different constant in the loss body, and a different
        optimizer hyperparameter."""
        def loss_a(params, batch, mask):
            return (params * 2.0).sum(), None

        def loss_b(params, batch, mask):
            return (params * 3.0).sum(), None

        assert (compilecache.program_identity(loss_a)
                != compilecache.program_identity(loss_b))
        assert (compilecache.program_identity(optax.sgd(0.1))
                != compilecache.program_identity(optax.sgd(0.2)))
        # deterministic across equivalent reconstructions (what two
        # processes re-running the same code must agree on)
        assert (compilecache.program_identity(optax.sgd(0.1))
                == compilecache.program_identity(optax.sgd(0.1)))

    @pytest.mark.parametrize("poison", [b"", b"not a pickle",
                                        b"\x80\x04garbage"])
    def test_corrupt_artifact_falls_back(self, tmp_path, poison):
        cache = compilecache.AOTCache(str(tmp_path))
        with open(cache.path("c"), "wb") as f:
            f.write(poison)
        before = compilecache.stats.fallback
        assert cache.load("c", compilecache.fingerprint()) is None
        assert compilecache.stats.fallback == before + 1

    def test_truncated_artifact_falls_back(self, tmp_path):
        """A real artifact cut mid-payload (the torn-write shape the
        atomic rename prevents, simulated anyway) reads as corrupt."""
        cache = compilecache.AOTCache(str(tmp_path))
        fn = jax.jit(lambda x: x * 3)
        args = (jnp.zeros((2,), jnp.float32),)
        fp = compilecache.fingerprint(avals=args)
        compilecache.load_or_compile(cache, "t", fp, fn, args)
        with open(cache.path("t"), "rb") as f:
            blob = f.read()
        with open(cache.path("t"), "wb") as f:
            f.write(blob[:len(blob) // 3])
        before = compilecache.stats.fallback
        assert cache.load("t", fp) is None
        assert compilecache.stats.fallback == before + 1


class TestTrainerAOT:
    def test_warm_trainer_loads_and_matches(self, tmp_path):
        """Two trainers over one store: the first compiles, the second
        loads — and N steps land on bit-identical weights."""
        cache_dir = str(tmp_path / "aot")
        cold = _fresh_trainer(cache_dir)
        warm = _fresh_trainer(cache_dir)
        for step in range(5):
            cold.step(_batch(seed=step))
        assert cold._aot_verdicts.get("step") == "compiled"
        for step in range(5):
            warm.step(_batch(seed=step))
        assert warm._aot_verdicts.get("step") == "loaded"
        np.testing.assert_array_equal(np.asarray(cold.state.params["w"]),
                                      np.asarray(warm.state.params["w"]))

    def test_restored_state_survives_donated_warm_dispatch(self, tmp_path):
        """The warm-rejoin path proper: checkpoint-restored state donated
        into a DESERIALIZED executable.  Restored buffers are externally
        owned (orbax/tensorstore) and double-free under donation on a
        multi-device CPU mesh (jaxlib 0.4.37) — restore_latest must rewrite
        them into runtime-owned buffers before the loaded program runs."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = build_mesh()
        sh = NamedSharding(mesh, PartitionSpec("data"))

        def sharded_batch(seed):
            rng = np.random.RandomState(seed)
            mk = jax.make_array_from_process_local_data
            x = rng.rand(8, 2).astype(np.float32)
            return {"x": mk(sh, x), "y": mk(sh, x @ np.asarray([1.0, -1.0],
                                                               np.float32))}

        def trainer():
            return Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                           mesh=mesh, batch_size=8, log_steps=1000,
                           aot_cache=str(tmp_path / "aot"), donate=True)

        ckpt = checkpoint.CheckpointManager(str(tmp_path / "ckpt"),
                                            save_interval_steps=100)
        try:
            cold = trainer()
            cold.step(sharded_batch(0))
            cold.step(sharded_batch(1))
            ckpt.maybe_save(int(cold.state.step), cold.state, force=True)
            ckpt.wait_until_finished()

            warm = trainer()
            assert warm.restore_latest(ckpt, validate=True) == 2
            # several donated dispatches: the heap corruption (when present)
            # surfaces within the first few frees, as a hard crash
            for step in range(6):
                loss, _ = warm.step(sharded_batch(step))
            assert warm._aot_verdicts.get("step") == "loaded"
            assert np.isfinite(float(loss))
            assert int(warm.state.step) == 8
        finally:
            ckpt.close()

    def test_mesh_shape_in_fingerprint(self, tmp_path):
        """A trainer on a different mesh must not load the artifact —
        its fingerprint carries the (axis, extent) layout."""
        mesh1 = build_mesh()                      # all 8 virtual devices
        fp1 = compilecache.fingerprint(mesh=mesh1)
        fp2 = compilecache.fingerprint(mesh=None)
        assert fp1 != fp2
        devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
        mesh3 = jax.sharding.Mesh(devs, ("data", "model"))
        assert (compilecache.fingerprint(mesh=mesh3)["mesh"]
                != fp1["mesh"])

    def test_aval_drift_reverts_program_to_jit(self, tmp_path):
        """An AOT executable resolved for one batch shape must not poison
        a later call with another: the dispatch catches the executable's
        aval rejection and permanently reverts that program to JIT."""
        tr = _fresh_trainer(str(tmp_path / "aot"), batch_size=8)
        tr.step(_batch(n=8))
        assert tr._aot_exec.get("step") is not None
        loss, _ = tr.step(_batch(n=4))            # drifted aval: no crash
        assert np.isfinite(float(loss))
        assert tr._aot_exec.get("step") is None   # reverted for good

    def test_changed_optimizer_rejects_stale_executable(self, tmp_path):
        """The REVIEW.md stale-resume trap: same shapes, same store, but a
        different learning rate — the resumed trainer must NOT load the
        old serialized step program; it recompiles (fallback counted)."""
        cache_dir = str(tmp_path / "aot")
        cold = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                       batch_size=8, log_steps=1000, aot_cache=cache_dir)
        cold.step(_batch())
        assert cold._aot_verdicts.get("step") == "compiled"

        before = compilecache.stats.fallback
        resumed = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.05),
                          batch_size=8, log_steps=1000, aot_cache=cache_dir)
        resumed.step(_batch())
        assert resumed._aot_verdicts.get("step") == "compiled"
        assert compilecache.stats.fallback == before + 1

    def test_changed_loss_rejects_stale_executable(self, tmp_path):
        """Same shapes, edited loss body -> fingerprint mismatch on
        program_id, clean recompile with correct numerics."""
        def loss_v2(params, batch, mask):
            pred = batch["x"] @ params["w"]
            err = jnp.abs(pred - batch["y"]) * mask       # L1, not L2
            return err.sum() / jnp.maximum(mask.sum(), 1.0), pred

        cache_dir = str(tmp_path / "aot")
        _fresh_trainer(cache_dir).step(_batch())
        resumed = Trainer(loss_v2, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                          batch_size=8, log_steps=1000, aot_cache=cache_dir)
        loss, _ = resumed.step(_batch())
        assert resumed._aot_verdicts.get("step") == "compiled"
        assert np.isfinite(float(loss))

    def test_program_version_gates_load(self, tmp_path):
        """An explicit aot_program_version is part of the fingerprint:
        same code, bumped version -> no load."""
        cache_dir = str(tmp_path / "aot")
        kw = dict(batch_size=8, log_steps=1000, aot_cache=cache_dir)
        v1 = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                     aot_program_version="v1", **kw)
        v1.step(_batch())
        v2 = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                     aot_program_version="v2", **kw)
        v2.step(_batch())
        assert v2._aot_verdicts.get("step") == "compiled"
        same = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                       aot_program_version="v2", **kw)
        same.step(_batch())
        assert same._aot_verdicts.get("step") == "loaded"

    def test_trainer_without_store_unchanged(self):
        tr = Trainer(_loss, {"w": jnp.zeros((2,))}, optax.sgd(0.1),
                     batch_size=8, log_steps=1000)
        loss, _ = tr.step(_batch())
        assert np.isfinite(float(loss))
        assert tr._aot_verdicts == {}


class TestServingAOT:
    def test_warm_restart_zero_compiles(self, tmp_path):
        """A replica restart over the warm dir must reach first
        prediction with compile_count == 0 and identical outputs."""
        params = {"dense": {"kernel": np.asarray([[2.0], [3.0]], np.float32),
                            "bias": np.zeros((1,), np.float32)}}
        export_dir = str(tmp_path / "export")
        checkpoint.export_model(export_dir, params, "linear",
                                model_config={"features": 1},
                                input_signature={"x": [None, 2]},
                                model=get_model("linear"))
        warm_dir = str(tmp_path / "warm")

        cold = serving.ModelServer(export_dir, batch_size=4,
                                   warm_cache_dir=warm_dir)
        cold.warmup()
        assert cold.warmup_report["compiled"] > 0
        cold_out = cold.predict_feed({"x": np.ones((2, 2), np.float32)}, 4)

        warm = serving.ModelServer(export_dir, batch_size=4,
                                   warm_cache_dir=warm_dir)
        warm.warmup()
        assert warm.compile_count == 0
        assert warm.warmup_report["loaded"] == cold.warmup_report["compiled"]
        warm_out = warm.predict_feed({"x": np.ones((2, 2), np.float32)}, 4)
        np.testing.assert_allclose(np.asarray(warm_out["output"]),
                                   np.asarray(cold_out["output"]))

    def test_cacheless_server_unchanged(self, tmp_path):
        params = {"dense": {"kernel": np.ones((2, 1), np.float32),
                            "bias": np.zeros((1,), np.float32)}}
        export_dir = str(tmp_path / "export")
        checkpoint.export_model(export_dir, params, "linear",
                                model_config={"features": 1},
                                input_signature={"x": [None, 2]},
                                model=get_model("linear"))
        server = serving.ModelServer(export_dir, batch_size=4)
        server.warmup()
        assert server.compile_count > 0
        assert server.warmup_report["loaded"] == 0


class TestConfigure:
    def test_inert_without_dir(self, monkeypatch):
        monkeypatch.delenv(compilecache.CACHE_DIR_ENV, raising=False)
        monkeypatch.delenv(compilecache.JAX_CACHE_DIR_ENV, raising=False)
        assert compilecache.configure(None, register_feed=False) is None

    @pytest.fixture
    def config_updates(self, monkeypatch):
        """Record ``jax.config.update`` calls instead of making them (the
        suite's own jax config stays as it is) and restore the module's
        record of the configured directory."""
        updates = {}
        monkeypatch.setattr(jax.config, "update", updates.__setitem__)
        monkeypatch.setattr(compilecache, "_configured_dir", None)
        monkeypatch.setenv(compilecache.CACHE_DIR_ENV, "")
        return updates

    def test_environment_variable_wins_over_the_argument(
            self, monkeypatch, tmp_path, config_updates):
        """A cache placed from outside (JAX_COMPILATION_CACHE_DIR) is the
        cache of every process: ``cluster.run(compile_cache_dir=)`` and
        TFOS_COMPILE_CACHE_DIR yield to it, and nothing sets another."""
        placed = str(tmp_path / "placed")
        monkeypatch.setenv(compilecache.JAX_CACHE_DIR_ENV, placed)
        got = compilecache.configure(str(tmp_path / "from_cluster_run"),
                                     register_feed=False)
        assert got == placed and compilecache.configured_dir() == placed
        assert "jax_compilation_cache_dir" not in config_updates
        assert not (tmp_path / "from_cluster_run").exists()
        # the same through the cluster's meta
        assert compilecache.configure_from_meta(
            {"compile_cache_dir": str(tmp_path / "meta")}) == placed

    def test_argument_places_the_cache_when_the_environment_does_not(
            self, monkeypatch, tmp_path, config_updates):
        monkeypatch.delenv(compilecache.JAX_CACHE_DIR_ENV, raising=False)
        want = str(tmp_path / "arg")
        assert compilecache.configure(want, register_feed=False) == want
        assert config_updates["jax_compilation_cache_dir"] == want

    def test_counters_snapshot_shape(self):
        snap = compilecache.stats.counters_snapshot()
        assert set(snap) >= {"compile_cache_hit", "compile_cache_miss",
                             "compile_cache_fallback",
                             "compile_cache_aot_load",
                             "compile_cache_aot_save",
                             "compile_cache_dir_bytes_hwm"}
        assert all(isinstance(v, int) for v in snap.values())

    def test_fingerprint_names_the_diverged_field(self):
        a = compilecache.fingerprint(extra={"program": "x"})
        b = compilecache.fingerprint(extra={"program": "y"})
        diff = sorted(k for k in set(a) | set(b) if a.get(k) != b.get(k))
        assert diff == ["program"]


class TestThePlanesOwnBooks:
    """The listeners keep trace / lower / backend / retrieval time and the
    programs made, cache directory or none, each instant once."""

    def _watch(self):
        """The raw events beside the tallies: every backend-compile event,
        and the plain sum of the four durations (nested ones twice)."""
        from jax import monitoring

        raw = {"programs": 0, "secs": 0.0}

        def listener(event, duration, **kwargs):
            if event in compilecache._STAGE_OF or \
                    event == compilecache._RETRIEVAL_EVENT:
                raw["secs"] += duration
            if event == compilecache._BACKEND_EVENT:
                raw["programs"] += 1

        monitoring.register_event_duration_secs_listener(listener)
        return raw, lambda: monitoring.unregister_event_duration_listener(
            listener)

    def test_counts_a_jitted_function_with_no_cache_directory(self):
        """No cache directory is configured in this process: the tallies
        still count a jitted function's trace, lowering and compile, a
        function traced inside another's trace is not counted twice, and
        installing the listeners twice counts once."""
        assert compilecache.configured_dir() is None
        compilecache._install_listeners()
        compilecache._install_listeners()
        raw, stop = self._watch()
        try:
            before = compilecache.stats.tallies()

            @jax.jit
            def inner(x):
                return jnp.tanh(x) * 3

            @jax.jit
            def outer(x):
                return inner(x) + inner(x + 1) + jnp.where(x > 0, x, 2 * x)

            outer(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
            after = compilecache.stats.tallies()
        finally:
            stop()
        moved = {k: after[k] - before[k] for k in after}
        assert moved["compile_programs"] == raw["programs"] >= 1
        assert moved["compile_trace_us"] > 0
        assert moved["compile_lower_us"] > 0
        assert moved["compile_backend_us"] > 0
        assert moved["compile_cache_retrieval_us"] == 0
        own = sum(v for k, v in moved.items() if k.endswith("_us"))
        # the nested traces are in the raw sum twice and in the tallies once
        assert 0 < own < raw["secs"] * 1e6 + 1000
        # a second call of the same shape makes nothing
        outer(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
        assert compilecache.stats.tallies() == after

    def test_nested_events_are_booked_once(self):
        """By hand: a trace of 10 ms holding a whole small program (trace
        1, lower 2, compile 3 with a retrieval of 2 inside) books 4 on its
        own, and the five add up to the outermost 10."""
        stats = compilecache.stats
        before = stats.tallies()
        for event, secs in (
                (compilecache._TRACE_EVENT, None),
                (compilecache._TRACE_EVENT, None),
                (compilecache._TRACE_EVENT, 0.001),
                (compilecache._LOWER_EVENT, None),
                (compilecache._LOWER_EVENT, 0.002),
                (compilecache._BACKEND_EVENT, None),
                (compilecache._RETRIEVAL_EVENT, 0.002),
                (compilecache._BACKEND_EVENT, 0.003),
                (compilecache._TRACE_EVENT, 0.010)):
            if secs is None:
                compilecache._on_scalar(event, 0.0, fun_name="f")
            else:
                compilecache._on_duration(event, secs, fun_name="f")
        moved = {k: v - before[k] for k, v in stats.tallies().items()}
        assert moved == {"compile_trace_us": 1000 + 4000,
                         "compile_lower_us": 2000,
                         "compile_backend_us": 1000,
                         "compile_cache_retrieval_us": 2000,
                         "compile_programs": 1}

    def test_a_cache_hit_is_a_program_and_not_a_compile(self, tmp_path):
        """With a cache directory: a cold process misses and compiles, a
        warm one makes the same programs executable from the cache, every
        one a hit, with the read booked as retrieval and not as the
        compiler's time.  (In processes of their own: jax's persistent cache
        is a process's for life.)"""
        import subprocess
        import sys

        script = tmp_path / "job.py"
        script.write_text(
            "import json, sys\n"
            "import jax, jax.numpy as jnp\n"
            "from tensorflowonspark_tpu import compilecache as cc\n"
            "cc.configure(sys.argv[1], register_feed=False)\n"
            "f = jax.jit(lambda x: jnp.tanh(x) @ x.T)\n"
            "f(jnp.ones((8, 8))).block_until_ready()\n"
            "print(json.dumps(dict(cc.stats.counters_snapshot())))\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
        env.pop(compilecache.JAX_CACHE_DIR_ENV, None)
        runs = []
        for _ in range(2):
            done = subprocess.run(
                [sys.executable, str(script), str(tmp_path / "cache")],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, timeout=300)
            assert done.returncode == 0, done.stderr[-2000:]
            runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
        cold, warm = runs
        assert cold["compile_programs"] == warm["compile_programs"] >= 1
        assert cold["compile_cache_miss"] == cold["compile_programs"]
        assert cold["compile_cache_hit"] == 0
        assert cold["compile_cache_retrieval_us"] == 0
        assert warm["compile_cache_hit"] == warm["compile_programs"]
        assert warm["compile_cache_miss"] == 0
        assert warm["compile_cache_retrieval_us"] > 0
        assert warm["compile_trace_us"] > 0 and warm["compile_lower_us"] > 0

    def test_listen_waits_for_the_process_to_import_jax(self, tmp_path):
        """``node.run`` calls ``listen()`` before the user function: a
        worker that never touches jax does not import it for the books'
        sake, and one that does is counted from its first program."""
        import subprocess
        import sys

        script = tmp_path / "job.py"
        script.write_text(
            "import json, sys\n"
            "from tensorflowonspark_tpu import compilecache as cc\n"
            "cc.listen()\n"
            "early = 'jax' in sys.modules or cc._listeners_installed\n"
            "import jax, jax.numpy as jnp\n"
            "on = cc._listeners_installed\n"
            "jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()\n"
            "cc.listen()\n"
            "print(json.dumps([early, on, cc.stats.tallies()]))\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        done = subprocess.run(
            [sys.executable, str(script)], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root))
        assert done.returncode == 0, done.stderr[-2000:]
        early, on, tallies = json.loads(done.stdout.strip().splitlines()[-1])
        assert early is False and on is True
        assert tallies["compile_programs"] >= 1
        assert tallies["compile_trace_us"] > 0

    def test_listen_sends_the_books_with_the_heartbeats_and_the_flight_record(
            self, monkeypatch):
        """``listen()`` is what a node calls for a process that hosts jax:
        no directory is needed, the tallies ride the stats feed, and the
        record of the step programs made is a flight source."""
        from tensorflowonspark_tpu import node, telemetry

        monkeypatch.setattr(compilecache, "_feed_registered", False)
        monkeypatch.setattr(node, "_feeds", [])
        monkeypatch.setattr(telemetry, "_flight_sources", {})
        compilecache.listen()
        assert [ref() for ref in node._feeds] == [compilecache.stats]
        assert set(compilecache.stats.counters_snapshot()) >= set(
            compilecache.stats.tallies())
        compilecache.stats.record.append({"program": "step"})
        try:
            assert telemetry._flight_sources["compile_programs"]() == [
                {"program": "step"}]
        finally:
            compilecache.stats.record.clear()
