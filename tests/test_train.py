"""Trainer / metrics / checkpoint tests (CPU mesh)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tensorflowonspark_tpu import checkpoint as ckpt_mod
from tensorflowonspark_tpu import metrics as metrics_mod
from tensorflowonspark_tpu.train import Trainer
from tensorflowonspark_tpu.parallel import build_mesh, batch_sharding


def _linear_loss(params, batch, mask):
    pred = batch["x"] @ params["w"] + params["b"]
    err = (pred - batch["y"]) ** 2 * mask
    return err.sum() / jnp.maximum(mask.sum(), 1.0), pred


TRUE_W = np.array([3.14, 1.618], dtype=np.float32)  # reference test weights
                                                    # (test_pipeline.py:17-25)


def _make_batch(mesh, n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 2).astype(np.float32)
    y = x @ TRUE_W
    sharding = batch_sharding(mesh)
    return {"x": jax.device_put(x, sharding), "y": jax.device_put(y, sharding)}


class TestTrainer:
    def test_converges_to_known_weights(self):
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        tr = Trainer(_linear_loss, params, optax.adam(0.1), mesh=mesh,
                     batch_size=64, log_steps=50)
        for step in range(300):
            loss, _ = tr.step(_make_batch(mesh, seed=step))
        assert float(loss) < 1e-3
        w = np.asarray(tr.state.params["w"])
        np.testing.assert_allclose(w, TRUE_W, atol=0.05)

    def test_mask_excludes_padded_rows(self):
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        tr = Trainer(_linear_loss, params, optax.sgd(0.0), mesh=mesh)
        batch = _make_batch(mesh)
        # poison the padded rows: with a correct mask they cannot affect loss
        y = np.asarray(batch["y"]).copy()
        y[32:] = 1e6
        batch["y"] = jax.device_put(y, batch["x"].sharding)
        mask = np.zeros((64,), np.float32)
        mask[:32] = 1.0
        loss_masked, _ = tr.step(batch, jax.device_put(mask, batch["x"].sharding))
        assert float(loss_masked) < 1e3


class TestMetrics:
    def test_time_history_throughput(self, cpu_peaks):
        th = metrics_mod.TimeHistory(batch_size=32, log_steps=2,
                                     step_flops=1e6, num_devices=8)
        th.on_train_begin()
        for _ in range(6):
            th.on_step_end()
        th.on_train_end()
        stats = th.build_stats(loss=0.5)
        assert stats["global_steps"] == 6
        assert stats["avg_exp_per_second"] > 0
        assert stats["loss"] == 0.5
        assert "mfu" in stats  # from the peaks this test passed in

    def test_cpu_run_reports_no_utilization(self):
        # no nominal CPU row: a run without an accelerator prints no MFU
        assert "cpu" not in metrics_mod.PEAK_FLOPS
        assert metrics_mod.peak_flops_per_device() is None
        assert metrics_mod.mfu_from_step_time(1e9, 0.01) is None

    def test_unknown_accelerator_kind_raises(self, monkeypatch):
        # an accelerator the table has no row for is an error, never a
        # default and never a silent None
        class Chip:
            platform = "tpu"
            device_kind = "TPU v9 imaginary"

        monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            metrics_mod.peak_flops_per_device()

    def test_v5e_row_is_keyed_by_the_kind_the_chip_reports(self, monkeypatch):
        # jax.devices()[0].device_kind on the attached v5e (chip_smoke.py
        # prints it and fails if the lookup misses)
        class Chip:
            platform = "tpu"
            device_kind = "TPU v5 lite"

        monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
        assert metrics_mod.peak_flops_per_device() == 197e12

    def test_peak_flops_exact_match_no_prefix_swallow(self):
        # "tpu v5" must not swallow "tpu v5 lite"/"tpu v5p" (2.3x MFU error)
        assert metrics_mod.PEAK_FLOPS["tpu v5 lite"] == 197e12
        assert metrics_mod.PEAK_FLOPS["tpu v5e"] == 197e12
        assert metrics_mod.PEAK_FLOPS["tpu v5p"] == 459e12
        assert metrics_mod.PEAK_FLOPS["tpu v5"] == 459e12
        # lookup is exact-match on the full device_kind string
        assert "tpu v4" in metrics_mod.PEAK_FLOPS
        assert metrics_mod.PEAK_FLOPS.get("tpu v5 lite x") is None

    def test_mfu_physically_possible_on_real_trainer(self, cpu_peaks):
        # Regression for >100%-MFU: window timing must sync on device
        # completion, so MFU from a real trainer run is always <= 1.
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        tr = Trainer(_linear_loss, params, optax.adam(0.1), mesh=mesh,
                     batch_size=64, log_steps=5,
                     step_flops_override=6 * 3 * 64 / mesh.size)
        loss = None
        for step in range(20):
            loss, _ = tr.step(_make_batch(mesh, seed=step))
        tr.history.on_train_end(loss)
        stats = tr.history.build_stats(loss=float(loss))
        assert 0.0 < stats["mfu"] <= 1.0, stats
        # per-window MFU too: recompute from the timestamp log
        log = tr.history.timestamp_log
        for (s0, t0), (s1, t1) in zip(log, log[1:]):
            mfu = tr.history.mfu((t1 - t0) / (s1 - s0))
            if mfu is not None:
                assert mfu <= 1.0, (s0, s1, mfu)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        state = {"w": jnp.arange(4.0), "step": jnp.asarray(7)}
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                         save_interval_steps=2)
        assert not mgr.maybe_save(1, state)   # off-interval
        assert mgr.maybe_save(2, state)
        mgr.wait_until_finished()
        abstract = jax.tree_util.tree_map(np.zeros_like, state)
        restored, step = mgr.restore_latest(abstract)
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(4.0))
        mgr.close()

    def test_interval_zero_means_explicit_saves_only(self, tmp_path):
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "c0"),
                                         save_interval_steps=0)
        assert not mgr.maybe_save(1, {"a": jnp.ones(1)})
        assert mgr.maybe_save(1, {"a": jnp.ones(1)}, force=True)
        mgr.close()

    def test_non_chief_participates_in_collective_save(self, tmp_path):
        # orbax save is a cross-process collective: every host must enter it
        # (gating the call on chiefness deadlocks multi-host runs); orbax
        # itself restricts the write to the primary host.
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "c2"), is_chief=False)
        assert mgr.maybe_save(100, {"a": jnp.ones(1)}, force=True)
        mgr.close()

    def test_restore_latest_valid_falls_back_past_corrupt_newest(self, tmp_path):
        """A garbled newest checkpoint (bit rot, writer preempted
        mid-finalize) must not crash recovery: restore_latest_valid
        quarantines it and restores the previous retained step."""
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                         save_interval_steps=1, max_to_keep=3)
        for step in (1, 2, 3):
            assert mgr.maybe_save(step, {"w": jnp.arange(4.0) * step},
                                  force=True)
        mgr.wait_until_finished()
        step_dir = os.path.join(mgr.directory, "3")
        for root, _, files in os.walk(step_dir):
            for fname in files:
                with open(os.path.join(root, fname), "wb") as f:
                    f.write(b"\xde\xad\xbe\xef")
        abstract = jax.tree_util.tree_map(np.zeros_like, {"w": jnp.zeros(4)})
        restored, step = mgr.restore_latest_valid(abstract)
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(4.0) * 2)
        # the bad step was renamed out of orbax's listing, kept for forensics
        assert not os.path.exists(step_dir)
        assert os.path.isdir(step_dir + ".corrupt")
        mgr.close()

    def test_restore_latest_valid_empty_when_nothing_valid(self, tmp_path):
        """Every retained step corrupt → (None, None): recovery starts from
        scratch instead of crashing on an operator-intervention wall."""
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                         save_interval_steps=1)
        assert mgr.maybe_save(1, {"w": jnp.ones(2)}, force=True)
        mgr.wait_until_finished()
        step_dir = os.path.join(mgr.directory, "1")
        for root, _, files in os.walk(step_dir):
            for fname in files:
                with open(os.path.join(root, fname), "wb") as f:
                    f.write(b"junk")
        abstract = jax.tree_util.tree_map(np.zeros_like, {"w": jnp.zeros(2)})
        assert mgr.restore_latest_valid(abstract) == (None, None)
        assert os.path.isdir(step_dir + ".corrupt")
        mgr.close()

    def test_corrupt_checkpoint_injector_fires_once(self, tmp_path, monkeypatch):
        """The chaos hook in maybe_save garbles exactly ONE step (the fault
        fires once), so later saves stay clean and fallback recovery works."""
        import json as json_mod

        from tensorflowonspark_tpu import fault as fault_mod

        monkeypatch.setenv(fault_mod.FAULT_SPEC_ENV,
                           json_mod.dumps({"corrupt_checkpoint": True}))
        mgr = ckpt_mod.CheckpointManager(str(tmp_path / "ckpt"),
                                         save_interval_steps=1)
        assert mgr.maybe_save(1, {"w": jnp.ones(2)}, force=True)   # garbled
        assert mgr.maybe_save(2, {"w": jnp.ones(2) * 2}, force=True)  # clean
        mgr.wait_until_finished()
        abstract = jax.tree_util.tree_map(np.zeros_like, {"w": jnp.zeros(2)})
        restored, step = mgr.restore_latest_valid(abstract)
        assert step == 2  # newest save survived: the fault fired once
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.ones(2) * 2)
        mgr.close()

    def test_export_load_model(self, tmp_path):
        params = {"dense": {"kernel": jnp.ones((2, 3))}}
        ckpt_mod.export_model(str(tmp_path / "exp"), params, "mnist_cnn",
                              model_config={"num_classes": 10})
        loaded, desc = ckpt_mod.load_model(str(tmp_path / "exp"))
        assert desc["model_name"] == "mnist_cnn"
        assert desc["model_config"]["num_classes"] == 10
        np.testing.assert_array_equal(
            np.asarray(loaded["dense"]["kernel"]), np.ones((2, 3)))


class TestMultiStep:
    def test_multi_step_matches_single_steps(self):
        """K steps via one lax.scan dispatch must produce the same params and
        loss trajectory as K sequential single steps."""
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        opt = optax.sgd(0.1, momentum=0.9)
        tr_single = Trainer(_linear_loss, params, opt, mesh=mesh,
                            batch_size=16, log_steps=100)
        tr_multi = Trainer(_linear_loss, params, opt, mesh=mesh,
                           batch_size=16, log_steps=100)

        batches = [_make_batch(mesh, n=16, seed=s) for s in range(4)]
        for b in batches:
            last_single, _ = tr_single.step(b)

        scan_sharding = mesh_mod.scan_batch_sharding(mesh)

        def stack(*xs):
            return jax.device_put(np.stack([np.asarray(x) for x in xs]),
                                  scan_sharding)

        stacked = jax.tree_util.tree_map(stack, *batches)
        masks = jax.device_put(np.ones((4, 16), np.float32), scan_sharding)
        last_multi = tr_multi.multi_step(stacked, masks)

        np.testing.assert_allclose(float(last_single), float(last_multi),
                                   rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            tr_single.state.params, tr_multi.state.params)
        assert tr_multi.history.global_steps == 4

    def test_multi_step_donated_matches_single_steps(self):
        """donate_batches=True (device-assembled stacks handed over to the
        allocator) must be numerically identical to the undonated scan AND
        to K sequential single steps.  Fresh stacks per call: donation
        invalidates the input buffers."""
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        opt = optax.sgd(0.1, momentum=0.9)
        tr_single = Trainer(_linear_loss, params, opt, mesh=mesh,
                            batch_size=16, log_steps=100)
        tr_donated = Trainer(_linear_loss, params, opt, mesh=mesh,
                             batch_size=16, log_steps=100)
        scan_sharding = mesh_mod.scan_batch_sharding(mesh)

        def fresh_group(seeds):
            batches = [_make_batch(mesh, n=16, seed=s) for s in seeds]
            stacked = jax.tree_util.tree_map(
                lambda *xs: jax.device_put(
                    np.stack([np.asarray(x) for x in xs]), scan_sharding),
                *batches)
            masks = jax.device_put(
                np.ones((len(seeds), 16), np.float32), scan_sharding)
            return batches, stacked, masks

        for seeds in ([0, 1, 2, 3], [4, 5, 6, 7]):
            batches, stacked, masks = fresh_group(seeds)
            for b in batches:
                last_single, _ = tr_single.step(b)
            last_donated = tr_donated.multi_step(stacked, masks,
                                                 donate_batches=True)
            # donated: the stacks' buffers are gone now — deleted, not stale
            assert stacked["x"].is_deleted()
            assert masks.is_deleted()

        np.testing.assert_allclose(float(last_single), float(last_donated),
                                   rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            tr_single.state.params, tr_donated.state.params)
        assert tr_donated.history.global_steps == 8

    def test_multi_step_no_host_sync_inside_window(self):
        """Tentpole invariant: between TimeHistory window boundaries a
        multi_step dispatch performs NO device-to-host transfer — loss and
        grad-norm reductions stay on device as O(1) scalars.  Proven by
        running warm dispatches under transfer_guard('disallow') and
        checking no boundary closed mid-guard."""
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        writer = _CaptureWriter()
        tr = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                     batch_size=16, log_steps=100, summary_writer=writer)
        scan_sharding = mesh_mod.scan_batch_sharding(mesh)

        def group(seeds):
            batches = [_make_batch(mesh, n=16, seed=s) for s in seeds]
            stacked = jax.tree_util.tree_map(
                lambda *xs: jax.device_put(
                    np.stack([np.asarray(x) for x in xs]), scan_sharding),
                *batches)
            masks = jax.device_put(
                np.ones((len(seeds), 16), np.float32), scan_sharding)
            return stacked, masks

        tr.multi_step(*group([0, 1]))       # warm-up: compile outside guard
        boundaries_before = len(tr.history.timestamp_log)
        with jax.transfer_guard_device_to_host("disallow"):
            for s in (2, 4, 6):
                tr.multi_step(*group([s, s + 1]))
        # mid-window: no boundary closed, nothing synced, nothing written
        assert len(tr.history.timestamp_log) == boundaries_before
        assert not [p for p in writer.points if "loss" in p[0]]
        assert tr.history.global_steps == 8
        # the window closes OUTSIDE the guard and flushes the buffered curve
        tr.history.on_train_end(tr._health_grad_norm)
        steps = [s for sc, s in writer.points if "loss" in sc]
        assert steps == list(range(1, 9))

    def test_multi_step_mfu_accounting(self):
        """The stated count is one optimizer step's, whatever K a dispatch
        runs: a K-step group advances the recorder K steps at that count."""
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        tr = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                     batch_size=16, log_steps=8, step_flops_override=36.0)
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        scan_sharding = mesh_mod.scan_batch_sharding(mesh)
        b = _make_batch(mesh, n=16)
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                np.stack([np.asarray(x)] * 2), scan_sharding), b)
        masks = jax.device_put(np.ones((2, 16), np.float32), scan_sharding)
        tr.multi_step(stacked, masks)
        assert tr.history.global_steps == 2
        assert tr.history.step_flops == 36.0


class TestGradAccum:
    def test_accum_matches_full_batch(self):
        """accum_steps=4 must produce exactly the full-batch update, padded
        rows included (mask-weighted microbatch averaging)."""
        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        opt = optax.sgd(0.1, momentum=0.9)
        full = Trainer(_linear_loss, params, opt, mesh=mesh, batch_size=32)
        accum = Trainer(_linear_loss, params, opt, mesh=mesh, batch_size=32,
                        accum_steps=4)
        b = _make_batch(mesh, n=32)
        mask = np.ones((32,), np.float32)
        mask[27:] = 0.0  # padded tail inside the final microbatch
        mask = jnp.asarray(mask)
        for _ in range(3):
            loss_f, _ = full.step(b, mask)
            loss_a, _ = accum.step(b, mask)
        np.testing.assert_allclose(float(loss_f), float(loss_a), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-7),
            full.state.params, accum.state.params)

    def test_accum_threads_extra_state(self):
        """Non-trainable collections update once per microbatch, and aux
        comes back without the extra_state key."""
        mesh = build_mesh()

        def loss_with_extra(params, extra, batch, mask):
            pred = batch["x"] @ params["w"]
            err = ((pred - batch["y"]) ** 2 * mask).sum() / \
                jnp.maximum(mask.sum(), 1.0)
            return err, {"extra_state": {"count": extra["count"] + 1},
                         "seen": mask.sum()}

        tr = Trainer(loss_with_extra, {"w": jnp.zeros((2,))},
                     optax.sgd(0.1), mesh=mesh, batch_size=32,
                     extra_state={"count": jnp.zeros((), jnp.int32)},
                     accum_steps=4)
        b = _make_batch(mesh, n=32)
        b = {"x": b["x"], "y": b["y"]}
        _, aux = tr.step(b)
        assert int(tr.state.extra["count"]) == 4  # once per microbatch
        assert "extra_state" not in aux
        assert float(aux["seen"]) == 8.0  # last microbatch's aux

    def test_accum_rejects_indivisible_batch(self):
        mesh = build_mesh()
        tr = Trainer(_linear_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                     optax.sgd(0.1), mesh=mesh, batch_size=24, accum_steps=5)
        with pytest.raises(ValueError, match="divisible by accum_steps"):
            tr.step(_make_batch(mesh, n=24))

    def test_accum_mfu_accounting_not_undercounted(self):
        """The stated count is the full batch's optimizer step: accum and
        no-accum trainers report the same step_flops, never a
        microbatch's share."""
        mesh = build_mesh()

        def big_loss(params, batch, mask):
            pred = batch["x"] @ params["w"]          # (B,128)@(128,128)
            err = ((pred - 1.0) ** 2).mean(-1) * mask
            return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

        params = {"w": jnp.zeros((128, 128))}
        sharding = batch_sharding(mesh)
        b = {"x": jax.device_put(
            np.random.RandomState(0).rand(32, 128).astype(np.float32),
            sharding)}
        count = 6 * 128 * 128 * 32 / mesh.size
        base = Trainer(big_loss, params, optax.sgd(0.1), mesh=mesh,
                       batch_size=32, step_flops_override=count)
        acc = Trainer(big_loss, params, optax.sgd(0.1), mesh=mesh,
                      batch_size=32, accum_steps=4,
                      step_flops_override=count)
        base.step(b)
        acc.step(b)
        assert base.history.step_flops == acc.history.step_flops == count


class _CaptureWriter:
    """SummaryWriter stand-in: records (scalars, step) pairs."""

    def __init__(self):
        self.points = []

    def add_scalars(self, scalars, step):
        self.points.append((dict(scalars), step))

    def flush(self):
        pass


class TestPerStepLossCurve:
    def test_multi_step_writes_dense_loss_curve(self):
        """Under K-steps-per-dispatch, the TensorBoard loss curve must keep
        PER-STEP density: a K=4 group with log_steps=4
        yields four loss points at steps 1..4, matching the single-step
        trajectory, not one point per dispatch."""
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        writer = _CaptureWriter()
        tr = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                     batch_size=16, log_steps=4, summary_writer=writer)
        tr_ref = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                         batch_size=16, log_steps=100)

        batches = [_make_batch(mesh, n=16, seed=s) for s in range(4)]
        ref_losses = [float(tr_ref.step(b)[0]) for b in batches]

        scan_sharding = mesh_mod.scan_batch_sharding(mesh)

        def stack(*xs):
            return jax.device_put(np.stack([np.asarray(x) for x in xs]),
                                  scan_sharding)

        stacked = jax.tree_util.tree_map(stack, *batches)
        masks = jax.device_put(np.ones((4, 16), np.float32), scan_sharding)
        last = tr.multi_step(stacked, masks)

        loss_points = [(s, sc["loss"]) for sc, s in writer.points
                       if "loss" in sc]
        assert [s for s, _ in loss_points] == [1, 2, 3, 4]
        np.testing.assert_allclose([v for _, v in loss_points], ref_losses,
                                   rtol=1e-5)
        np.testing.assert_allclose(float(last), ref_losses[-1], rtol=1e-5)

    def test_train_end_flushes_curve_tail(self):
        """Steps since the last window boundary still reach the curve when
        training ends mid-window."""
        from tensorflowonspark_tpu.parallel import mesh as mesh_mod

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        writer = _CaptureWriter()
        tr = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                     batch_size=16, log_steps=100, summary_writer=writer)
        batches = [_make_batch(mesh, n=16, seed=s) for s in range(2)]
        scan_sharding = mesh_mod.scan_batch_sharding(mesh)

        def stack(*xs):
            return jax.device_put(np.stack([np.asarray(x) for x in xs]),
                                  scan_sharding)

        stacked = jax.tree_util.tree_map(stack, *batches)
        masks = jax.device_put(np.ones((2, 16), np.float32), scan_sharding)
        last = tr.multi_step(stacked, masks)
        assert not [p for p in writer.points if "loss" in p[0]]  # buffered
        tr.history.on_train_end(last)
        steps = [s for sc, s in writer.points if "loss" in sc]
        assert steps == [1, 2]


class TestEvaluateCacheKey:
    def test_fresh_closures_share_cache_under_key(self):
        """evaluate(cache_key=...) dedups fresh metric closures: two calls
        with different function objects but one key compile once and
        agree."""
        from tensorflowonspark_tpu.parallel.infeed import ShardedFeed

        mesh = build_mesh()
        params = {"w": jnp.zeros((2,)), "b": jnp.zeros(())}
        tr = Trainer(_linear_loss, params, optax.sgd(0.1), mesh=mesh,
                     batch_size=16, log_steps=100)

        class _ListFeed:
            def __init__(self, batches):
                self._batches = batches

            def batches(self, drain=None):
                return iter(self._batches)

        batch = _make_batch(mesh, n=16, seed=0)
        mask = jnp.ones((16,), jnp.float32)
        feed = _ListFeed([(batch, mask)])

        def make_metric():
            def metric(params, batch, mask):
                pred = batch["x"] @ params["w"] + params["b"]
                err = ((pred - batch["y"]) ** 2) * mask
                return {"mse": err.sum()}, mask.sum()
            return metric

        r1 = tr.evaluate(_ListFeed([(batch, mask)]), make_metric(),
                         cache_key="mse")
        r2 = tr.evaluate(_ListFeed([(batch, mask)]), make_metric(),
                         cache_key="mse")
        assert list(tr._eval_cache) == ["mse"]
        assert r1 == r2 and "mse" in r1


def test_step_keeps_an_explicit_param_sharding():
    """The state comes back from every step program laid out as it went in:
    an explicit ``param_sharding`` survives training (it used to be gone
    after one step, and the second call compiled a second program)."""
    from tensorflowonspark_tpu import train as train_mod
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod, tp

    mesh = build_mesh({"data": 2, "tensor": 2}, devices=jax.devices()[:4])

    def loss(params, batch, mask):
        h = jnp.tanh(batch["x"] @ params["w1"])
        err = ((h @ params["w2"] - batch["y"]) ** 2).mean(-1) * mask
        return err.sum() / jnp.maximum(mask.sum(), 1.0), {}

    params = {"w1": jnp.ones((16, 32)) * 0.1, "w2": jnp.ones((32, 8)) * 0.1}
    optimizer = optax.adam(1e-2)
    abstract = jax.eval_shape(
        lambda p: train_mod.TrainState(jnp.zeros((), jnp.int32), p,
                                       optimizer.init(p)), params)
    tr = Trainer(loss, params, optimizer, mesh=mesh, batch_size=8,
                 param_sharding=tp.tp_param_shardings(abstract, mesh))

    def specs(state):
        return [str(x.sharding.spec)
                for x in jax.tree_util.tree_leaves(state)]

    before = specs(tr.state)
    assert any("tensor" in spec for spec in before)
    shard = mesh_mod.batch_sharding(mesh)
    batch = {"x": jax.device_put(np.ones((8, 16), np.float32), shard),
             "y": jax.device_put(np.ones((8, 8), np.float32), shard)}
    tr.step(batch)
    assert specs(tr.state) == before
    scan = mesh_mod.scan_batch_sharding(mesh)
    stack = {k: jax.device_put(np.stack([np.asarray(v)] * 2), scan)
             for k, v in batch.items()}
    tr.multi_step(stack, jax.device_put(np.ones((2, 8), np.float32), scan))
    assert specs(tr.state) == before
    assert tr._train_step._cache_size() == 1  # one program, not two


@pytest.fixture
def lowered():
    """Names of the programs jax lowers while the test runs, from its
    monitoring events (``benchmark/harness.py``'s ``CompileWatch`` counts
    the same events).  A lowering is counted, not a backend compile: a hit
    in a persistent cache skips the compile and not the lowering."""
    from jax._src import monitoring

    names = []

    def listener(event, duration, fun_name=None, **kwargs):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            names.append(fun_name)

    monitoring.register_event_duration_secs_listener(listener)
    yield names
    monitoring.unregister_event_duration_listener(listener)


def _stacked(batch, k, mesh):
    from tensorflowonspark_tpu.parallel import mesh as mesh_mod

    scan = mesh_mod.scan_batch_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(np.stack([np.asarray(x)] * k), scan), batch)


@pytest.mark.parametrize("stated", [True, False], ids=["stated", "unstated"])
@pytest.mark.parametrize("program,accum_steps,k", [
    ("train_step", 1, 1), ("train_step_accum", 2, 1), ("multi", 1, 2)],
    ids=["step", "step_accum2", "multi_step2"])
def test_first_dispatch_compiles_only_the_program_it_runs(
        lowered, program, accum_steps, k, stated):
    """A Trainer's first dispatch lowers the program it runs and no other,
    whether or not its owner stated a step's FLOPs."""
    mesh = build_mesh()
    tr = Trainer(_linear_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                 optax.sgd(0.1), mesh=mesh, batch_size=16,
                 accum_steps=accum_steps,
                 step_flops_override=36.0 if stated else None)
    batch = _make_batch(mesh, n=16)
    mask = jax.device_put(np.ones((16,), np.float32), batch_sharding(mesh))
    if k > 1:
        batch, mask = _stacked((batch, mask), k, mesh)
    del lowered[:]   # what building the trainer and the batch lowered
    if k > 1:
        tr.multi_step(batch, mask)
    else:
        tr.step(batch, mask)
    assert lowered == ["jit(%s)" % program]
    assert tr.history.step_flops == (36.0 if stated else None)


@pytest.mark.parametrize("stated", [True, False], ids=["stated", "unstated"])
def test_utilisation_gauges_follow_the_stated_count(cpu_peaks, stated):
    """``train_mfu_pct_max`` and ``train_flops_per_sec_max`` are the stated
    count over the peak over a closed window's device-synced step time, and
    are absent, with ``build_stats``' ``mfu``, when no count was stated."""
    mesh = build_mesh()
    count = 1e6   # large enough for the gauge's four places of a percent
    tr = Trainer(_linear_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                 optax.sgd(0.1), mesh=mesh, batch_size=64, log_steps=2,
                 step_flops_override=count if stated else None)
    batch = _make_batch(mesh)
    for _ in range(4):
        loss, _ = tr.step(batch)
    tr.history.on_train_end(loss)
    tr._account_windows()
    snap = tr.counters_snapshot()
    stats = tr.history.build_stats()
    assert snap["step_ms_count"] == 4     # two windows closed either way
    if not stated:
        assert tr.history.step_flops is None
        assert "train_mfu_pct_max" not in snap
        assert "train_flops_per_sec_max" not in snap
        assert "mfu" not in stats
        return
    (s0, t0), (s1, t1) = tr.history.timestamp_log[-2:]
    step_s = (t1 - t0) / (s1 - s0)
    assert snap["train_flops_per_sec_max"] == pytest.approx(count / step_s)
    assert snap["train_mfu_pct_max"] == pytest.approx(
        100 * count / 1e11 / step_s, rel=1e-3, abs=1e-4)
    assert stats["mfu"] > 0


def test_counters_snapshot_never_compiles_or_syncs(lowered):
    """A heartbeat's snapshot, taken from another thread while a step is in
    flight, returns without waiting for the step and lowers nothing."""
    import threading

    mesh = build_mesh()
    release = threading.Event()

    def hold(x):
        release.wait(timeout=30)   # the step stays in flight until released
        return x

    def held_loss(params, batch, mask):
        loss, pred = _linear_loss(params, batch, mask)
        # the mask carries no gradient, so the callback runs in the step's
        # primal computation only
        one = jax.pure_callback(
            hold, jax.ShapeDtypeStruct((), jnp.float32), mask[0])
        return loss * one, pred

    tr = Trainer(held_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                 optax.sgd(0.1), mesh=mesh, batch_size=64, log_steps=1000,
                 step_flops_override=1.0)
    batch = _make_batch(mesh)
    release.set()
    jax.block_until_ready(tr.step(batch)[0])   # compiled, history built
    release.clear()
    del lowered[:]
    snaps = []
    try:
        loss, _ = tr.step(batch)               # returns with the step held
        reader = threading.Thread(
            target=lambda: snaps.append((tr.counters_snapshot(),
                                         loss.is_ready())))
        reader.start()
        reader.join(timeout=20)
        assert not reader.is_alive(), "the snapshot waited for the step"
    finally:
        release.set()
    jax.block_until_ready(loss)
    (snap, step_was_done), = snaps
    assert not step_was_done
    assert snap["dispatch_count"] == 0 and snap["train_steps_total"] == 2
    assert lowered == []


def _compile_watching_trainer():
    from tensorflowonspark_tpu import compilecache

    compilecache._install_listeners()   # what node.run does for a worker
    mesh = build_mesh()
    tr = Trainer(_linear_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                 optax.sgd(0.1), mesh=mesh, batch_size=64, log_steps=1000)
    return mesh, tr, compilecache.stats


def test_a_batch_of_another_shape_is_named_as_a_recompile(tmp_path):
    """Which program, at which step: the first dispatch makes ``step`` and
    is no recompile; a batch of another shape makes it again, and the record
    names the program, the step it happened at and what it cost, counts
    ``train_recompiles_total`` and, with telemetry on, emits the instant
    ``compile/program``."""
    from tensorflowonspark_tpu import telemetry

    tracer = telemetry.configure(True, str(tmp_path))
    try:
        mesh, tr, stats = _compile_watching_trainer()
        stats.record.clear()
        for _ in range(3):
            tr.step(_make_batch(mesh, n=64))
        first, = list(stats.record)
        assert first["program"] == "step" and first["steps_total"] == 0
        assert first["recompile"] is False
        assert first["compile_programs"] >= 1
        assert "train_recompiles_total" not in tr.counters_snapshot()
        tr.step(_make_batch(mesh, n=32))          # the fourth step
        again = stats.record[-1]
        assert len(stats.record) == 2
        assert again["program"] == "step" and again["steps_total"] == 3
        assert again["recompile"] is True
        assert again["compile_programs"] >= 1
        assert again["compile_trace_us"] > 0 and again["compile_lower_us"] > 0
        assert again["compile_backend_us"] > 0
        assert again["compile_cache_retrieval_us"] >= 0
        snap = tr.counters_snapshot()
        assert snap["train_recompiles_total"] == 1
        assert snap["compile_programs"] == stats.programs
        path = tracer.flush()
    finally:
        telemetry.configure(False)
    with open(path) as f:
        instants = [e for e in json.load(f)["traceEvents"]
                    if e["name"] == "compile/program"]
    assert [(e["args"]["program"], e["args"]["steps_total"],
             e["args"]["recompile"]) for e in instants] == [
        ("step", 0, False), ("step", 3, True)]


def test_a_steady_run_names_one_program_and_no_recompile():
    """Steps of one shape, single and grouped: each program is made once,
    under its own name, and ``train_recompiles_total`` stays absent; a
    program made between two dispatches (the caller's own) is not booked on
    the next one."""
    mesh, tr, stats = _compile_watching_trainer()
    stats.record.clear()
    batch = _make_batch(mesh)
    mask = jax.device_put(np.ones((64,), np.float32), batch_sharding(mesh))
    for _ in range(3):
        tr.step(batch, mask)
    jax.jit(lambda x: x * 5 + 2)(jnp.arange(3.0)).block_until_ready()
    tr.step(batch, mask)
    stacked = _stacked((batch, mask), 2, mesh)
    tr.multi_step(*stacked)
    tr.multi_step(*_stacked((batch, mask), 2, mesh))
    assert [(r["program"], r["steps_total"], r["recompile"])
            for r in stats.record] == [("step", 0, False),
                                       ("multi_2", 4, False)]
    assert "train_recompiles_total" not in tr.counters_snapshot()


def test_counters_snapshot_tells_the_bringup_only_once_it_is_whole(
        monkeypatch):
    """``counters_snapshot()`` returns the account once the first dispatch
    of ``fit_feed`` has returned and nothing of it before; the heartbeat's
    share (``_own_counters``) never holds what the process keeps once."""
    from tensorflowonspark_tpu import telemetry

    account = telemetry.Bringup()
    monkeypatch.setattr(telemetry, "bringup", account)
    mesh = build_mesh()
    tr = Trainer(_linear_loss, {"w": jnp.zeros((2,)), "b": jnp.zeros(())},
                 optax.sgd(0.1), mesh=mesh, batch_size=64, log_steps=1000)
    assert [p for _, p in account.export()] == ["trainer_init", "user"]
    assert not [k for k in tr.counters_snapshot() if k.startswith("bringup")]

    class Feed(object):
        def batches(self):
            for _ in range(3):
                yield _make_batch(mesh), jax.device_put(
                    np.ones((64,), np.float32), batch_sharding(mesh))

    seen = []
    tr.fit_feed(Feed(), on_steps=lambda s: seen.append(
        (s, tr.counters_snapshot().get("bringup_wall_us"))))
    snap = tr.counters_snapshot()
    phases = {p: snap["bringup_%s_us" % p] for p in telemetry.BRINGUP_PHASES}
    assert sum(phases.values()) == snap["bringup_wall_us"] > 0
    assert phases["trainer_init"] > 0 and phases["first_dispatch"] > 0
    assert [p for _, p in account.export()] == [
        "trainer_init", "user", "first_batch", "user", "first_dispatch", None]
    # whole from the first hook on, and the same ever after
    assert [w for _, w in seen] == [snap["bringup_wall_us"]] * 3
    assert not [k for k in tr._own_counters()
                if k.startswith(("bringup_", "compile_"))]


def _pointers(tree):
    return {shard.data.unsafe_buffer_pointer()
            for leaf in jax.tree_util.tree_leaves(tree)
            for shard in leaf.addressable_shards}


def test_the_trainer_owns_its_buffers_without_holding_adams_moments_twice():
    """What the caller hands over is copied (the donated step must not
    delete the caller's arrays); what ``optimizer.init`` made here is not:
    a copy of the whole state held Adam's two moments twice for a moment."""
    from tensorflowonspark_tpu import train as train_mod

    params = {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}
    optimizer = optax.adam(1e-3)
    made = optimizer.init(params)
    state = train_mod._own(train_mod.TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=made))
    assert not _pointers(state.params) & _pointers(params)
    assert _pointers(state.opt_state) == _pointers(made)
    trainer = Trainer(lambda p, batch, mask: (jnp.sum(p["w"]) * 0.0
                                              + jnp.sum(batch["x"] @ p["w"]
                                                        + p["b"]), {}),
                      params, optimizer, batch_size=2)
    trainer.step({"x": jnp.ones((2, 8))}, jnp.ones((2,)))
    assert float(params["w"].sum()) == 32.0      # the caller's, still there


def test_an_optimizer_state_that_is_the_parameters_is_copied():
    """Leaves of the optimizer's state that share a buffer with the
    parameters, or with each other, get buffers of their own: donating one
    buffer twice is an error, and the caller's would be deleted."""
    from tensorflowonspark_tpu import train as train_mod

    params = {"w": jnp.ones((8, 4))}
    fresh = jnp.zeros((8, 4))
    state = train_mod._own(train_mod.TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state={"slow": params["w"], "mu": fresh, "again": fresh}))
    taken = [_pointers(leaf) for leaf in (
        state.params, state.opt_state["slow"], state.opt_state["mu"],
        state.opt_state["again"])]
    assert len(set.union(*taken)) == sum(len(t) for t in taken) == 4
    assert not set.union(*taken) & _pointers(params)
    # the first of two that share one keeps it (a dict's leaves go by key)
    assert _pointers(state.opt_state["again"]) == _pointers(fresh)
