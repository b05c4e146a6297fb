"""Multi-process jax.distributed tests (SURVEY §4.3).

Each test spawns N separate interpreters running
``tests/multiproc_worker.py`` with ``jax.distributed.initialize`` against a
localhost coordinator, so the ``process_count() > 1`` branches of
``collectives.py`` / ``mesh.py`` / ``infeed.py`` / ``checkpoint.py``
actually execute (the in-process 8-device mesh can't reach them).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "multiproc_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_world(scenario, tmpdir, world=2, timeout=180):
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO, env.get("PYTHONPATH", "")) if p)
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, scenario, str(rank), str(world),
             str(port), str(tmpdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(world)
    ]
    outs = []
    failed = False
    for proc in procs:
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            failed = True
        outs.append(out.decode("utf-8", "replace"))
        failed = failed or proc.returncode != 0
    if failed:
        raise AssertionError(
            "scenario {!r} failed:\n{}".format(
                scenario, "\n---- rank ----\n".join(outs)))
    return outs


@pytest.mark.slow
class TestMultiProcess:
    def test_end_of_data_consensus_uneven_feeds(self, tmp_path):
        outs = _run_world("consensus", tmp_path)
        assert all("consensus ok" in o for o in outs)

    def test_sharded_feed_global_batch_assembly(self, tmp_path):
        outs = _run_world("infeed", tmp_path)
        assert all("infeed ok" in o for o in outs)

    def test_grouped_feed_degrades_in_lockstep(self, tmp_path):
        outs = _run_world("grouped", tmp_path)
        assert all("grouped ok" in o for o in outs)

    def test_orbax_collective_save_restore(self, tmp_path):
        outs = _run_world("checkpoint", tmp_path)
        assert all("checkpoint ok" in o for o in outs)

    def test_drain_all_consumes_every_row(self, tmp_path):
        outs = _run_world("drain", tmp_path)
        assert all("drain ok" in o for o in outs)

    def test_filefeed_multihost_file_sharding(self, tmp_path):
        outs = _run_world("filefeed", tmp_path)
        assert all("filefeed ok" in o for o in outs)

    def test_degrade_prefetch_shmring_terminate_storm(self, tmp_path):
        """All the fragile pieces at once, on a 3-process uneven world:
        K-group degrade consensus + prefetch + shm-ring transport + early
        terminate."""
        outs = _run_world("storm", tmp_path, world=3, timeout=240)
        assert all("storm ok" in o for o in outs)
