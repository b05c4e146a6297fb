"""TPU host/chip discovery and pinning (reference ``gpu_info.py``).

The reference shelled out to ``nvidia-smi``/``libcudart`` to find free GPUs and
build ``CUDA_VISIBLE_DEVICES`` (``gpu_info.py:43-104``).  On TPU the runtime
owns enumeration: libtpu exposes local chips through PJRT (``jax.devices()``),
and *exclusivity* is per-process — a second process cannot share a chip, so the
"find a free GPU" dance becomes "bound this process to a subset of local chips
before initializing JAX".

Pinning uses the standard libtpu env vars and must happen before the first
``import jax`` resolves a TPU client; :func:`pin_chips` therefore only sets
environment variables and raises if JAX was already initialized.
"""

import logging
import os
import sys
import time

logger = logging.getLogger(__name__)

MAX_RETRIES = 3  # mirror reference gpu_info.py:17 retry-on-busy behavior


def get_devices():
    """Enumerate this host's accelerator devices via PJRT (replaces the
    reference's ``nvidia-smi`` listing, ``gpu_info.py:56``)."""
    import jax

    return jax.devices()


def is_tpu_device(device=None):
    """True when ``device`` (default: the default device) is a TPU."""
    import jax

    if device is None:
        device = jax.devices()[0]
    return device.platform == "tpu"


def backends_initialized():
    """Whether this process has already created a JAX backend.  Imports
    nothing and creates nothing: callers are the places that must NOT be
    the first to touch the device (the executor shell before it forks the
    user function, the heartbeat thread, :func:`pin_chips`).  Safe from a
    thread that polls while another imports jax: a module that is still
    being imported has created no backend."""
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    probe = getattr(xla_bridge, "backends_are_initialized", None)
    return probe is not None and probe()


def device_summary():
    """Human-readable device roster for lifecycle logs."""
    import jax

    return [
        {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", "unknown"),
            "process_index": d.process_index,
        }
        for d in jax.devices()
    ]


def num_local_chips():
    """Number of accelerator chips attached to this host/process."""
    import jax

    return jax.local_device_count()


def pin_chips(worker_index, chips_per_worker, total_chips=4):
    """Bind this process to a deterministic subset of the host's TPU chips.

    The TPU equivalent of the reference's deterministic by-worker-index GPU
    placement for multi-worker-per-host setups (``gpu_info.py:91-102``):
    worker ``i`` gets chips ``[i*chips_per_worker, (i+1)*chips_per_worker)``.

    Must be called before JAX initializes; only manipulates env vars
    (``TPU_VISIBLE_CHIPS``, ``TPU_CHIPS_PER_PROCESS_BOUNDS``,
    ``TPU_PROCESS_BOUNDS``).

    What this gives, as observed on a four-chip v5e host (2x2, jax 0.9.0,
    libtpu 0.0.34; ``chip_smoke.py --chips 4``): four processes pinned to
    chips 0..3 open their chips **at the same time**, and each is an
    independent world of one device (``jax.device_count() == 1``,
    ``process_index == 0`` in every one of them).  That is the layout for
    processes that do not talk to each other through the device — one
    serving replica per chip.  It is **not** one four-device world:
    ``jax.distributed.initialize`` returns, but the TPU runtime takes its
    topology from these bounds (``TPU_PROCESS_BOUNDS="1,1,1"``: a world of
    one process), so ``ctx.initialize_distributed()`` refuses the layout with
    a clear error.  To train over all the chips of a host, run one executor
    that owns them.
    """
    if backends_initialized():
        raise RuntimeError(
            "pin_chips must run before JAX initializes its TPU client")
    first = worker_index * chips_per_worker
    chips = list(range(first, first + chips_per_worker))
    assert chips[-1] < total_chips, (
        "worker {} requests chips {} beyond this host's {} chips".format(
            worker_index, chips, total_chips))
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chips)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
    logger.info("pinned worker %d to TPU chips %s", worker_index, chips)
    return chips


def tpu_env(libtpu_init_args=(), xla_flags=(), base=None, **env_vars):
    """Compose the TPU/XLA tuning environment for executor processes — the
    analog of the reference's GPU perf knobs (``TF_GPU_THREAD_MODE`` etc.,
    reference ``common.py:143-166``); pass the result as ``cluster.run(...,
    executor_env=...)`` so every node applies it BEFORE its first jax import
    (libtpu reads these only at client creation).

    Args:
      libtpu_init_args: iterable of ``--flag=value`` strings appended to
        ``LIBTPU_INIT_ARGS`` (libtpu runtime flags, e.g.
        ``--xla_tpu_enable_data_parallel_all_reduce_opt=true``).
      xla_flags: iterable of ``--xla_...`` strings appended to ``XLA_FLAGS``
        (compiler flags, e.g. ``--xla_tpu_spmd_threshold_for_allgather_cse=8``).
      base: dict to extend; the node later merges the result over its own
        inherited environment.
      **env_vars: extra plain variables (e.g.
        ``JAX_ENABLE_ASYNC_CHECKPOINTING="1"``).

    Returns a plain env dict suitable for ``executor_env``.
    """
    env = dict(base or {})

    def _append(key, flags):
        flags = [f for f in flags if f]
        if flags:
            prior = env.get(key, "")
            env[key] = (prior + " " + " ".join(flags)).strip()

    _append("LIBTPU_INIT_ARGS", libtpu_init_args)
    _append("XLA_FLAGS", xla_flags)
    env.update({k: str(v) for k, v in env_vars.items()})
    return env


def wait_for_devices(min_devices=1, timeout=90):
    """Block until the TPU runtime exposes at least ``min_devices`` devices.

    Mirrors the reference's retry-with-backoff while GPUs were busy
    (``gpu_info.py:77-81``): on TPU the transient is another process still
    holding the chip.  Observed on a v5e host (jax 0.9.0, libtpu 0.0.34,
    ``JAX_PLATFORMS=tpu,cpu``): while the chip is held, ``jax.devices()``
    fails within 0.2 s with ``RuntimeError: Unable to initialize backend
    'tpu' ... libtpu multi-process lockfile``, leaves no backend behind, and
    the same call in the same process succeeds (in about 7 s) once the
    holder has exited; a holder that exits cleanly leaves nothing behind.
    """
    deadline = time.time() + timeout
    attempt = 0
    while True:
        try:
            devices = get_devices()
            if len(devices) >= min_devices:
                return devices
        except RuntimeError as e:
            logger.warning("TPU enumeration failed (attempt %d): %s", attempt, e)
        attempt += 1
        if time.time() > deadline or attempt > MAX_RETRIES:
            raise RuntimeError(
                "TPU devices unavailable after {} attempts; another process "
                "may hold the chip lock".format(attempt))
        time.sleep(max(0.1, min(5 * attempt, deadline - time.time())))
