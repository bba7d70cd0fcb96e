"""Device abstraction: the ``DEVICE`` dispatch seam, TPU-natively.

The reference branches on ``DEVICE`` at import time into four accelerator
stacks (``xla|cuda|triton|cpu``, reference ``app/run-sd.py:41-44,104-135``).
Here the same seam is two tiers — ``tpu`` and ``cpu`` — and the branch
changes *nothing* about model code: JAX targets either platform with the same
jitted functions. ``cpu`` is the test/CI tier (the reference's Graviton tier)
and also what powers multi-chip simulation in tests.
"""

from __future__ import annotations

import logging
import os
from typing import List, Optional

log = logging.getLogger(__name__)


def resolve_device(device: str) -> str:
    """Check the requested tier against the backend JAX actually brought up.

    ``DEVICE=tpu`` means a TPU: a process that asked for one and got the CPU
    backend raises here instead of serving from the CPU under a ``tpu``
    label. Initializes the backend, so call it after :func:`apply_platform`
    and :func:`maybe_distributed_init`.
    """
    import jax

    if device not in ("cpu", "tpu"):
        raise ValueError(f"unknown device tier {device!r}")
    platform = jax.devices()[0].platform
    if platform != device:
        raise RuntimeError(
            f"DEVICE={device} requested but the JAX backend is {platform!r} "
            f"({len(jax.devices())} device(s)); refusing to serve from it")
    return device


def live_backend() -> dict:
    """What JAX reports for the live backend (``GET /`` shows it beside the
    requested ``device`` tier)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def apply_platform(device: str) -> None:
    """Pin the process's JAX platform to the requested tier.

    The reference's ``DEVICE`` branch selects a whole accelerator stack at
    import time (``app/run-sd.py:41-44``); here ``DEVICE=cpu`` must keep the
    process off the TPU entirely (a cpu-tier pod on a TPU host must not claim
    the chip). ``jax`` is imported (and ``JAX_PLATFORMS`` read) before this
    runs, so use the live config; call before the first backend use.
    ``DEVICE=tpu`` pins nothing here: :func:`resolve_device` checks what came
    up.
    """
    if device != "cpu":
        return
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        log.warning(
            "JAX backend already initialized; DEVICE=cpu will fall back to "
            "default-platform placement"
        )


def local_devices(device: Optional[str] = None) -> List:
    """Devices for the requested tier, in stable id order."""
    import jax

    if device in (None, ""):
        return list(jax.devices())
    if device == "cpu":
        return list(jax.devices("cpu"))
    if device != "tpu":
        raise ValueError(f"unknown device tier {device!r}")
    return [d for d in jax.devices() if d.platform == "tpu"]


def maybe_distributed_init(env=None) -> bool:
    """Join a multi-host JAX cluster when the pod env asks for it.

    The reference's multi-host serving tier runs TP=32 over 8 Neuron devices
    through NxD's NeuronLink/EFA collectives (``compile-vllm-job.yaml:38-44``,
    SURVEY.md §2.7). TPU-natively a multi-host slice (v5e-16+) is one JAX
    cluster: after ``jax.distributed.initialize`` every process sees the
    GLOBAL device set, the same ``NamedSharding`` meshes span hosts, and XLA
    routes collectives over ICI within the slice and DCN across slices —
    no NCCL/MPI equivalent to manage.

    Env contract (set by the StatefulSet manifest from the pod ordinal):

    - ``SHAI_COORDINATOR``: ``host:port`` of process 0 (its headless-service
      DNS name, e.g. ``llama-mh-0.llama-mh:8476``)
    - ``SHAI_NUM_PROCESSES``: total host processes in the unit
    - ``SHAI_PROCESS_ID``: this pod's ordinal

    Returns True when distributed init ran. Must be called before the first
    backend touch (same rule as :func:`apply_platform`).
    """
    env = os.environ if env is None else env
    coord = env.get("SHAI_COORDINATOR", "")
    if not coord:
        return False
    import jax

    n = int(env["SHAI_NUM_PROCESSES"])
    pid = int(env["SHAI_PROCESS_ID"])
    log.info("joining multi-host cluster: coordinator=%s process %d/%d",
             coord, pid, n)
    jax.distributed.initialize(coordinator_address=coord, num_processes=n,
                               process_id=pid)
    return True


def force_host_device_count(n: int) -> None:
    """Configure N virtual CPU devices (tests / multi-chip dry runs).

    Must run before JAX initializes its backends.
    """
    import re

    # shai-lint: allow(env-knob) XLA_FLAGS is a read-modify-write of the
    # platform's own variable, not a serving knob behind the parser seam
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
