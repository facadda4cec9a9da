"""Production mesh builders (functions, never module-level constants, so
importing this module never touches jax device state)."""
from __future__ import annotations

import os
import pathlib

import jax
import numpy as np

__all__ = [
    "make_production_mesh",
    "make_serving_mesh",
    "forced_host_devices_env",
    "DP_AXES",
    "LANES_AXIS",
]

#: The 1-D serving mesh axis: admission-batch lanes are data-parallel over it.
LANES_AXIS = "lanes"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod stacks 2 pods = 512 chips.

    Axes: 'data' carries batch (gradient all-reduce), 'model' carries tensor/
    expert/vocab parallelism (and the decode split-K axis); 'pod' composes
    with 'data' for the hierarchical cross-pod gradient reduction.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def DP_AXES(multi_pod: bool) -> tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def make_serving_mesh(n_devices: int | None = None, *, devices=None):
    """1-D ``("lanes",)`` mesh for data-parallel fused serving.

    Every lane of a fixed-lane admission batch (serving/batched.py) is an
    independent while-loop, so the batched executor shards purely along a
    single ``"lanes"`` axis — no tensor axis, no collectives on the hot path.

    ``n_devices=None`` takes every visible device.  On CPU hosts, multi-device
    meshes are simulated with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set it BEFORE jax initializes); the error message points there because
    that is the one environment knob tests and CI need.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    n = len(devs) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n}")
    if n > len(devs):
        raise ValueError(
            f"n_devices={n} but only {len(devs)} devices are visible; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before jax initializes"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n]), (LANES_AXIS,))


def forced_host_devices_env(n_devices: int) -> dict:
    """Subprocess environment with ``n_devices`` simulated CPU devices.

    jax fixes its device list at first initialization, so multi-device CPU
    work (the cross-device parity tests, the sharded benchmark sweep) must
    run in a FORKED process with ``--xla_force_host_platform_device_count``
    set before jax imports.  This is the one shared recipe: append the
    force flag to any existing ``XLA_FLAGS``, pin the platform to cpu
    (the flag only multiplies HOST devices — an accelerator platform would
    ignore it and defeat the simulation), and prepend this package's
    ``src`` root to ``PYTHONPATH`` so the child can ``import repro`` no
    matter its cwd.  Real multi-chip runs don't go through this: they pass
    ``make_serving_mesh`` over the actual devices to the server directly,
    and on a host with an accelerator this raises rather than hand a child
    the CPU in its place.
    """
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "forced host devices are a CPU rehearsal; this host has "
            f"{jax.default_backend()!r} devices — pass make_serving_mesh "
            "over them in this process instead"
        )
    env = dict(os.environ)
    force = f"--xla_force_host_platform_device_count={int(n_devices)}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + force).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = str(pathlib.Path(__file__).resolve().parents[2])
    extra = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + os.pathsep + extra if extra else src
    return env
