"""Device meshes and per-arch parallelism-plan resolution.

Every mesh in the repo is built by :func:`auto_mesh`, over whatever devices
JAX reports: the TPU chips on a TPU host, the (virtual) host devices under
``JAX_PLATFORMS=cpu``. Its axes are ``Auto``, so GSPMD propagates shardings
through the vmapped worker axis and honours ``with_sharding_constraint``.
The builders are FUNCTIONS (not module-level constants) so that importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import os
import re
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.configs.base import ModelConfig, ParallelismPlan

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def require_host_devices(n: int, *, strict: bool = True) -> bool:
    """Ensure the host (CPU) platform exposes >= ``n`` simulated devices.

    Patches ``XLA_FLAGS`` (raising any existing
    ``--xla_force_host_platform_device_count`` to at least ``n``) — which
    only takes effect if the jax backend has NOT initialized yet — then
    verifies the live device count. Call it before any jax computation
    (dryrun does so at import time; multi-device tests run in a
    subprocess for the same reason). Returns True when ``n`` devices are
    available; with ``strict=False`` a too-late call degrades to False
    instead of raising, so opportunistic callers (benchmarks) can skip
    their multi-device sections.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_HOST_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_COUNT_FLAG}={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_HOST_COUNT_FLAG}={n}")
    if jax.device_count() >= n:
        return True
    if strict:
        raise RuntimeError(
            f"need {n} host devices but jax initialized with "
            f"{jax.device_count()} — require_host_devices must run before "
            "the first jax computation (use a subprocess if the parent "
            "already touched jax)")
    return False


def auto_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """Mesh of ``shape`` over ``devices`` (default: all of ``jax.devices()``)
    with every axis ``Auto``. ``jax.make_mesh`` alone defaults to
    ``Explicit`` axes, under which the vmapped worker axis and the flat
    plane's sharding constraints do not trace."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def worker_mesh(n_workers: Optional[int] = None, *, devices=None):
    """(data, model) training mesh over ``devices`` (default: all devices).

    ``n_workers`` sizes the data (local-SGD worker) axis; the remaining
    devices go to the model axis, which a sharded ``--flat`` run uses for
    its plane shards. ``None`` puts every device on the data axis, and so
    does a request that does not divide the device count.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    data = n if n_workers is None else max(1, min(n_workers, n))
    if n % data:
        data = n
    return auto_mesh((data, n // data), ("data", "model"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


# Parameter-count thresholds steering worker granularity (see DESIGN.md §2/§4)
_POD_WORKER_THRESHOLD = 20e9       # > 20B params: one local-SGD worker per pod
_SYNC_ONLY_THRESHOLD = 100e9       # > 100B: no local workers (AdaAlter, global FSDP)


def resolve_plan(cfg: ModelConfig, mesh, *, optimizer: str = "local_adaalter",
                 override: Optional[ParallelismPlan] = None) -> ParallelismPlan:
    """Choose local-SGD worker granularity from model size and mesh topology."""
    if override is not None:
        return override
    axes = set(mesh.shape.keys())
    has_pod = "pod" in axes
    n_params = cfg.param_count()
    local = optimizer in ("local_adaalter", "local_sgd")

    if n_params > _SYNC_ONLY_THRESHOLD or not local:
        # fully synchronous (AdaAlter/AdaGrad): all non-model axes do
        # data-parallel FSDP; the paper's "local" part is disabled.
        dp = tuple(a for a in ("pod", "data") if a in axes)
        return ParallelismPlan(local_axes=(), grad_axes=dp, fsdp_axes=dp,
                               remat="full" if n_params > 1e9 else "none",
                               weight_gather_serving=n_params > _POD_WORKER_THRESHOLD)
    if n_params > _POD_WORKER_THRESHOLD:
        # workers = pods; within a pod every-step sync + ZeRO over "data"
        return ParallelismPlan(
            local_axes=("pod",) if has_pod else (),
            grad_axes=("data",),
            fsdp_axes=("data",),
            remat="full",
            weight_gather_serving=True,
        )
    # paper-style many workers: every (pod, data) slice is a worker
    return ParallelismPlan(
        local_axes=("pod", "data") if has_pod else ("data",),
        grad_axes=(),
        fsdp_axes=(),
        remat="full" if n_params > 1e9 else "none",
    )
