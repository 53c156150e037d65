"""Device-mesh topology for sharded evaluation and campaign dispatch.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets ``xla_force_host_platform_device_count`` before
any jax initialization; everything else must see whatever the launch
environment configured).  jax itself is imported lazily inside each
constructor, so :func:`ensure_host_platform_devices` can be called from
a jax-free process to request a many-device CPU mesh *before* the
backend initializes.

Two named axes cover every consumer:

``eval``
    The config-batch axis: candidate depth rows are embarrassingly
    parallel, so the sharded evaluators (:mod:`repro.core.backends.mesh`)
    split rows across it and evaluate each shard with the unchanged
    jitted kernels — bit-identical to the solo path by construction.
``design``
    The campaign axis: the hetero dispatcher packs rows from many
    designs design-major, so partitioning over ``("design", "eval")``
    jointly lands contiguous design blocks on contiguous device groups.

On CPU hosts (CI, laptops) a multi-device mesh comes from XLA's
host-platform device emulation::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...

or programmatically via :func:`ensure_host_platform_devices` before jax
initializes.
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Sequence, Tuple

__all__ = [
    "device_grid", "ensure_host_platform_devices", "make_campaign_mesh",
    "make_eval_mesh", "make_local_mesh", "make_production_mesh",
]


def ensure_host_platform_devices(n: int) -> bool:
    """Request an ``n``-device CPU host-platform mesh for this process.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    when (a) no such flag is present already and (b) jax's backends have
    not initialized yet (the flag is read exactly once, at backend init).
    Returns True when a forced device count is in effect after the call
    — either ours or one the environment set — and False when it is too
    late to apply (jax already initialized), so callers can fall back to
    fewer shards instead of crashing.

    Never imports jax itself: safe from numpy-only processes.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return True
    if "jax" in sys.modules:
        # jax 0.9.0 (the pinned version) has no public form of this check
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            return False
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip())
    return True


def device_grid(n: int) -> Tuple[int, int]:
    """Near-square 2-D factorization of ``n`` devices, ``a <= b``."""
    n = int(n)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    a = int(n ** 0.5)
    while n % a:
        a -= 1
    return (a, n // a)


def _require(n_devices: int, shape: Sequence[int], what: str):
    import math
    need = math.prod(shape)
    if need > n_devices:
        raise ValueError(
            f"{what}: requested mesh shape {tuple(shape)} needs {need} "
            f"devices but only {n_devices} are available "
            f"(jax.device_count()). On CPU hosts, launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"or call ensure_host_platform_devices({need}) before jax "
            f"initializes.")


def make_eval_mesh(shards: Optional[int] = None):
    """1-D ``("eval",)`` mesh over ``shards`` devices (default: all).

    The config-batch sharding axis used by
    :class:`repro.core.backends.mesh.MeshBackend`.  Fails with a clear
    error when ``shards`` exceeds ``jax.device_count()``.
    """
    import jax
    n = jax.device_count()
    shards = n if shards is None else int(shards)
    _require(n, (shards,), "make_eval_mesh")
    return jax.make_mesh((shards,), ("eval",),
                         devices=jax.devices()[:shards])


def make_campaign_mesh(design_shards: Optional[int] = None,
                       eval_shards: Optional[int] = None):
    """2-D ``("design", "eval")`` mesh for cross-design campaign dispatch.

    Defaults to a near-square grid over every available device; either
    axis can be pinned.  The hetero dispatcher partitions its packed
    row batch over BOTH axes jointly (rows are stacked design-major, so
    design blocks land on contiguous device groups).
    """
    import jax
    n = jax.device_count()
    if design_shards is None and eval_shards is None:
        shape = device_grid(n)
    elif design_shards is None:
        _require(n, (eval_shards,), "make_campaign_mesh")
        shape = (n // int(eval_shards), int(eval_shards))
    elif eval_shards is None:
        _require(n, (design_shards,), "make_campaign_mesh")
        shape = (int(design_shards), n // int(design_shards))
    else:
        shape = (int(design_shards), int(eval_shards))
    _require(n, shape, "make_campaign_mesh")
    import math
    used = math.prod(shape)
    return jax.make_mesh(shape, ("design", "eval"),
                         devices=jax.devices()[:used])


def make_production_mesh(*, multi_pod: bool = False,
                         shape: Optional[Sequence[int]] = None):
    """Accelerator-pod mesh, shape derived from ``jax.device_count()``.

    Single pod: a near-square ``("data", "model")`` grid over every
    device (256 chips -> 16x16).  ``multi_pod`` splits the fleet into 2
    pods first: ``("pod", "data", "model")`` with a near-square grid per
    pod (512 chips -> 2x16x16).  Pass ``shape`` to pin an explicit
    topology; it is validated against the available device count and
    fails with a clear error instead of letting jax crash deep in
    ``make_mesh``.
    """
    import jax
    n = jax.device_count()
    if shape is not None:
        axes = ("pod", "data", "model") if len(shape) == 3 \
            else ("data", "model")
        if len(shape) != len(axes):
            raise ValueError(
                f"make_production_mesh: shape must be 2-D (data, model) "
                f"or 3-D (pod, data, model), got {tuple(shape)}")
        _require(n, shape, "make_production_mesh")
    elif multi_pod:
        if n < 2 or n % 2:
            raise ValueError(
                f"make_production_mesh(multi_pod=True) needs an even "
                f"device count >= 2, got {n}")
        shape = (2,) + device_grid(n // 2)
        axes = ("pod", "data", "model")
    else:
        shape = device_grid(n)
        axes = ("data", "model")
    import math
    used = math.prod(shape)
    return jax.make_mesh(tuple(shape), axes,
                         devices=jax.devices()[:used])


def make_local_mesh():
    """1x1 ``("data", "model")`` mesh over the first local device
    (CPU tests / examples)."""
    import jax
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
