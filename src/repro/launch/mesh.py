"""Production mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Target hardware: TPU v5e, 256 chips/pod (16x16), optionally 2 pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the runtime places
    arrays with GSPMD shardings and enters manual regions through
    ``shard_map``; explicit-sharding types (the default of
    ``jax.make_mesh``) would make every jnp op on a sharded array outside
    those regions, e.g. the serving engine's matmuls on trained params,
    demand an output sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 1):
    """The 512-device (2 pods) / 256-device production mesh.  ``pipe``
    carves pipeline stages out of the DATA dimension (16 % pipe == 0)
    so 'model' stays minor-most: TP rings ride the fastest stride-1
    links, pipe boundary ppermutes one stride up, and the client axes
    keep the slowest (cross-pod) hops."""
    if pipe < 1 or 16 % pipe != 0:
        raise ValueError(f"pipe={pipe} must divide the 16-wide data dim")
    if pipe == 1:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return _auto_mesh(shape, axes)
    shape = ((2, 16 // pipe, pipe, 16) if multi_pod
             else (16 // pipe, pipe, 16))
    axes = (("pod", "data", "pipe", "model") if multi_pod
            else ("data", "pipe", "model"))
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1,
                   pipe: int = 1):
    """Small (data[, pipe], model) mesh over whatever devices exist
    (tests / examples).  Validates the factorization up front —
    ``jax.make_mesh`` would otherwise silently build a mesh over a
    subset (or fail deep in device assignment) when the axis sizes don't
    divide the host devices."""
    n = len(jax.devices())
    if pipe < 1:
        raise ValueError(f"pipe axis size {pipe} must be >= 1")
    inner = model * pipe
    if model < 1 or inner < 1 or n % inner != 0:
        raise ValueError(
            f"model axis size {model} x pipe {pipe} must divide the {n} "
            f"available device(s) (n % (model*pipe) == "
            f"{n % inner if inner else 'undef'}); "
            f"pick --model-axis/--pp from the divisors of {n}, or raise "
            f"the device count via XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=<n>")
    if data is None:
        data = n // inner
    if data < 1 or data * inner != n:
        raise ValueError(
            f"mesh ({data} data x {pipe} pipe x {model} model) needs "
            f"{data * inner} devices but {n} are available; leave "
            f"data=None to infer data = n // (model*pipe) = {n // inner}")
    if pipe == 1:
        return _auto_mesh((data, model), ("data", "model"))
    return _auto_mesh((data, pipe, model), ("data", "pipe", "model"))


# hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip per direction)
HBM_BYTES = 16 * 1024**3          # 16 GiB per chip
