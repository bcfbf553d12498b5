"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — device count is locked at first jax init, and only
``dryrun.py`` sets the 512-device XLA flag.

Mesh shapes (assignment):
  * single-pod: (16, 16)     axes ("data", "model")   = 256 chips
  * multi-pod:  (2, 16, 16)  axes ("pod", "data", "model") = 512 chips

Axis roles (DESIGN.md §6): "model" = TP + EP; "data" = FSDP + batch DP;
"pod" = hierarchical DP (gradient all-reduce over DCI; weights replicated
per pod so only grads cross pods).

Every mesh is built with Auto axis types: the repo's sharding contract is
GSPMD propagation from operand shardings, stated rather than left to
``jax.make_mesh``'s default.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1):
    """Whatever this host actually has (CPU smoke tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """``"DxM"`` (also ``D×M``) -> ``(data, model)`` ints, with a clear error
    on malformed input — shared by the serve CLI and the benchmarks."""
    d, sep, m = spec.lower().replace("×", "x").partition("x")
    try:
        if not sep:
            raise ValueError
        return int(d), int(m)
    except ValueError:
        raise ValueError(
            f"bad mesh spec {spec!r}: expected DATAxMODEL, e.g. 2x4") from None


def make_serve_mesh(data: int, model: int) -> jax.sharding.Mesh:
    """(data, model) serving mesh over the first ``data*model`` local devices
    (the ``--mesh DxM`` serve flag; forced host-platform CPU devices in tests
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {data}x{model}")
    need = data * model
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"--mesh {data}x{model} needs {need} devices but this host has "
            f"{len(devs)}; force CPU devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devs[:need])


# TPU v5e hardware constants used by every roofline computation.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW_PER_LINK = 50e9            # bytes/s per link
ICI_LINKS = 4                     # 2D torus: 4 links/chip (x+/x-/y+/y-)
DCI_BW = 25e9                     # inter-pod per-host effective (conservative)
HBM_PER_CHIP = 16 * 1024**3
