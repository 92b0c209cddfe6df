"""Production meshes for the TPU v5e target.

Single pod: 256 chips as (16, 16) ('data', 'model').
Multi-pod:  2 pods = 512 chips as (2, 16, 16) ('pod', 'data', 'model') —
the 'pod' axis carries the asynchronous FL *clusters* (DESIGN.md §2).

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax

# v5e hardware constants for the roofline model
PEAK_FLOPS_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the real local devices (CPU smoke / example runs)."""
    n = len(jax.devices())
    data = min(data, n)
    return jax.make_mesh((data, max(1, min(model, n // data))),
                         ("data", "model"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def n_chips(mesh) -> int:
    return mesh.devices.size
