"""A configuration small enough for the CPU: the cell's own file with its
widths and fleet cut, every other key as the cell runs it."""
from __future__ import annotations

import copy

from bench import harness as h


def tiny_config(name: str, controller: dict = None) -> dict:
    cfg = copy.deepcopy(h.config(name))
    spec = cfg["spec"]
    spec["fleet"]["n_devices"] = 8
    spec["clustering"]["n_clusters"] = 2
    spec["task"]["params"].update(hidden=16, n_classes=4)
    spec["local_batch"] = 8
    cfg["data"].update(n_samples=512, dim=32, n_classes=4, noise=0.8,
                       unit_variance=False)
    if cfg["data"]["partition"] == "writer":
        cfg["data"].update(writer_mean=64.0, writer_std=20.0)
    if controller is not None:
        spec["controller"] = controller
    return cfg


FIXED = {"kind": "fixed", "params": {"a": 3}}
