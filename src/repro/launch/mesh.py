"""Mesh axis conventions.

In the federated mapping (DESIGN.md §7) the ``pod``+``data`` axes carry the
client cohort / per-client batch; ``model`` carries tensor/expert parallel.
"""
from __future__ import annotations


def data_axes(mesh) -> tuple:
    """Mesh axes that carry the batch/cohort dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
