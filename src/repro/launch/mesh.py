"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis extends data parallelism across the inter-pod links (gradient
sync is the only cross-pod traffic; TP stays inside a pod where ICI is
fastest).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialisation).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..distributed.sharding import EXPERT_AXIS


def _make_mesh(shape: tuple, axes: tuple):
    # Auto axes: the sharding code places arrays with
    # with_sharding_constraint, which Explicit axes (jax.make_mesh's
    # default) reject.
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, expert: int = 0):
    """``expert > 0`` carves an expert-parallel axis out of the *data*
    axis (16 must divide by it): tokens are exchanged between expert
    shards over intra-pod ICI while gradient sync stays the only
    cross-pod traffic — axes ``("expert", data/expert, "model")``
    (with a leading ``"pod"`` when multi-pod).  MoE expert weights
    shard E over "expert" (distributed/sharding.py) and
    ``moe_apply`` takes the repro.ep all-to-all dispatch path."""
    data = 16
    if expert:
        if data % expert:
            raise ValueError(
                f"expert axis {expert} must divide the data axis {data}")
        shape = (2, expert, data // expert, 16) if multi_pod else \
            (expert, data // expert, 16)
        axes = ("pod", EXPERT_AXIS, "data", "model") if multi_pod else \
            (EXPERT_AXIS, "data", "model")
        return _make_mesh(shape, axes)
    shape = (2, data, 16) if multi_pod else (data, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0,
                   expert: int = 0):
    """Small mesh for CI (requires xla_force_host_platform_device_count)."""
    shape, axes = (), ()
    if pod:
        shape, axes = (pod,), ("pod",)
    if expert:
        shape, axes = shape + (expert,), axes + (EXPERT_AXIS,)
    shape, axes = shape + (data, model), axes + ("data", "model")
    return _make_mesh(shape, axes)
