"""The few jax API spellings the codebase routes through one place.

Written for the installed jax (0.9): ``jax.shard_map`` with ``check_vma``,
``jax.make_mesh`` with explicit axis types, ``pltpu.CompilerParams``.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.experimental.pallas import tpu as pltpu


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` over ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device mesh with Auto-typed axes (what shard_map + tracing-time
    collectives expect)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` for a ``pallas_call``."""
    return pltpu.CompilerParams(**kwargs)
