"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run launcher sets XLA_FLAGS for 512 host devices *before*
any jax import; smoke tests and benchmarks see the real (1-device) backend.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType



def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0,
                   stage: int = 0):
    """Small mesh over however many host devices exist (tests/benches).
    ``stage > 0`` appends a pipeline-stage axis (DP x TP x PP meshes for
    the pipelined train step)."""
    shape = ((pod,) if pod else ()) + (data, model) + \
        ((stage,) if stage else ())
    axes = (("pod",) if pod else ()) + ("data", "model") + \
        (("stage",) if stage else ())
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
