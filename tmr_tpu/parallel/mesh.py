"""Mesh construction + multi-host init helpers."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    shape: Tuple[int, ...] = (-1, 1),
    axis_names: Optional[Tuple[str, ...]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a device mesh — ('data', 'model') by default, or
    ('data', 'model', 'seq') when a third (sequence/context-parallel) size
    is given. A single -1 entry fills with all remaining devices. Works
    identically on a real slice and on the virtual CPU mesh used in
    tests/dry runs.

    Device order: jax.experimental.mesh_utils picks an ICI-friendly layout on
    real TPU topologies; on hosts it's the flat device list.
    """
    devices = list(devices if devices is not None else jax.devices())
    if axis_names is None:
        axis_names = ("data", "model", "seq")[: len(shape)]
    elif len(shape) != len(axis_names):
        raise ValueError(
            f"shape {shape} and axis_names {axis_names} length mismatch"
        )
    sizes = list(shape)
    if sizes.count(-1) > 1:
        raise ValueError("at most one -1 mesh dimension")
    fixed = int(np.prod([s for s in sizes if s != -1]))
    if -1 in sizes:
        if len(devices) % fixed:
            raise ValueError(f"{len(devices)} devices not divisible by {fixed}")
        sizes[sizes.index(-1)] = len(devices) // fixed
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError(f"mesh {sizes} needs {n} devices, have {len(devices)}")
    from jax.experimental import mesh_utils

    try:
        arr = mesh_utils.create_device_mesh(tuple(sizes), devices=devices[:n])
    except (ValueError, NotImplementedError, AssertionError) as e:
        # a shape the topology mapper cannot place (e.g. a sub-slice of a
        # host): the flat device order still gives a correct mesh, only not
        # an ICI-aware one — say so instead of hiding the layout
        import warnings

        warnings.warn(
            f"make_mesh: create_device_mesh refused {tuple(sizes)} over "
            f"{n} devices ({type(e).__name__}: {e}); using the flat "
            "device order"
        )
        arr = np.array(devices[:n]).reshape(sizes)
    return Mesh(arr, tuple(axis_names))


def initialize_multihost(coordinator: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Multi-host (DCN) initialization — the reference's multi-node story is
    Hadoop job submission; ours is jax.distributed over the pod.

    No-op when single-process (the common case in this image)."""
    if num_processes in (None, 1):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------- serving

#: axis names of the serving mesh: batches shard over ``dp`` (replica
#: groups), the ViT feature dimensions shard over ``tp`` inside a group
SERVE_AXES = ("dp", "tp")

_SPEC_RE = re.compile(r"(dp|tp)(\d+)")


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse a serving-mesh spec string into ``{"dp": N, "tp": M}``.

    The spec is a concatenation of ``dp<N>`` / ``tp<M>`` terms in any
    order (``"dp4"``, ``"tp4"``, ``"dp2tp2"``); an omitted axis is 1.
    Raises ValueError on anything else — a typo'd ``TMR_SERVE_MESH``
    must fail engine construction loudly, not silently serve unsharded.
    """
    s = (spec or "").strip().lower()
    if not s:
        raise ValueError("empty mesh spec")
    out = {"dp": 1, "tp": 1}
    seen = set()
    pos = 0
    for m in _SPEC_RE.finditer(s):
        if m.start() != pos:
            break
        axis, n = m.group(1), int(m.group(2))
        if axis in seen:
            raise ValueError(f"mesh spec {spec!r}: duplicate {axis!r}")
        if n < 1:
            raise ValueError(f"mesh spec {spec!r}: {axis}{n} < 1")
        seen.add(axis)
        out[axis] = n
        pos = m.end()
    if pos != len(s) or not seen:
        raise ValueError(
            f"bad mesh spec {spec!r}: expected dp<N>/tp<M> terms, "
            "e.g. 'dp4', 'tp4', 'dp2tp2'"
        )
    return out


def make_serve_mesh(spec: str,
                    devices: Optional[Sequence] = None) -> Mesh:
    """Build the serving mesh for ``spec`` over the leading
    ``dp * tp`` local devices: axes ``("dp", "tp")``, row-major — the
    ``tp`` rows are the replica groups (see :func:`replica_groups`).
    Unlike :func:`make_mesh` the device order is the flat local list on
    every backend: serving replica groups must be stable across engine
    restarts for the compiled-program cache keys to hit."""
    sizes = parse_mesh_spec(spec)
    devices = list(devices if devices is not None else jax.devices())
    need = sizes["dp"] * sizes["tp"]
    if need > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {need} devices, have {len(devices)}"
        )
    arr = np.array(devices[:need]).reshape(sizes["dp"], sizes["tp"])
    return Mesh(arr, SERVE_AXES)


def replica_groups(mesh: Mesh) -> List[List]:
    """The serving mesh's replica groups: one list of devices per ``dp``
    index (each group spans the ``tp`` axis — the devices one
    tensor-parallel program executes across)."""
    arr = np.asarray(mesh.devices)
    if arr.ndim != 2:
        raise ValueError(f"expected a 2-axis serve mesh, got {arr.shape}")
    return [list(row) for row in arr]
