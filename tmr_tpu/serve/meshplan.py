"""Mesh execution plan for the serving tier: replica groups, per-bucket
mode selection, and the dispatch targets sharded programs compile
against.

``TMR_SERVE_MESH`` (or ``ServeEngine(mesh=...)``) names a device mesh
over the local chips — ``"dp4"``, ``"tp4"``, ``"dp2tp2"`` — with the
axes of ``parallel.mesh.SERVE_AXES``:

- **dp** — data parallelism: one dispatch shards its batch across the
  ``dp`` replica groups (each image computed whole on one group). With
  ``tp == 1`` the program is a ``shard_map`` over ``dp`` whose per-shard
  trace IS the unsharded program body at the local batch shape, so
  per-request results stay bitwise-identical to the unsharded engine.
- **tp** — tensor parallelism inside a replica group: the ViT feature
  dimensions shard over the group's ``tp`` devices (Megatron-style,
  ``parallel/sharding.py`` specs through the GSPMD/pjit path), so ONE
  big image uses every chip in its group. TP collectives reorder float
  reductions, so tp results are allclose-level with identical keep
  decisions (the heads-path precedent), never silently different.

Mode is selected **per bucket**: buckets at or above the
``TMR_SERVE_TP_SIZE`` image size run tensor-parallel on a replica group
(big images — saturate a group per image); smaller buckets fan out
data-parallel across groups (small images — saturate the mesh per
batch). Feature-cached ``heads`` buckets always run per group on the
group's primary device (the split tail is not worth collectives).

The plan is immutable after construction; the engine owns all mutable
scheduling state (per-group queues, round-robin counters).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

from tmr_tpu.parallel.mesh import (
    SERVE_AXES,
    make_serve_mesh,
    parse_mesh_spec,
    replica_groups,
)

#: target modes: "group" = one replica group (tensor-parallel when the
#: group has > 1 device, the plain per-device program when tp == 1);
#: "dp" = the full mesh, batch sharded over the dp axis
TARGET_MODES = ("group", "dp")


class MeshTarget:
    """One dispatch target: a mesh (or sub-mesh) plus the batch-axis
    mode a program compiles for. ``key`` is the hashable component the
    sharded ``Predictor._compiled`` entries embed — it names the axis
    sizes AND the concrete device ids, so a mesh-shape change (or a
    different replica group) can never silently collide with a cached
    program built for other devices."""

    def __init__(self, name: str, mode: str, mesh, devices: Sequence[Any]):
        assert mode in TARGET_MODES, mode
        self.name = str(name)
        self.mode = mode
        self.mesh = mesh
        self.devices = tuple(devices)
        shape = dict(mesh.shape)
        self.dp = int(shape.get("dp", 1))
        self.tp = int(shape.get("tp", 1))
        self.key = (
            self.mode,
            tuple(sorted(shape.items())),
            tuple(getattr(d, "id", str(d)) for d in self.devices),
        )

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def primary(self):
        """The group's first device — where unsharded programs (the
        feature-cache heads path) execute."""
        return self.devices[0]

    def __repr__(self) -> str:  # per_device_batches / health keys
        return self.name

    __str__ = __repr__


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class MeshPlan:
    """The serving tier's execution plan for one mesh spec.

    ``group_targets`` — one :class:`MeshTarget` per replica group (mode
    "group").  ``dp_target`` — the full-mesh data-parallel target, or
    None when ``dp == 1`` (then every bucket runs on the single group).
    ``mode_for(bucket)`` / ``target_for(bucket, group)`` encode the
    per-bucket replica-group selection documented in the module
    docstring.
    """

    def __init__(self, spec: str, devices: Optional[Sequence[Any]] = None,
                 tp_size: Optional[int] = None):
        self.spec = str(spec).strip().lower()
        self.sizes = parse_mesh_spec(self.spec)
        self.mesh = make_serve_mesh(self.spec, devices=devices)
        self.dp = self.sizes["dp"]
        self.tp = self.sizes["tp"]
        #: image-size floor for tensor-parallel mode (big images go tp);
        #: ignored when the mesh has no usable alternative
        self.tp_size = (
            _env_int("TMR_SERVE_TP_SIZE", 512)
            if tp_size is None else int(tp_size)
        )
        groups = replica_groups(self.mesh)
        self.group_targets: List[MeshTarget] = []
        for i, devs in enumerate(groups):
            sub = make_serve_mesh(f"dp1tp{self.tp}", devices=devs)
            self.group_targets.append(
                MeshTarget(f"group{i}", "group", sub, devs)
            )
        self.dp_target: Optional[MeshTarget] = (
            MeshTarget("dp", "dp", self.mesh,
                       [d for row in groups for d in row])
            if self.dp > 1 else None
        )

    # ------------------------------------------------------------ policy
    def mode_for(self, bucket: tuple) -> str:
        """"dp" or "group" for one bucket key.

        - ``heads`` buckets (feature-cache path) always run per group.
        - With both axes available, image size decides: >= ``tp_size``
          runs tensor-parallel on a group, smaller fans out dp.
        - A pure-dp mesh (tp == 1) sends everything dp except heads; a
          pure-tp mesh (dp == 1) has only the one group.
        """
        if self.dp_target is None:
            return "group"
        kind, size = bucket[0], int(bucket[1])
        if kind == "heads":
            return "group"
        if self.tp > 1 and size >= self.tp_size:
            return "group"
        return "dp"

    def group_ids(self) -> List[Any]:
        """The batcher queue-group ids: one per replica group, plus
        "dp" when the full-mesh target exists."""
        ids: List[Any] = [t.name for t in self.group_targets]
        if self.dp_target is not None:
            ids.append(self.dp_target.name)
        return ids

    def target_by_group(self, group: Any) -> MeshTarget:
        if self.dp_target is not None and group == self.dp_target.name:
            return self.dp_target
        for t in self.group_targets:
            if t.name == group:
                return t
        raise KeyError(f"unknown replica group {group!r}")

    # ---------------------------------------------------------- reporting
    def describe(self) -> Dict[str, Any]:
        """The ``mesh`` attachment serve_report/v1 carries (validated by
        ``diagnostics.validate_serve_report``): spec, axis shape, axis
        names, replica groups by device string, and the mode policy's
        size threshold."""
        return {
            "spec": self.spec,
            "shape": {"dp": self.dp, "tp": self.tp},
            "axis_names": list(SERVE_AXES),
            "replica_groups": [
                [str(d) for d in t.devices] for t in self.group_targets
            ],
            "tp_size_threshold": self.tp_size,
        }


def refuse_cached_subslice_tp(plan: "MeshPlan") -> None:
    """Raise when this plan would load from the persistent compile cache,
    on a TPU, a multi-chip program for chips that do not include the
    host's first.

    Seen on a 2x2 v5e host (jaxlib 0.9.0 / libtpu 0.0.34, PR 23;
    ``scripts/mesh_cache_probe.py`` is the reproduction: one process per
    cell, all loading from a directory the first one filled). The ``tp2``
    program on chips 2 and 3 runs when it is compiled in the process. The
    same executable — same cache key, same fingerprint, same devices —
    loaded from the persistent cache halts both cores on first execution
    ("schecklt: Invalid logical z: enhanced-barrier-parent-phase-1 ...
    Core halted unexpectedly") and the runtime terminates the process.
    Loaded from the same cache, ``tp2`` on chips 0 and 1 runs, and so
    does a one-chip program on chip 2. The fault therefore needs a
    cross-chip barrier and chips that are not the slice's first; every
    ``dp > 1, tp > 1`` plan has such a replica group, and so has a
    ``tpN`` plan handed later devices. Such a plan needs the cache off:
    an error here is better than a halted slice."""
    import jax

    if not (
        jax.default_backend() == "tpu"
        and jax.config.jax_enable_compilation_cache
        and jax.config.jax_compilation_cache_dir
    ):
        return
    first = jax.local_devices()[0]
    halting = [t.name for t in plan.group_targets
               if t.n_devices > 1 and first not in list(t.devices)]
    if halting:
        raise RuntimeError(
            f"mesh {plan.spec!r}: the tensor-parallel program of "
            f"{', '.join(halting)} runs on chips that do not include "
            f"{first}, and loaded from the persistent compile cache such "
            "a program halts the TPU (libtpu 0.0.34); start the process "
            "with JAX_ENABLE_COMPILATION_CACHE=false"
        )


def resolve_plan(mesh: Optional[str],
                 devices: Optional[Sequence[Any]] = None,
                 tp_size: Optional[int] = None) -> Optional[MeshPlan]:
    """The engine's mesh resolution: explicit argument first, then the
    ``TMR_SERVE_MESH`` env knob; empty/unset -> None (the unsharded
    round-robin engine, byte-identical to the pre-mesh behavior)."""
    spec = os.environ.get("TMR_SERVE_MESH", "") if mesh is None else mesh
    spec = (spec or "").strip()
    if not spec or spec in ("0", "off", "none"):
        return None
    return MeshPlan(spec, devices=devices, tp_size=tp_size)
