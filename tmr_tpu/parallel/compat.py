"""The sharding surface's helpers: ``shard_map`` with the argument order
every parallel module here uses, :func:`partitioned` — the one place that
decides a program is XLA's to partition and so can hold no Mosaic kernel —
and the one compile seam of the sharded serving programs."""

from __future__ import annotations

import jax

from tmr_tpu.diagnostics import mosaic_kernels_off


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with ``mesh`` positional. ``check_vma`` toggles
    the static varying-manual-axes check that several of our islands
    disable (collectives whose replication the checker cannot prove)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def partitioned(f, mesh):
    """``f`` as the body (or the jitted callable) of a program XLA
    partitions over ``mesh`` by itself (GSPMD: a ``jax.jit`` whose
    arguments or ``in_shardings``/``out_shardings`` span the mesh).

    Over more than one device JAX refuses to lower a Pallas TPU kernel
    into such a program ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map."), so ``f`` then
    runs with the Mosaic gates answering no, cause "partitioned"
    (``diagnostics.mosaic_kernels_off``), and its trace takes the XLA
    formulations. On one device, or with no mesh, ``f`` comes back as it
    is. Every GSPMD program of the repo — the tensor-parallel serving
    targets below, the trainer's step and eval programs — is built
    through here; a ``shard_map`` whose axes are all manual is not XLA's
    to partition and keeps its kernels."""
    if mesh is None or mesh.size == 1:
        return f
    return mosaic_kernels_off(
        "GSPMD-partitioned program: Mosaic kernels cannot be "
        "automatically partitioned"
    )(f)


def compile_sharded(f, mesh, *, in_shardings=None, out_shardings=None,
                    in_specs=None, out_specs=None, donate_argnums=()):
    """One compile seam for the sharded serving programs (the SNIPPETS.md
    compile-helper pattern): explicit shardings -> ``jax.jit`` with
    ``in_shardings``/``out_shardings`` (the pjit/GSPMD path — XLA derives
    the tensor-parallel collectives from the param specs; traced through
    :func:`partitioned`), plain
    PartitionSpecs -> :func:`shard_map` over the mesh wrapped in jit (the
    pure data-parallel map path, whose per-shard trace IS the unsharded
    program body — the serving tier's bitwise-exactness lever).

    Exactly one of the two spec families must be given; mixing them is a
    caller bug, refused loudly.
    """
    use_pjit = in_shardings is not None or out_shardings is not None
    use_smap = in_specs is not None or out_specs is not None
    if use_pjit == use_smap:
        raise ValueError(
            "compile_sharded: pass in_shardings/out_shardings (pjit) OR "
            "in_specs/out_specs (shard_map), not both/neither"
        )
    if use_pjit:
        if in_shardings is None or out_shardings is None:
            raise ValueError(
                "compile_sharded: the pjit path needs BOTH in_shardings "
                "and out_shardings"
            )
        return jax.jit(partitioned(f, mesh), in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=donate_argnums)
    if in_specs is None or out_specs is None:
        raise ValueError(
            "compile_sharded: the shard_map path needs BOTH in_specs "
            "and out_specs"
        )
    return jax.jit(
        shard_map(f, mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False),
        donate_argnums=donate_argnums,
    )
