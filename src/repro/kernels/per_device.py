"""Run a Mosaic kernel once per device inside a partly manual mesh."""

from __future__ import annotations

import jax


def per_device(f):
    """Run ``f`` once per device over the mesh axes left to the compiler.

    GSPMD cannot partition a Mosaic kernel, so inside a ``shard_map``
    that keeps some mesh axes automatic (the train step's model axis)
    the kernel is wrapped in one more ``shard_map``, its operands
    replicated across those axes.  That ``shard_map`` names every mesh
    axis, the outer manual ones too: under ``jax.set_mesh`` it lowers
    against the concrete mesh, which does not know the outer axes are
    manual, and Mosaic refuses a kernel unless all axes are.  Outside a
    mesh, or where every axis is already manual, ``f`` runs as it is.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not set(mesh.axis_names) - set(mesh.manual_axes):
        return f
    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=P(), axis_names=set(mesh.axis_names),
        check_vma=False,
    )
