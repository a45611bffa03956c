"""jax API shims: every version- or context-dependent jax call funnels
through here.

* ``enable_x64`` — scoped float64 (``jax.enable_x64(True)``); the rest of
  the process stays float32.
* ``shard_map`` — ``jax.shard_map`` with partial-manual ``axis_names``.
* ``ambient_mesh`` — the mesh in scope.  Callers still enter meshes with a
  legacy ``with mesh:``, which ``jax.sharding.get_abstract_mesh()`` does not
  see (it returns an empty mesh there); only the thread-resource env holds
  it.  That fallback stays until the callers move to ``jax.set_mesh``.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["enable_x64", "shard_map", "ambient_mesh"]


def enable_x64():
    """Context manager: float64 inside, the previous setting restored on
    exit."""
    return jax.enable_x64(True)


def ambient_mesh():
    """The mesh currently in scope via ``jax.set_mesh`` or ``with mesh:``
    (or None)."""
    m = jax.sharding.get_abstract_mesh()
    if m is not None and tuple(getattr(m, "axis_names", ()) or ()):
        return m
    # a legacy `with mesh:` is visible only in the thread-resource env
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    if m is not None and not m.empty:
        return m
    return None


def shard_map(f, *, mesh=None, in_specs, out_specs,
              axis_names: Optional[set] = None, check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes to be
    manual over, ``mesh`` defaults to the ambient mesh."""
    kwargs = dict(in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if mesh is not None:
        kwargs["mesh"] = mesh
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
