"""shard_map wrapping for partitioned SDFG callables.

``ShardMapPass`` (transforms/shard_map.py) divides the SDFG's container
shapes and map ranges by ``n_shards`` and stamps the partition under
``sdfg.metadata["shard_map"]``; the backend's built callable therefore
computes ONE shard. This module wraps it in
``jax.shard_map`` over a 1-D device mesh so the
global-shaped call runs every shard in parallel: shard-local containers
get ``PartitionSpec(axis)`` on their partition dim, replicated ones
``PartitionSpec()``, and collective outputs (wcr reduced over the
partition) a ``lax.psum`` inside the mapped function.

The mesh is built lazily at first call from the first ``n_shards``
devices — under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
these are the simulated hosts; on a real pod, the processes' local
devices. A mesh *shrink* never reuses this wrapper: a different
``n_shards`` is a different pass configuration, hence a different
pipeline signature and content hash — a compilation-cache miss and a
fresh compile, never a stale kernel.
"""
from __future__ import annotations

from typing import Dict, Set

import numpy as np


class ShardMeshError(RuntimeError):
    """Not enough devices to build the requested shard mesh."""


def make_shard_mesh(n_shards: int, axis: str):
    """1-D mesh over the first ``n_shards`` visible devices."""
    import jax
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ShardMeshError(
            f"shard mesh needs {n_shards} devices but only {len(devs)} "
            f"{jax.default_backend()} devices are visible; run on a host "
            f"or slice with at least {n_shards} chips")
    return jax.sharding.Mesh(np.array(devs[:n_shards]), (axis,))


def _pspec(axis: str, dim):
    from jax.sharding import PartitionSpec as P
    if dim is None:
        return P()
    return P(*([None] * int(dim) + [axis]))


def wrap_shard_map(fn, spec: Dict, written):
    """Wrap a kwargs->dict SDFG callable in shard_map per ``spec``.

    ``spec`` is the ``sdfg.metadata["shard_map"]`` stamp; ``written`` the
    output container names (the dict keys ``fn`` returns).
    """
    import jax

    axis = spec["axis"]
    k = int(spec["n_shards"])
    specs = spec.get("specs", {})
    psums: Set[str] = set(spec.get("psum", ()))
    out_specs = {n: _pspec(axis, None if n in psums else specs.get(n))
                 for n in sorted(written)}
    mesh_box = []

    def sharded(**kwargs):
        if not mesh_box:
            mesh_box.append(make_shard_mesh(k, axis))
        mesh = mesh_box[0]
        names = sorted(kwargs)
        in_specs = ([_pspec(axis, specs.get(n)) for n in names],)

        def inner(vals):
            out = fn(**dict(zip(names, vals)))
            for n in psums:
                if n in out:
                    out[n] = jax.lax.psum(out[n], axis)
            return {n: out[n] for n in sorted(out)}

        return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(
            [kwargs[n] for n in names])

    sharded.__name__ = getattr(fn, "__name__", "sdfg") + f"_shard{k}"
    return sharded
