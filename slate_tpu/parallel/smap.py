"""The one `shard_map` entry of the explicit-schedule modules
(parallel/collectives.py, the dist/ algorithm package)."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map` with `check_vma` off by default: values
    replicated by hand-placed all_gather/psum/ppermute trees are
    intended, not statically inferable."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
