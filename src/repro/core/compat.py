"""Version-compatibility shims for the JAX APIs this repo spans."""
from __future__ import annotations

import jax


def shard_map(body, mesh, in_specs, out_specs, *, axis_names):
    """``jax.shard_map`` with replication checking off: the one wrapper every
    manual-axis region in the repo goes through, so a later API change is
    absorbed here."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=False)
