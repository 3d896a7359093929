"""The one place each drifting JAX API is named.

Every module under ``src/repro`` goes through this package instead of
calling these APIs directly, so a JAX upgrade is absorbed here. The
package targets the installed JAX (0.9.0) and carries no branch for an
older release:

  - :mod:`repro.compat.tree`   — pytree utilities with path support
  - :mod:`repro.compat.pallas` — the Pallas modules and the interpret switch
  - :mod:`repro.compat.mesh`   — mesh construction / shard_map entry points
  - :mod:`repro.compat.probes` — backend probes (a failed backend raises)
  - :mod:`repro.compat.xla`    — compiled-artifact introspection (memory /
    cost analysis)
"""
from repro.compat import mesh, pallas, probes, tree, xla

__all__ = ["tree", "pallas", "mesh", "probes", "xla"]
