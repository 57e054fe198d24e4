"""Launchers: mesh construction, the multi-pod dry-run, the training
driver and the SSSP driver.  (dryrun must be run as a module so its
XLA device-count flag precedes jax initialization.)"""

from repro.launch.mesh import (
    make_local_topology, make_production_mesh, make_topology,
)

__all__ = ["make_local_topology", "make_production_mesh", "make_topology"]
