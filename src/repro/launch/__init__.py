"""Launchers: mesh construction, multi-pod dry-run, train/serve/fed drivers.

``repro.launch.dryrun`` run as a program pins the CPU backend with 512
forced host devices; importing it sets nothing.
"""

from repro.launch.mesh import V5E, make_host_mesh, make_production_mesh

__all__ = ["V5E", "make_host_mesh", "make_production_mesh"]
