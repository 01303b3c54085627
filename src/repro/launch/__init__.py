# Launch layer: meshes, sharding rules, step builders, dry-run, drivers.
from repro.launch import mesh, roofline, sharding  # noqa: F401
