# Launch layer: meshes, sharding rules, roofline terms, drivers.
from repro.launch import mesh, roofline, sharding  # noqa: F401
