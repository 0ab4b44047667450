# NOTE: do not import dryrun here — it sets XLA_FLAGS at import time and must
# only be imported as the process entrypoint.
from .mesh import make_production_mesh, required_device_count
