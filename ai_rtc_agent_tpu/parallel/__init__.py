from . import mesh, ring_attention, sharding, trainer  # noqa: F401
