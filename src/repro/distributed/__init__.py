"""Distribution layer: sharding rules, pipeline parallelism, compression."""
from . import compression, pipeline, sharding

__all__ = ["compression", "pipeline", "sharding"]
