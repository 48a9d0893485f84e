from .ops import decode_attention
from . import kernel, ops

__all__ = ["decode_attention", "kernel", "ops"]
