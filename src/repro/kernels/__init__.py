from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention

__all__ = ["ops", "ref", "flash_attention"]
