from repro.models.model import Model
from repro.models.params import (
    abstract_params, block_cycle, count_params, init_params, lead_blocks, param_logical_axes,
)
from repro.models.kvcache import abstract_cache, cache_logical_axes, zero_cache

__all__ = [
    "Model", "abstract_params", "block_cycle", "count_params", "init_params",
    "lead_blocks", "param_logical_axes", "abstract_cache", "cache_logical_axes", "zero_cache",
]
