from .analytic import (
    arch_profile,
    flops_per_token,
    kv_cache_bytes_per_token,
    module_duration,
    param_count,
)
from .hardware import CATALOG, TPUSpec, spec_for
from .interference import InterferenceModel, calibrate as calibrate_interference
from .measured import (
    corrected_profile,
    corrected_profiles,
    duration_scale,
    quantize_scale,
)

__all__ = [
    "CATALOG", "InterferenceModel", "TPUSpec", "arch_profile",
    "calibrate_interference", "corrected_profile", "corrected_profiles",
    "duration_scale", "flops_per_token", "kv_cache_bytes_per_token",
    "module_duration", "param_count", "quantize_scale", "spec_for",
]
