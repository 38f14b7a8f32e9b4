from pytorch_distributed_rnn_tpu.utils.hw import (
    CPU_PEAK_FLOPS_ESTIMATE,
    PEAK_FLOPS_TABLE,
    local_peak_flops,
    peak_flops,
)
from pytorch_distributed_rnn_tpu.utils.platform import (
    apply_platform_overrides,
    compile_cache_dir,
    compile_cache_stats,
    enable_compile_cache,
)

__all__ = [
    "CPU_PEAK_FLOPS_ESTIMATE",
    "PEAK_FLOPS_TABLE",
    "apply_platform_overrides",
    "compile_cache_dir",
    "compile_cache_stats",
    "enable_compile_cache",
    "local_peak_flops",
    "peak_flops",
]
