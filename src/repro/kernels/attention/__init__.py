from repro.kernels.attention.ops import flash_attention, use_impl
