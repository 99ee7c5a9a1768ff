from repro.kernels.grouped_matmul.ops import gmm, use_impl
