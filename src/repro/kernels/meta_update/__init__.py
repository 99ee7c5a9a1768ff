from repro.kernels.meta_update.ops import (inner_update, meta_update,
                                           resolve_impl, use_impl,
                                           weighted_aggregate)
