"""meta_update_roofline: the packed client plane's three Pallas kernels
(inner update, weighted aggregate, fused Adam) against their roofline,
in %: the sum of their least times over the sum of their device times in
the traced window.

All three are elementwise passes over the (clients, params) float32
plane, bound by HBM bandwidth: a call's least time is its least bytes
(the configuration's `kernel_bytes`, from shapes) over the chip's HBM
bandwidth. The kernels are found by the names the trace gives their
custom calls today: the inner update's kernel appears as `step`, the
others as `weighted_aggregate_flat` and `adam_flat_pallas`. A kernel
the trace does not name so is not counted."""
import re

KERNELS = {
    "inner_update": re.compile(r"^step(\.\d+)?$"),
    "aggregate": re.compile(r"^weighted_aggregate_flat(\.\d+)?$"),
    "adam": re.compile(r"^adam_flat_pallas(\.\d+)?$"),
}


def kernel_of(hlo: str):
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    name = hlo.partition(" = ")[0].strip().lstrip("%")
    for kind, pattern in KERNELS.items():
        if pattern.match(name):
            return kind
    return None


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    least = spent = 0.0
    for op in summary.ops:
        kind = kernel_of(op.name)
        if kind is not None:
            least += work["kernel_bytes"][kind] / peaks["hbm_bytes_per_s"]
            spent += op.end - op.start
    if spent <= 0.0:
        return None
    return 100.0 * least / spent
