"""The node's pair bytes (as ``node_roofline``) at the chip's peak HBM
bandwidth, over the summed device time of the Pallas FPE kernel's
operations, found by name in the trace: the kernel runs as a
``tpu_custom_call`` named after ``fpe_aggregate_pallas``, its wrapper in
``repro.kernels.kv_aggregate``."""

from chipbench import roofline

KERNEL = "fpe_aggregate_pallas"
TARGET = 'custom_call_target="tpu_custom_call"'


def kernel_ops(ops):
    return [o for o in ops if KERNEL in o.name and TARGET in o.long_name]


def read(w):
    t = w.trace
    if t is None or w.peaks is None:
        return None
    secs = sum(o.end - o.start for o in kernel_ops(t.ops)) / 1e9
    if secs <= 0:
        return None
    least = roofline.node_bytes(w.jobs, w.lanes) / w.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
