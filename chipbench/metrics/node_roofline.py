"""Share of the device-busy time that the node's pair traffic needs at the
chip's peak HBM bandwidth.

Bytes are counted from the cascade's level counters, whatever implements
the node: every level reads its real input pairs and writes its forwarded
pairs once, each pair a 4-byte key and 4 bytes per value lane.
"""

from chipbench import roofline


def read(w):
    t = w.trace
    if t is None or w.peaks is None or t.busy_s <= 0:
        return None
    least = roofline.node_bytes(w.jobs, w.lanes) / w.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.busy_s
