"""Bytes the aggregation node's work needs, from the cascade's counters."""

#: bytes of one key (int32)
KEY_BYTES = 4
#: bytes of one carried value lane (float32)
LANE_BYTES = 4


def node_bytes(jobs, lanes: int) -> int:
    """Every level reads its real input pairs and writes its forwarded
    pairs once: (sum of level inputs + sum of level outputs) x pair bytes,
    over the jobs given."""
    pairs = sum(sum(j.level_in) + sum(j.level_out) for j in jobs)
    return pairs * (KEY_BYTES + LANE_BYTES * lanes)
