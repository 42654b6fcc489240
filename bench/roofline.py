"""Peaks of the chips the benchmark runs on, and the bytes a Pregel
superstep must move.

The bytes are the algorithm's needs, not the HLO's: the same count holds
whatever variant or kernel implements the superstep, so a change that
moves fewer bytes than this shows as a share above what the work allows
only if it skips work.
"""
from __future__ import annotations

# device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 16 GB HBM at 819 GB/s, 197 TFLOP/s
# bf16, 393 TOP/s int8.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py "
                       f"with their source") from None


def superstep_bytes(n_vertices: int, edge_slots: int, state_itemsize: int,
                    state_width: int = 1, reads_weight: bool = False,
                    id_itemsize: int = 4, weight_itemsize: int = 4) -> int:
    """Least HBM bytes one dense superstep moves.

    Per edge slot: its source and destination ids, its weight where the
    program reads it, one gathered source state and one message combined
    into the destination, each ``state_width`` values wide.  Per vertex:
    one read and one write of its state.
    """
    state = state_width * state_itemsize
    per_edge = 2 * id_itemsize + 2 * state
    if reads_weight:
        per_edge += weight_itemsize
    return edge_slots * per_edge + 2 * n_vertices * state
