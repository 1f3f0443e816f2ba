"""Peaks of the chips, and the least work one plan window needs.

Peaks: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393
TOP/s int8, 819 GB/s HBM per chip.  A device that is not in the table
is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                    "int8_ops": 393e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                "int8_ops": 393e12},
}

# bytes of one schedule-table row the fire mask reads
# (ops/schedule_table.py: 8 uint32 masks, period and phase_mod int32,
# dom_star / dow_star / is_every / active / paused bool)
TABLE_ROW_BYTES = 8 * 4 + 2 * 4 + 5


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return PEAKS[device_kind]


def plan_window_work(job_capacity: int, nodes: int, window_s: int,
                     fires: float):
    """(bytes, integer ops) one window of ``window_s`` seconds has to
    move and do, by the algorithm and not by its implementation:
    the table streams once per window; every second reads its fire
    column and the exclusive flag, and writes the compacted indexes;
    every fire reads its eligibility row (nodes / 8 bytes) once and its
    assignment is written once.  Ops: ~24 integer operations per row
    and second for the mask, one per (fire, node) for the bid."""
    by = (job_capacity * TABLE_ROW_BYTES
          + window_s * job_capacity * 2
          + fires * (nodes / 8 + 4 + 2))
    ops = job_capacity * window_s * 24 + fires * nodes
    return by, ops


def plan_window_least_seconds(device_kind: str, job_capacity: int,
                              nodes: int, window_s: int, fires: float):
    """(seconds, which bound applies)."""
    p = peaks(device_kind)
    by, ops = plan_window_work(job_capacity, nodes, window_s, fires)
    t_mem, t_ops = by / p["hbm_bytes_per_s"], ops / p["int8_ops"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
