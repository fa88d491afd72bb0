"""The least work of one max-min rate solve, from its shapes alone.

A survey simulation on W workers has at most F = ``download_slots`` x W
downloads in flight (Appendix A of the paper: 4 per destination worker),
each using the upload of its source and the download of its destination:
2W resources.  Whatever an implementation does, one solve must read the
flows' endpoints, their active flags and the 2W capacities and write F
rates (4-byte words each), and must pass once over the flow -> resource
incidence: one multiply-add per (flow, resource) pair.  The count never
includes the number of filling rounds an implementation chooses to run,
so it stays valid for a kernel that packs simulations or stops early.
"""
from __future__ import annotations

WORD = 4


def waterfill_work(lanes: int, workers: int, download_slots: int = 4):
    """``(flops, bytes)`` of one batched solve over ``lanes`` simulations."""
    flows = download_slots * workers
    resources = 2 * workers
    flops = 2 * flows * resources
    nbytes = WORD * (3 * flows + resources + flows)
    return lanes * flops, lanes * nbytes


def least_time_s(flops: float, nbytes: float, peak: dict):
    """``(seconds, bound)``: the larger of compute and memory time at the
    chip's peaks, and which of the two it is."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
