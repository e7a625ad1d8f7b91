"""Peaks of each chip the benchmark runs on, and the operations and bytes
of the kernels whose roofline share it reports.

A device kind that is not in ``PEAKS`` is an error, never a default.
"""
from __future__ import annotations

# device_kind as JAX reports it -> published peaks of one chip
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "bytes_per_s": 819e9,         # HBM
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       "source") from None


def uct_scores_cost(out_shape: str, actions: int) -> tuple:
    """(flops, bytes) that one ``uct_scores`` call needs, from the output
    shape its trace event names, e.g. ``f32[4,8,128]``: the search scores
    one node of ``actions`` edges per game, so each ``[8, 128]`` tile
    holds one row of work, and the leading dimensions count the games.
    Per row: six f32 inputs and the f32 output of ``actions`` values,
    and four f32 per-row scalars; 13 operations per edge (an add and a
    max for the effective count; two multiplies, a subtract and a divide
    for q; a divide, a sqrt and a multiply for the bonus; two adds and
    two selects for the score) and one log per row.  Padding is not
    work: what the kernel moves beyond this is its waste."""
    dims = [int(d) for d in out_shape.split("[")[1].rstrip("]").split(",")]
    rows = 1
    for d in dims[:-2]:
        rows *= d
    return rows * (13 * actions + 1), rows * (7 * 4 * actions + 4 * 4)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple:
    """(share of the roofline in %, the bound: 'memory' or 'compute')."""
    p = peaks(device_kind)
    t_mem = nbytes / p["bytes_per_s"]
    t_flop = flops / p["flops_per_s"]
    bound = "memory" if t_mem >= t_flop else "compute"
    return 100.0 * max(t_mem, t_flop) / seconds, bound
