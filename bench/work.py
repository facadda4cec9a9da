"""Operations and bytes the algorithm requires, from shapes and served records.

These count what the work needs whatever implements it, never a padded
layout, so a later implementation of the same work is held to the same
count.  Peaks come from ``bench/peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
import pathlib

F32 = 4
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"flops": ..., "hbm_bytes_per_s": ...}`` of one chip of this kind."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table["devices"][device_kind]


def megabatch_rows(k: int, m: int, m_sobol: int) -> int:
    """Model rows of one planner iteration: AMI, the point, Saltelli A/B/AB."""
    return m + 1 + (k + 2) * m_sobol


def request_rows(k: int, m: int, m_sobol: int, iters: int) -> int:
    """Model rows one served request needed: the z⁰ evaluation (its Saltelli
    block only where the guarantee failed at z⁰) and one megabatch per
    planner iteration."""
    init = m + 1 + ((k + 2) * m_sobol if iters > 0 else 0)
    return init + iters * megabatch_rows(k, m, m_sobol)


def request_work(shape: dict, z, iters: int) -> tuple[float, float]:
    """(operations, bytes) one served request required.

    Bytes: the rows it sampled (Σ_j z_j f32 values) and its model rows'
    features ((k + e) f32 each).  Operations: ``ops_per_row`` per model row,
    as the file of the model's kind counts them (trees × depth for trees).
    """
    k, e = shape["k"], shape["e"]
    rows = request_rows(k, shape["m"], shape["m_sobol"], int(iters))
    nbytes = F32 * float(sum(int(x) for x in z)) + F32 * rows * (k + e)
    ops = float(rows) * shape["ops_per_row"]
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])


def prefix_power_sums_work(k: int, cap: int) -> tuple[float, float]:
    """(operations, bytes) of one ``prefix_power_sums`` call on (k, cap).

    Reads the k·cap f32 values, writes the (k, cap, 4) f32 running sums of
    u, u², u³, u⁴ (u = v − shift): per value one subtraction, three
    multiplications and four additions.
    """
    n = k * cap
    return 8.0 * n, F32 * n * (1 + 4)
