"""Work of the route-score kernel, counted from shapes, and the peaks.

The chunked router scores a chunk of ``c`` requests against ``n``
servers in one kernel call: the switch-free base of eq. 11,
``prompt / uplink + work / flops``, with the cell mask folded in as
``+inf``. The work counted is what that algorithm needs, not what one
implementation does: each request's prompt bits, work and cell and each
server's uplink, FLOP rate and cell read once, the ``(c, n)`` float32
panel written once, and three floating-point operations a pair (two
divisions and an add; the mask's compare and select are not counted).
No padded lanes, no padded contraction, no re-read of the panel.
"""
from __future__ import annotations

import json
import pathlib

WORD = 4  # float32 / int32 bytes
REQUEST_COLUMNS = 3  # prompt_bits, work, cell
SERVER_COLUMNS = 3  # uplink_bps, flops_per_s, cell
FLOPS_PER_PAIR = 3


def route_score_work(c: int, n: int) -> dict:
    """Bytes and FLOPs one ``(c, n)`` score panel needs."""
    return {
        "bytes": WORD * (c * REQUEST_COLUMNS + n * SERVER_COLUMNS + c * n),
        "flops": FLOPS_PER_PAIR * c * n,
    }


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json")
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(work: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of FLOPs over the FLOP peak and
    bytes over the memory bandwidth, and which of the two it is."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
