"""The work an IVF search needs, counted from its configuration, and the
chip peaks it is held against.

The counts are the algorithm's, whatever implements it:

* scan FLOPs = 2 * q * n_probes * (n_rows / n_lists) * dim;
* scan bytes = min(n_rows, q * n_probes * n_rows / n_lists) * dim *
  payload_bytes + q * dim * 4: each probed list is read at most once per
  dispatch, and the float32 queries once.

The scan is held against the chip's fastest published rate for an 8-bit
payload (int8 operations), so no implementation can read above 100% by
changing how it computes.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak row of ``device_kind``; an unlisted device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {path}; "
                       f"listed: {sorted(table)}")
    return table[device_kind]


def payload_bytes(cfg: dict) -> int:
    return {"uint8": 1, "int8": 1, "float16": 2, "bfloat16": 2,
            "float32": 4}[cfg["scan_payload"]]


def scan_work(cfg: dict, q: int) -> tuple:
    """(FLOPs, bytes) of the list scan of one dispatch of ``q`` queries."""
    rows_per_list = cfg["n_rows"] / cfg["n_lists"]
    dim = cfg["scan_dim"]
    pairs_rows = q * cfg["n_probes"] * rows_per_list
    flops = 2.0 * pairs_rows * dim
    nbytes = min(cfg["n_rows"], pairs_rows) * dim * payload_bytes(cfg) \
        + q * cfg["dim"] * 4
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple:
    """(share of the roofline in %, the bound that binds: "compute" or
    "memory") for work done in ``seconds`` of kernel time."""
    t_compute = flops / peaks["int8_ops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
