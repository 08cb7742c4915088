"""The bytes an operator has to move, from shapes alone, and the least
time the chip could take for them. Kept with the benchmark so that no
PR that claims a gain can change how its share is computed. These
operators compare and move 64-bit values and do no arithmetic worth
counting, so HBM bandwidth is the bound for all of them.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}: add it with its source")
    return table[device_kind]


def index_bytes(rows: int) -> int:
    """Bytes of one entry of a gather-index vector over `rows` rows."""
    return 4 if rows < 2 ** 31 else 8


def join_min_bytes(left_rows: int, right_rows: int, out_rows: int,
                   key_bytes: int = 8) -> int:
    """An equi-join that yields a pair of gather-index vectors, whatever
    implements it: each side's key column read once, each index vector
    written once. Payload gathers are not the join's."""
    return ((left_rows + right_rows) * key_bytes
            + out_rows * (index_bytes(left_rows) + index_bytes(right_rows)))


def stage_min_bytes(rows: int, predicate_bytes_per_row: int,
                    selected_rows: int, returned_bytes_per_row: int) -> int:
    """A filter + project stage: the predicate's columns read over every
    row, the returned columns read and written for the selected rows."""
    return (rows * predicate_bytes_per_row
            + 2 * selected_rows * returned_bytes_per_row)


def least_seconds(n_bytes: int, device_kind: str) -> float:
    return n_bytes / peaks(device_kind)["hbm_bytes_per_s"]


def share_pct(n_bytes: int, device_seconds: float, device_kind: str):
    """Share of the HBM roofline in percent; None where there is no
    device time to divide by."""
    if not device_seconds or device_seconds <= 0:
        return None
    return 100.0 * least_seconds(n_bytes, device_kind) / device_seconds


def column_bytes(name: str, dataset) -> int:
    """Bytes a column takes on the device, by the type its dataset
    (a module of `datasets/`) gives it: dictionary codes and dates 4,
    everything else 8."""
    if name in dataset.VOCABULARY or name in dataset.DATE_COLUMNS:
        return 4
    return 8
