"""The control of `correct`: the plain reference put in the program's
place, with one stated guarantee broken, must come out as not correct.

The guarantee broken is "every column exact, float64 to the bit". The
step that tempts on this chip is the next precision down: its own f64
is an f32 pair, so carrying a float64 column as a device float (and not
as its int64 bit pattern, `io/columnar.DeviceColumn.raw`) rounds and
clamps it; and a predicate over dates fuses most cheaply over the
chip's native 16-bit lanes, bfloat16, which holds a day count only to
8 bits. `lossy_tables` is the dataset with every float64 column taken
through float32 and every date column through bfloat16; the reference
computed over it is the control.
"""

from __future__ import annotations

import numpy as np

from lib import compare


def lossy_tables(tables: dict, dataset) -> dict:
    import ml_dtypes

    def lossy(name, data):
        if data.dtype == np.float64:
            with np.errstate(over="ignore", under="ignore"):
                return data.astype(np.float32).astype(np.float64)
        if name in dataset.DATE_COLUMNS:
            return data.astype(ml_dtypes.bfloat16).astype(data.dtype)
        return data

    return {t: {name: lossy(name, data) for name, data in cols.items()}
            for t, cols in tables.items()}


def control_reading(reference_module, tables: dict, dataset, query: dict,
                    params: dict) -> dict:
    """{mismatched_rows, rows}: the reference over the lossy tables
    against the reference over the exact ones, by the comparison that
    decides `correct`."""
    exact = reference_module.Reference(tables).answer(query, params)
    lossy = reference_module.Reference(
        lossy_tables(tables, dataset)).answer(query, params)
    return {"mismatched_rows": compare.mismatched_rows(
                compare.reference_columns(lossy),
                compare.SortedRows(compare.reference_columns(exact))),
            "rows": len(next(iter(exact.values())))}
