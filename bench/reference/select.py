"""The plain reference of the operation `select`: what the query a
traffic file describes must return, worked out in numpy from the
generated tables alone. Imports nothing of the program and takes
nothing the program made.

A plain reference is a module of `reference/` with a class
`Reference(tables)` that has `answer(query, params) -> {column:
ndarray}`, rows in no particular order."""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, tables: dict):
        self.tables = tables
        self._sorted = {}

    def _order(self, table: str, column: str):
        key = (table, column)
        if key not in self._sorted:
            values = self.tables[table][column]
            order = np.argsort(values, kind="stable")
            self._sorted[key] = (order, values[order])
        return self._sorted[key]

    def answer(self, query: dict, params: dict) -> dict:
        """{column: ndarray} in no particular row order."""
        left = self.tables[query["table"]]
        rows = None
        if "range" in query:
            order, sorted_values = self._order(query["table"],
                                               query["range"]["column"])
            lo = np.searchsorted(sorted_values, params["lo"], side="left")
            hi = np.searchsorted(sorted_values, params["hi"], side="left")
            rows = order[lo:hi]
        out = {}
        right_rows = None
        if "join" in query:
            j = query["join"]
            order, sorted_keys = self._order(j["table"], j["right_on"])
            if len(sorted_keys) > 1 and \
                    not np.all(sorted_keys[1:] != sorted_keys[:-1]):
                raise ValueError("the reference joins to a unique key only")
            lkeys = left[j["left_on"]] if rows is None \
                else left[j["left_on"]][rows]
            lo, hi = int(sorted_keys[0]), int(sorted_keys[-1])
            if hi - lo < 4 * len(sorted_keys):
                # keys packed densely: a table from key to sorted position
                # (one gather instead of 18 M binary searches)
                position = np.full(hi - lo + 2, len(sorted_keys) - 1,
                                   dtype=np.int64)
                position[sorted_keys - lo] = np.arange(len(sorted_keys))
                at_clipped = position[np.clip(lkeys - lo, -1, hi - lo + 1)]
            else:
                at_clipped = np.minimum(np.searchsorted(sorted_keys, lkeys),
                                        len(sorted_keys) - 1)
            hit = sorted_keys[at_clipped] == lkeys
            if not hit.all():  # inner join: unmatched left rows drop out
                rows = (np.arange(len(lkeys)) if rows is None else rows)[hit]
                at_clipped = at_clipped[hit]
            right_rows = order[at_clipped]
        for name in query["select"]:
            if name in left:
                out[name] = left[name] if rows is None else left[name][rows]
            else:
                out[name] = self.tables[query["join"]["table"]][name][
                    right_rows]
        return out
