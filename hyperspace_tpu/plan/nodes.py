"""Logical plan nodes for the relational IR.

The reference matches/rewrites Catalyst trees
(`Project(Filter(LogicalRelation))`, `Join(l, r, cond)`); this framework owns
an equivalent minimal node set: Scan (= LogicalRelation over lake files),
Filter, Project, Join. Nodes are immutable, JSON-serializable (see
`plan/serde.py`), and carry enough metadata (root paths, bucket spec) for the
rewrite rules to swap base-table scans for index scans exactly as the
reference's rules do (`index/rules/FilterIndexRule.scala:109-131`,
`index/rules/JoinIndexRule.scala:124-153`).
"""

from __future__ import annotations

import glob
import os
from functools import cached_property
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.plan.expr import Expression
from hyperspace_tpu.plan.schema import Schema


@dataclass(frozen=True)
class BucketSpec:
    """Bucketing metadata: the key enabler of shuffle-free joins.

    Parity: Spark `BucketSpec(numBuckets, bucketedBy, sortedBy)` as used at
    reference `index/DataFrameWriterExtensions.scala:49-66` (write side) and
    `index/rules/JoinIndexRule.scala:124-153` (read side).
    """

    num_buckets: int
    bucket_columns: tuple
    sort_columns: tuple

    def to_dict(self) -> dict:
        return {"numBuckets": self.num_buckets,
                "bucketColumns": list(self.bucket_columns),
                "sortColumns": list(self.sort_columns)}

    @staticmethod
    def from_dict(d: Optional[dict]) -> Optional["BucketSpec"]:
        if d is None:
            return None
        return BucketSpec(int(d["numBuckets"]), tuple(d["bucketColumns"]),
                          tuple(d["sortColumns"]))


class LogicalPlan:
    """Base plan node."""

    @property
    def children(self) -> List["LogicalPlan"]:
        return []

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def with_children(self, children: List["LogicalPlan"]) -> "LogicalPlan":
        raise NotImplementedError

    def transform_up(self, fn: Callable[["LogicalPlan"], "LogicalPlan"]) -> "LogicalPlan":
        new_children = [c.transform_up(fn) for c in self.children]
        node = self if new_children == self.children else self.with_children(new_children)
        return fn(node)

    def transform_down(self, fn: Callable[["LogicalPlan"], "LogicalPlan"]) -> "LogicalPlan":
        node = fn(self)
        new_children = [c.transform_down(fn) for c in node.children]
        return node if new_children == node.children else node.with_children(new_children)

    def collect_leaves(self) -> List["LogicalPlan"]:
        if not self.children:
            return [self]
        out: List[LogicalPlan] = []
        for c in self.children:
            out.extend(c.collect_leaves())
        return out

    def is_linear(self) -> bool:
        """True iff every node has at most one child — the join rule's guard
        against signature collisions (reference `JoinIndexRule.scala:210-211`)."""
        if len(self.children) > 1:
            return False
        return all(c.is_linear() for c in self.children)

    def to_dict(self) -> dict:
        raise NotImplementedError

    def simple_string(self) -> str:
        raise NotImplementedError

    def tree_string(self, depth: int = 0) -> str:
        lines = [("  " * depth) + ("+- " if depth else "") + self.simple_string()]
        for c in self.children:
            lines.append(c.tree_string(depth + 1))
        return "\n".join(lines)

    def __eq__(self, other) -> bool:
        if type(self) is not type(other):
            return NotImplemented
        return self.to_dict() == other.to_dict()

    def __hash__(self):
        return hash(self.simple_string())


class Scan(LogicalPlan):
    """Leaf relation over lake files (= reference `LogicalRelation` over
    `HadoopFsRelation`). Carries root paths, schema, format, and an optional
    bucket spec; `files()` resolves the concrete file listing (= the
    reference's `location.allFiles`, `actions/CreateActionBase.scala:89-97`).
    """

    def __init__(self, root_paths: Sequence[str], schema: Schema,
                 file_format: str = "parquet",
                 bucket_spec: Optional[BucketSpec] = None,
                 files: Optional[Sequence[str]] = None,
                 index_name: Optional[str] = None,
                 pinned_version: Optional[int] = None,
                 appended: bool = False):
        from hyperspace_tpu.utils.storage import canonical
        self.root_paths = [canonical(p) for p in root_paths]
        self._schema = schema
        self.file_format = file_format
        self.bucket_spec = bucket_spec
        # Snapshot pin (set by `Rule.index_scan`): the committed `v__=N`
        # this plan resolved AT PLAN TIME. A pinned scan's file listing
        # is resolved once when the pin is taken and never re-listed at
        # execution, so a maintenance writer racing the query between
        # plan and scan can neither add files to nor swap the version
        # this plan reads (the segment cache keys on the same version).
        # In-process only, like index_name: excluded from to_dict().
        self.pinned_version = pinned_version
        # Set iff a rewrite rule swapped this scan in over INDEX data
        # (`Rule.index_scan`): the execution-time marker the graceful-
        # degradation path keys on — an index scan whose data is missing
        # or unreadable raises IndexDataUnavailableError instead of
        # silently serving empty, and the query falls back to the source
        # plan. In-process only: deliberately excluded from to_dict()
        # (identity/serde), since a serialized plan never carries rule
        # rewrites.
        self.index_name = index_name
        # Set iff a rewrite rule made this scan hybrid scan's branch
        # over the files APPENDED since an index was built (its explicit
        # file list is exactly those). In-process only, like index_name:
        # what the scan's telemetry keys on.
        self.appended = appended
        # An EXPLICIT file list (hybrid scan / incremental deltas) restricts
        # the scan and is part of its identity; a lazily-cached glob is not.
        self._explicit_files = files is not None
        self._files = list(files) if files is not None else None

    @property
    def schema(self) -> Schema:
        return self._schema

    def with_children(self, children):
        if children:
            raise HyperspaceException("Scan is a leaf node.")
        return self

    def files(self) -> List[str]:
        """Enumerate data files under the root paths (cached per node)."""
        if self._files is None:
            from hyperspace_tpu.utils import storage
            found: List[str] = []
            for root in self.root_paths:
                if storage.is_url(root):
                    fs, real = storage.get_fs(root)
                    proto = storage.protocol_of(root)
                    if fs.isfile(real):
                        found.append(root)
                    else:
                        found.extend(
                            proto + p for p in fs.find(real)
                            if p.endswith("." + self.file_format))
                    continue
                if os.path.isfile(root):
                    found.append(root)
                else:
                    pattern = os.path.join(root, "**", f"*.{self.file_format}")
                    found.extend(glob.glob(pattern, recursive=True))
            self._files = sorted(found)
        return self._files

    def to_dict(self) -> dict:
        d = {"node": "scan", "rootPaths": list(self.root_paths),
             "format": self.file_format,
             "schema": [f.to_dict() for f in self._schema.fields],
             "bucketSpec": self.bucket_spec.to_dict() if self.bucket_spec else None}
        if self._explicit_files:
            d["files"] = list(self._files)
        return d

    def simple_string(self) -> str:
        bucket = f", buckets={self.bucket_spec.num_buckets}" if self.bucket_spec else ""
        restrict = (f", files={len(self._files)}" if self._explicit_files else "")
        return (f"Scan {self.file_format} [{', '.join(self._schema.names)}] "
                f"roots={self.root_paths}{bucket}{restrict}")


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.condition = condition
        self.child = child

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def with_children(self, children):
        (child,) = children
        return Filter(self.condition, child)

    def to_dict(self) -> dict:
        return {"node": "filter", "condition": self.condition.to_dict(),
                "child": self.child.to_dict()}

    def simple_string(self) -> str:
        return f"Filter ({self.condition!r})"


class Project(LogicalPlan):
    """Projection. Entries are plain column names (pass-through) or
    `Alias(expr, name)` computed columns — the reference rides Catalyst's
    `Project(projectList: Seq[NamedExpression], ...)`; this engine
    evaluates computed entries with the same XLA-fused compiler filters
    use (`engine/compiler.py`)."""

    def __init__(self, columns: Sequence, child: LogicalPlan):
        from hyperspace_tpu.plan.expr import Alias, Expression
        entries = []
        for c in columns:
            if isinstance(c, str) or isinstance(c, Alias):
                entries.append(c)
            elif isinstance(c, Expression):
                raise HyperspaceException(
                    f"Projection expression needs a name: use "
                    f".alias(...) on {c!r}.")
            else:
                raise HyperspaceException(f"Bad projection entry: {c!r}")
        self.columns = entries
        self.child = child

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    def output_names(self) -> List[str]:
        return [c if isinstance(c, str) else c.name for c in self.columns]

    def references(self) -> set:
        """Source column names this projection reads (plain entries
        reference themselves)."""
        out: set = set()
        for c in self.columns:
            if isinstance(c, str):
                out.add(c)
            else:
                out |= c.references()
        return out

    def is_simple(self) -> bool:
        """True when every entry is a plain column name (the shape the
        rewrite rules and bucketed chains reason about)."""
        return all(isinstance(c, str) for c in self.columns)

    @property
    def schema(self) -> Schema:
        memo = self.__dict__.get("_schema_memo")
        if memo is None:
            from hyperspace_tpu.plan.expr import infer_dtype
            from hyperspace_tpu.plan.schema import Field
            fields = []
            for c in self.columns:
                if isinstance(c, str):
                    fields.append(self.child.schema.field(c))
                else:
                    fields.append(Field(c.name,
                                        infer_dtype(c.child,
                                                    self.child.schema),
                                        True))
            memo = self.__dict__["_schema_memo"] = Schema(fields)
        return memo

    def with_children(self, children):
        (child,) = children
        return Project(self.columns, child)

    def to_dict(self) -> dict:
        return {"node": "project",
                "columns": [c if isinstance(c, str) else c.to_dict()
                            for c in self.columns],
                "child": self.child.to_dict()}

    def simple_string(self) -> str:
        parts = [c if isinstance(c, str) else repr(c) for c in self.columns]
        return f"Project [{', '.join(parts)}]"


_AGG_FUNCS = ("sum", "count", "min", "max", "avg", "stddev",
              "count_distinct")


@dataclass(frozen=True)
class AggSpec:
    """One aggregation: func over an input (a column name, "*" for
    count(*), or a value Expression — e.g. sum(x * y))."""

    func: str
    column: object  # str | Expression
    alias: str

    def __post_init__(self):
        # Window reuses this spec shape with its own function set;
        # Aggregate and Window each validate against theirs.
        if self.func not in _AGG_FUNCS + ("rank", "dense_rank",
                                          "row_number"):
            raise HyperspaceException(f"Unsupported aggregate: {self.func}")

    @property
    def is_expression(self) -> bool:
        from hyperspace_tpu.plan.expr import Expression
        return isinstance(self.column, Expression)

    def references(self) -> set:
        if self.is_expression:
            return self.column.references()
        return set() if self.column == "*" else {self.column}

    def input_dtype(self, child_schema) -> str:
        from hyperspace_tpu.plan.expr import infer_dtype
        if self.is_expression:
            return infer_dtype(self.column, child_schema)
        return child_schema.field(self.column).dtype

    def to_dict(self) -> dict:
        column = (self.column.to_dict() if self.is_expression
                  else self.column)
        return {"func": self.func, "column": column, "alias": self.alias}

    @staticmethod
    def from_dict(d: dict) -> "AggSpec":
        from hyperspace_tpu.plan.expr import Expression
        column = d["column"]
        if isinstance(column, dict):
            column = Expression.from_dict(column)
        return AggSpec(d["func"], column, d["alias"])


class Aggregate(LogicalPlan):
    """Group-by aggregation (sum/count/min/max/avg). The reference delegates
    aggregation to Spark SQL; this framework's engine executes it as
    device segment reductions over sorted groups."""

    def __init__(self, group_columns: Sequence[str],
                 aggregates: Sequence[AggSpec], child: LogicalPlan):
        self.group_columns = list(group_columns)
        self.aggregates = list(aggregates)
        if not self.aggregates and not self.group_columns:
            raise HyperspaceException(
                "Aggregate requires group columns or at least one "
                "aggregation expression.")
        for spec in self.aggregates:
            if spec.func not in _AGG_FUNCS:
                raise HyperspaceException(
                    f"Unsupported aggregate: {spec.func}")
        # Group columns with no aggregates = DISTINCT over those columns.
        self.child = child

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    @cached_property
    def schema(self) -> Schema:
        from hyperspace_tpu.plan.schema import Field
        fields = [self.child.schema.field(c) for c in self.group_columns]
        for spec in self.aggregates:
            if spec.func in ("count", "count_distinct"):
                dtype = "int64"
            elif spec.func in ("avg", "stddev"):
                dtype = "float64"
            elif spec.func == "sum":
                src = spec.input_dtype(self.child.schema)
                dtype = ("float64" if src in ("float32", "float64")
                         else "int64")
            else:  # min/max keep the input type
                dtype = spec.input_dtype(self.child.schema)
            fields.append(Field(spec.alias, dtype, True))
        return Schema(fields)

    def with_children(self, children):
        (child,) = children
        return Aggregate(self.group_columns, self.aggregates, child)

    def to_dict(self) -> dict:
        return {"node": "aggregate", "groupBy": list(self.group_columns),
                "aggregates": [a.to_dict() for a in self.aggregates],
                "child": self.child.to_dict()}

    def simple_string(self) -> str:
        aggs = ", ".join(f"{a.func}({a.column}) AS {a.alias}"
                         for a in self.aggregates)
        return f"Aggregate [{', '.join(self.group_columns)}] [{aggs}]"


_WINDOW_FUNCS = ("rank", "dense_rank", "row_number", "sum", "avg", "min",
                 "max", "count")


class Window(LogicalPlan):
    """Window functions: appends one column per spec to the child's rows
    (input row order preserved). `partition_by` are plain column names;
    `order_by` uses Sort's spec syntax ("name" asc / "-name" desc) and is
    required by the rank family. The reference delegates windows to Spark
    SQL; this engine executes them as sorted-segment computations
    (`ops/window.py`)."""

    def __init__(self, partition_by: Sequence[str], order_by: Sequence[str],
                 specs: Sequence[AggSpec], child: LogicalPlan):
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.specs = list(specs)
        self.child = child
        if not self.specs:
            raise HyperspaceException("Window requires at least one spec.")
        for spec in self.specs:
            if spec.func not in _WINDOW_FUNCS:
                raise HyperspaceException(
                    f"Unsupported window function: {spec.func}")
            if spec.func in ("rank", "dense_rank") and not self.order_by:
                raise HyperspaceException(
                    f"{spec.func} requires an ORDER BY.")
            if spec.is_expression:
                raise HyperspaceException(
                    "Window inputs must be plain columns; project the "
                    "expression first.")
            if (spec.column == "*"
                    and spec.func not in ("rank", "dense_rank",
                                          "row_number", "count")):
                raise HyperspaceException(
                    f"Window {spec.func} requires a column input.")
            if child.schema.contains(spec.alias):
                raise HyperspaceException(
                    f"Window output name collides with an input column: "
                    f"{spec.alias}")

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    @cached_property
    def schema(self) -> Schema:
        from hyperspace_tpu.plan.schema import Field
        fields = list(self.child.schema.fields)
        for spec in self.specs:
            if spec.func in ("rank", "dense_rank", "row_number", "count"):
                dtype = "int64"
            elif spec.func == "avg":
                dtype = "float64"
            elif spec.func == "sum":
                src = spec.input_dtype(self.child.schema)
                dtype = ("float64" if src in ("float32", "float64")
                         else "int64")
            else:  # min/max keep the input type
                dtype = spec.input_dtype(self.child.schema)
            fields.append(Field(spec.alias, dtype, True))
        return Schema(fields)

    def with_children(self, children):
        (child,) = children
        return Window(self.partition_by, self.order_by, self.specs, child)

    def to_dict(self) -> dict:
        return {"node": "window", "partitionBy": list(self.partition_by),
                "orderBy": list(self.order_by),
                "specs": [s.to_dict() for s in self.specs],
                "child": self.child.to_dict()}

    def simple_string(self) -> str:
        parts = [f"{s.func}({s.column}) AS {s.alias}" for s in self.specs]
        order = f" ORDER BY {', '.join(self.order_by)}" if self.order_by \
            else ""
        return (f"Window [{', '.join(parts)}] PARTITION BY "
                f"[{', '.join(self.partition_by)}]{order}")


def sort_direction(column: str):
    """Parse a sort spec: "name" -> (name, False); "-name" -> (name, True)
    (descending). Descending follows Spark's default null placement:
    ascending is nulls-first, descending is nulls-last."""
    if column.startswith("-"):
        return column[1:], True
    return column, False


class Sort(LogicalPlan):
    """ORDER BY. Plain column names sort ascending (nulls first); a
    leading "-" sorts that column descending (nulls last)."""

    def __init__(self, columns: Sequence[str], child: LogicalPlan):
        self.columns = list(columns)
        self.child = child
        for spec in self.columns:
            name, desc = sort_direction(spec)
            if desc and child.schema.contains(spec):
                # A column literally named "-x" would silently alias
                # column "x" descending; fail loudly instead.
                raise HyperspaceException(
                    f"Ambiguous sort spec {spec!r}: a column with that "
                    "literal name exists; rename it to sort by it.")

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def with_children(self, children):
        (child,) = children
        return Sort(self.columns, child)

    def to_dict(self) -> dict:
        return {"node": "sort", "columns": list(self.columns),
                "child": self.child.to_dict()}

    def simple_string(self) -> str:
        parts = [f"{name} DESC" if desc else name
                 for name, desc in map(sort_direction, self.columns)]
        return f"Sort [{', '.join(parts)}]"


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        if n < 0:
            raise HyperspaceException("Limit must be non-negative.")
        self.n = n
        self.child = child

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.child]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def with_children(self, children):
        (child,) = children
        return Limit(self.n, child)

    def to_dict(self) -> dict:
        return {"node": "limit", "n": self.n, "child": self.child.to_dict()}

    def simple_string(self) -> str:
        return f"Limit {self.n}"


class Union(LogicalPlan):
    """Row-wise union of same-schema children (column names must align).
    Exists for Hybrid Scan: index data UNION appended source files."""

    def __init__(self, children: Sequence[LogicalPlan]):
        if not children:
            raise HyperspaceException("Union requires at least one child.")
        self._children = list(children)
        names0 = [n.lower() for n in self._children[0].schema.names]
        for c in self._children[1:]:
            if [n.lower() for n in c.schema.names] != names0:
                raise HyperspaceException(
                    "Union children must share column names/order.")

    @property
    def children(self) -> List[LogicalPlan]:
        return list(self._children)

    @property
    def schema(self) -> Schema:
        return self._children[0].schema

    def with_children(self, children):
        return Union(children)

    def to_dict(self) -> dict:
        return {"node": "union",
                "children": [c.to_dict() for c in self._children]}

    def simple_string(self) -> str:
        return f"Union ({len(self._children)} children)"


class SetOp(LogicalPlan):
    """SQL set operation with DISTINCT semantics (INTERSECT / EXCEPT):
    output = DISTINCT rows of `left` present in (Intersect) / absent from
    (Except) `right`. Row equality treats NULL as equal to NULL — SQL set
    operations, UNLIKE joins, group nulls together. The reference's serde
    zoo exists to make exactly these queries serializable
    (`index/serde/package.scala:64-167`, IntersectWrapper/ExceptWrapper);
    this IR carries them natively (TPC-DS q8/q14/q38/q87)."""

    kind: str = ""

    def __init__(self, left: LogicalPlan, right: LogicalPlan):
        ln = [n.lower() for n in left.schema.names]
        rn = [n.lower() for n in right.schema.names]
        if ln != rn:
            raise HyperspaceException(
                f"{type(self).__name__} sides must share column "
                f"names/order; got {ln} vs {rn}.")
        self.left = left
        self.right = right

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.left, self.right]

    @property
    def schema(self) -> Schema:
        return self.left.schema

    def with_children(self, children):
        left, right = children
        return type(self)(left, right)

    def to_dict(self) -> dict:
        return {"node": self.kind, "left": self.left.to_dict(),
                "right": self.right.to_dict()}

    def simple_string(self) -> str:
        return type(self).__name__


class Intersect(SetOp):
    kind = "intersect"


class Except(SetOp):
    kind = "except"


_JOIN_TYPES = ("inner", "left_outer", "right_outer", "full_outer",
               "left_semi", "left_anti", "cross")


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 condition: Optional[Expression], join_type: str = "inner"):
        if join_type not in _JOIN_TYPES:
            raise HyperspaceException(f"Unsupported join type: {join_type}")
        if (condition is None) != (join_type == "cross"):
            raise HyperspaceException(
                "cross joins take no condition; every other join type "
                "requires one.")
        self.left = left
        self.right = right
        self.condition = condition
        self.join_type = join_type

    @property
    def children(self) -> List[LogicalPlan]:
        return [self.left, self.right]

    @cached_property
    def schema(self) -> Schema:
        """Left fields then right fields; duplicate names get a `_r` suffix
        on the right (matching the executor's output); outer joins make the
        nullable side's fields nullable; semi/anti joins output the left
        side only. Memoized — nodes are immutable, and deep query trees
        re-ask for ancestor schemas repeatedly."""
        from hyperspace_tpu.plan.schema import Field as SchemaField
        if self.join_type in ("left_semi", "left_anti"):
            return self.left.schema
        fields = list(self.left.schema.fields)
        left_names = {f.name.lower() for f in fields}
        if self.join_type in ("right_outer", "full_outer"):
            fields = [SchemaField(f.name, f.dtype, True) for f in fields]
        right_nullable = self.join_type in ("left_outer", "full_outer")
        for f in self.right.schema.fields:
            name = (f.name if f.name.lower() not in left_names
                    else f.name + "_r")
            fields.append(SchemaField(name, f.dtype,
                                      f.nullable or right_nullable))
        return Schema(fields)

    def with_children(self, children):
        left, right = children
        return Join(left, right, self.condition, self.join_type)

    def to_dict(self) -> dict:
        return {"node": "join", "type": self.join_type,
                "condition": (self.condition.to_dict()
                              if self.condition is not None else None),
                "left": self.left.to_dict(), "right": self.right.to_dict()}

    def simple_string(self) -> str:
        if self.condition is None:
            return f"Join {self.join_type}"
        return f"Join {self.join_type} ({self.condition!r})"
