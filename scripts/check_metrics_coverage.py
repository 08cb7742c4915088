#!/usr/bin/env python
"""Lint: every PhysicalNode subclass must emit operator metrics
records, and every Action subclass must emit an action report.

`PhysicalNode.__init_subclass__` (engine/physical.py) wraps each
subclass's `execute` / `execute_bucketed` with the telemetry operator
hook and stamps the wrapper with `__telemetry_instrumented__`;
`Action.__init_subclass__` (actions/base.py) does the same for `run`
with `__action_report_instrumented__`. This check imports EVERY module
under `hyperspace_tpu`, walks both live subclass trees, and fails if
any subclass resolves an entry point to an unstamped callable — i.e.
an operator that could execute without a metrics record, or an index
maintenance action that could run without emitting its structured
report (assigned after class creation, shadowed by a plain function,
or otherwise routed around the instrumentation).

Compile coverage rides the same check: every `jax.jit` entry point
must route through `telemetry.compilation.instrumented_jit` (the
compile-span stamp — trace counters, retrace-cause events, Perfetto
compile track — and the program's device scope). `jax.jit` in any
spelling (`jax.jit(...)`, `@jax.jit`, `__import__("jax").jit`,
`partial(jax.jit, ...)`, `from jax import jit`) anywhere in the package
besides telemetry/compilation.py is a jit entry point that can trace
without being seen, and fails the lint; so does an `instrumented_jit`
whose `scope=` is missing or not a literal key of `DEVICE_SCOPES`, a
`DEVICE_SCOPES` that differs from docs/telemetry.md's device-scope
table, and a registered wrapper missing its
`__compile_span_instrumented__` stamp.

Runs in the tier-1 flow via `tests/test_telemetry.py`; also runnable
standalone:  python scripts/check_metrics_coverage.py
"""

import ast
import importlib
import os
import pkgutil
import re
import sys

# A lint over source and docs: it never needs the chip, and pinning the
# CPU keeps it (and the tests that shell out to it) from taking the one
# process slot a chip allows.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _all_subclasses(cls):
    for sub in cls.__subclasses__():
        yield sub
        yield from _all_subclasses(sub)


# Direct jit construction — the only sanctioned caller is the
# instrumented_jit wrapper itself. Read from the syntax tree, so a
# mention of the name in prose never matches and no spelling escapes.
_JIT_ALLOWED = os.path.join("telemetry", "compilation.py")
_SCOPE_TABLE_HEADER = "| Device scope | Where | What |"


def _is_jax(node) -> bool:
    """`jax`, or `__import__("jax")`."""
    if isinstance(node, ast.Name):
        return node.id == "jax"
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "__import__" and node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value == "jax")


def _names_instrumented_jit(node) -> bool:
    return ((isinstance(node, ast.Name) and node.id == "instrumented_jit")
            or (isinstance(node, ast.Attribute)
                and node.attr == "instrumented_jit"))


def _jit_lint(tree, scopes):
    """(lineno, message) of every raw jit and every instrumented_jit
    call whose scope is not a literal key of `scopes`."""
    found = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr == "jit"
                and _is_jax(node.value)):
            found.append((node.lineno, "jit entry point lacks the "
                          "compile-span stamp — route it through "
                          "telemetry.instrumented_jit"))
        elif (isinstance(node, ast.ImportFrom) and node.module == "jax"
              and any(a.name == "jit" for a in node.names)):
            found.append((node.lineno, "`from jax import jit`: route the "
                          "entry point through telemetry.instrumented_jit"))
        elif isinstance(node, ast.Call):
            func = node.func
            is_partial = ((isinstance(func, ast.Name)
                           and func.id == "partial")
                          or (isinstance(func, ast.Attribute)
                              and func.attr == "partial"))
            if not (_names_instrumented_jit(func) or (
                    is_partial and node.args
                    and _names_instrumented_jit(node.args[0]))):
                continue
            scope = next((k.value for k in node.keywords
                          if k.arg == "scope"), None)
            if scope is None:
                found.append((node.lineno, "instrumented_jit without a "
                              "scope= — name the program's device scope "
                              "(telemetry.DEVICE_SCOPES)"))
            elif not (isinstance(scope, ast.Constant)
                      and scope.value in scopes):
                shown = (repr(scope.value) if isinstance(scope, ast.Constant)
                         else "a computed value")
                found.append((node.lineno, f"instrumented_jit scope {shown} "
                              "is not a key of telemetry.DEVICE_SCOPES"))
    return found


def device_scope_table(doc: str):
    """The scope names of docs/telemetry.md's device-scope table."""
    lines = doc.splitlines()
    try:
        i = lines.index(_SCOPE_TABLE_HEADER) + 2
    except ValueError:
        return None
    names = []
    while i < len(lines) and lines[i].startswith("|"):
        m = re.match(r"\|\s*`([^`]+)`", lines[i])
        if m:
            names.append(m.group(1))
        i += 1
    return names


def check_jit_entry_points(package_dir: str, doc_path=None):
    """Source lint: no `jax.jit` in any spelling outside the sanctioned
    wrapper module, every `instrumented_jit` names a scope of
    `DEVICE_SCOPES`, `DEVICE_SCOPES` is docs/telemetry.md's device-scope
    table (where `doc_path` is given), and every registered wrapper
    carries the compile-span stamp."""
    from hyperspace_tpu.telemetry import DEVICE_SCOPES

    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _JIT_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue  # surfaced by the import walk instead
            for lineno, message in sorted(_jit_lint(tree, DEVICE_SCOPES)):
                failures.append(f"hyperspace_tpu/{rel}:{lineno}: {message}")
    if doc_path is not None:
        try:
            with open(doc_path, encoding="utf-8") as f:
                table = device_scope_table(f.read())
        except OSError:
            table = None
        if table is None:
            failures.append(f"{doc_path}: no device-scope table "
                            f"({_SCOPE_TABLE_HEADER!r})")
        else:
            for name in sorted(set(DEVICE_SCOPES) - set(table)):
                failures.append(f"{doc_path}: device scope {name!r} has no "
                                "row in the device-scope table")
            for name in sorted(set(table) - set(DEVICE_SCOPES)):
                failures.append(f"{doc_path}: the device-scope table names "
                                f"{name!r}, which telemetry.DEVICE_SCOPES "
                                "lacks")
    from hyperspace_tpu.telemetry import compilation
    for name, wrapper in sorted(compilation.REGISTRY.items()):
        if not getattr(wrapper, "__compile_span_instrumented__", False):
            failures.append(
                f"instrumented jit {name!r} lost its compile-span stamp")
    return failures


# The ONE sanctioned link seam: every host->device placement routes
# through the pipelined transfer engine (chunked staging, in-flight
# byte window, fault injection, link.{h2d,d2h}.* counters). A raw
# `jax.device_put` anywhere else in the package is a link crossing the
# engine cannot pipeline, observe, or fault-inject. Tests live outside
# the package tree and stay exempt.
_RAW_PUT_RE = re.compile(r"jax\.device_put\s*\(|partial\(\s*jax\.device_put\b")
_PUT_ALLOWED = os.path.join("io", "transfer.py")


def check_device_put_seam(package_dir: str):
    """Source lint: no direct `jax.device_put` outside io/transfer.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _PUT_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_PUT_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: raw "
                            "jax.device_put bypasses the transfer "
                            "engine — route it through io/transfer.py")
    return failures


# The ONE sanctioned device-residency seam: HBM-resident batches live
# in the segment cache (io/segcache.py — version-keyed, byte-budgeted,
# single-flight fills, index-FSM invalidation). The legacy device-batch
# LRU's entry points are BANNED outside that module: a raw
# `_device_cache` map or `read_device_batch(...)` call anywhere else is
# device residency the cache cannot budget, invalidate, or coalesce.
_RAW_DEVCACHE_RE = re.compile(r"\b_device_cache\b|\bread_device_batch\b")
_DEVCACHE_ALLOWED = os.path.join("io", "segcache.py")


def check_segment_cache_seam(package_dir: str):
    """Source lint: no direct `_device_cache`/`read_device_batch`
    access outside io/segcache.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _DEVCACHE_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_DEVCACHE_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: direct "
                            "device-batch cache access bypasses the "
                            "HBM segment cache — route it through "
                            "io/segcache.py")
    return failures


# The ONE sanctioned serving-concurrency point: engine/ code runs on
# the caller's thread or on the sanctioned pools
# (`telemetry.propagating`-wrapped executors); a raw threading.Thread
# in the engine is concurrency the scheduler cannot admit, cancel,
# budget, or drain at shutdown. Only the scheduler module itself may
# own threads (it currently owns none — waiting happens on caller
# threads — but it is the one place that legitimately could).
_RAW_THREAD_RE = re.compile(r"threading\.Thread\s*\(")
_THREAD_ALLOWED = os.path.join("engine", "scheduler.py")


def check_engine_thread_seam(package_dir: str):
    """Source lint: no raw `threading.Thread(...)` under engine/
    outside scheduler.py."""
    failures = []
    engine_dir = os.path.join(package_dir, "engine")
    for root, _dirs, files in os.walk(engine_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _THREAD_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_THREAD_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: raw "
                            "threading.Thread in engine/ — concurrency "
                            "the query scheduler cannot admit, cancel, "
                            "or drain; route it through "
                            "engine/scheduler.py or a propagating-"
                            "wrapped executor")
    return failures


def check_serving_error_counters():
    """Every typed serving error must have a registry counter behind
    it: each `QueryServingError` subclass declares `counter`, and
    `scheduler.SERVING_ERROR_COUNTERS` (the table the scheduler's
    raise-path bookkeeping reads) must list exactly that counter — a
    new serving failure mode cannot ship without a scrape-able
    series."""
    from hyperspace_tpu.engine import scheduler
    from hyperspace_tpu.exceptions import QueryServingError

    failures = []
    seen = set()
    for cls in sorted(set(_all_subclasses(QueryServingError)),
                      key=lambda c: c.__name__):
        counter = getattr(cls, "counter", "")
        if not counter:
            failures.append(
                f"{cls.__module__}.{cls.__name__}: typed serving error "
                "lacks a registry counter (declare `counter = "
                "'serve.<name>'`)")
            continue
        mapped = scheduler.SERVING_ERROR_COUNTERS.get(cls.__name__)
        if mapped != counter:
            failures.append(
                f"{cls.__module__}.{cls.__name__}: counter "
                f"{counter!r} not registered in "
                "scheduler.SERVING_ERROR_COUNTERS "
                f"(found {mapped!r}) — the scheduler cannot count what "
                "it does not know about")
        seen.add(cls.__name__)
    for name in scheduler.SERVING_ERROR_COUNTERS:
        if name not in seen:
            failures.append(
                f"scheduler.SERVING_ERROR_COUNTERS lists {name!r} but "
                "no such QueryServingError subclass exists")
    return failures


# Index-kind serde registry: every derived-dataset index kind must be
# registered in `log_entry.DERIVED_DATASET_KINDS` (so IndexLogEntry
# serde can dispatch it through the log FSM) and must round-trip
# `from_dict(x.to_dict()) == x` on its declared `_serde_sample()`. A
# new index-kind class that ships without registration would serialize
# through `begin()` and then be UNREADABLE by every later action and
# rule — this lint makes that a build failure, not a corrupt catalog.
def check_index_kind_serde():
    from hyperspace_tpu.index import log_entry

    failures = []
    registry = log_entry.DERIVED_DATASET_KINDS
    registered = {cls for cls in registry.values()}
    for name, obj in sorted(vars(log_entry).items()):
        if not isinstance(obj, type):
            continue
        kind = getattr(obj, "kind", None)
        if not isinstance(kind, str) or not kind.endswith("Index"):
            continue
        if obj not in registered:
            failures.append(
                f"index.log_entry.{name}: index-kind class (kind="
                f"{kind!r}) missing from DERIVED_DATASET_KINDS — "
                "IndexLogEntry serde cannot dispatch it")
            continue
        if registry.get(kind) is not obj:
            failures.append(
                f"index.log_entry.{name}: registered under a kind "
                f"string that is not its own ({kind!r})")
    for kind, cls in sorted(registry.items()):
        sample_fn = getattr(cls, "_serde_sample", None)
        if sample_fn is None:
            failures.append(
                f"{cls.__name__}: registered index kind lacks "
                "_serde_sample() — the serde round-trip cannot be "
                "proven")
            continue
        try:
            sample = sample_fn()
            d = sample.to_dict()
            back = log_entry.derived_dataset_from_dict(d)
            if back.to_dict() != d:
                failures.append(
                    f"{cls.__name__}: serde round-trip is lossy "
                    "(from_dict(to_dict(x)).to_dict() != to_dict(x))")
        except Exception as exc:
            failures.append(
                f"{cls.__name__}: serde round-trip raised {exc!r}")
    return failures


# The ONE sanctioned sketch-consultation point: data-skipping pruning
# decisions live in the rules module (`plan/rules/skipping.py` calls
# into the blob loader `index/sketch.py`). A `load_sketches(...)` or
# `prune_files(...)` call anywhere else is a pruning decision the
# optimizer cannot see, the telemetry cannot attribute, and the
# no-false-negative property test does not cover.
_RAW_SKETCH_RE = re.compile(r"\bload_sketches\s*\(|\bprune_files\s*\(")
_SKETCH_ALLOWED = (os.path.join("index", "sketch.py"),)
_SKETCH_ALLOWED_DIR = os.path.join("plan", "rules")


def check_sketch_seam(package_dir: str):
    """Source lint: no sketch-consulting calls outside plan/rules/ and
    the blob-IO module."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel in _SKETCH_ALLOWED \
                    or rel.startswith(_SKETCH_ALLOWED_DIR + os.sep):
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_SKETCH_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: sketch-"
                            "consulting call outside the rules module — "
                            "pruning decisions belong in "
                            "plan/rules/skipping.py")
    return failures


# The ONE sanctioned layout-spec seam: every NamedSharding /
# PartitionSpec / shard_map the package constructs comes from
# parallel/mesh.py (row_spec, shard_rows, replicated, compat_shard_map,
# bucket_ranges) — the born-sharded on-disk layout, the per-device cache
# residency, and the SPMD collectives all derive from that ONE map, and a
# raw construction elsewhere is a layout that can silently drift from it.
# SLICE TOPOLOGY rides the same seam: constructing a `jax.sharding.Mesh`
# (flat or hierarchical), reshaping a device grid, or spelling the DCN
# axis name as a literal anywhere else is a (slice, device) topology the
# bucket-range hierarchy (`slice_bucket_ranges`), the replica router,
# and the two-hop repartition cannot see — topology construction stays
# inside parallel/mesh.py (`make_mesh` / `slice_submesh`).
_RAW_SHARDING_RE = re.compile(
    r"NamedSharding\s*\(|PartitionSpec\s*\(|(?<!compat_)shard_map\s*\(|"
    r"from\s+jax\.sharding\s+import|from\s+jax\.experimental\s+import\s+"
    r"shard_map|from\s+jax\.experimental\.shard_map\s+import|"
    r"(?<![\w.])Mesh\s*\(|jax\.sharding\.Mesh|create_device_mesh\s*\(|"
    r"[\"']dcn[\"']")
_SHARDING_ALLOWED = os.path.join("parallel", "mesh.py")


def check_sharding_seam(package_dir: str):
    """Source lint: no raw NamedSharding/PartitionSpec/shard_map/Mesh/
    device-grid/slice-topology construction outside parallel/mesh.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _SHARDING_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_SHARDING_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: raw "
                            "sharding/layout construction outside "
                            "parallel/mesh.py — derive the spec from "
                            "the canonical helpers (row_spec/"
                            "shard_rows/replicated/compat_shard_map/"
                            "bucket_ranges)")
    return failures


# The ONE sanctioned advisor build point: every index the advisor
# creates goes through its executor module, which routes through the
# collection manager's lease-gated Create path (stale-writer recovery,
# OCC one-winner, action reports). Constructing an Action — or even
# importing the actions package — anywhere else under advisor/ is a
# build that could bypass the lease and corrupt an index a concurrent
# maintenance verb owns.
_RAW_ADVISOR_BUILD_RE = re.compile(
    r"\b[A-Z]\w*Action\s*\(|from\s+hyperspace_tpu\.actions\b|"
    r"import\s+hyperspace_tpu\.actions\b")
_ADVISOR_BUILD_ALLOWED = os.path.join("advisor", "executor.py")


def check_advisor_build_seam(package_dir: str):
    """Source lint: no Action construction / actions import inside
    advisor/ outside executor.py."""
    failures = []
    advisor_dir = os.path.join(package_dir, "advisor")
    for root, _dirs, files in os.walk(advisor_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _ADVISOR_BUILD_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_ADVISOR_BUILD_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: Action "
                            "construction inside advisor/ outside the "
                            "executor — advisor builds must go through "
                            "advisor/executor.py's lease path")
    return failures


def check_ingest_build_seam(package_dir: str):
    """Source lint: no Action construction / actions import anywhere
    under engine/ — in particular the ingest coordinator
    (engine/ingest.py) must drive every refresh through the collection
    manager's lease-gated path (stale-writer recovery, OCC one-winner),
    never by constructing a maintenance verb directly. There is NO
    allowed file: the engine executes queries; the actions package owns
    writes."""
    failures = []
    engine_dir = os.path.join(package_dir, "engine")
    for root, _dirs, files in os.walk(engine_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_ADVISOR_BUILD_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: Action "
                            "construction inside engine/ — refresh and "
                            "every other maintenance verb must go "
                            "through the collection manager's "
                            "lease-gated path (see engine/ingest.py)")
    return failures


# The ONE sanctioned batched-execution point: the stacked-predicate
# program (`parallel/spmd.batched_predicate_masks`, the serve.batch jit
# entry) may only be invoked by the batching lane in engine/batcher.py.
# Any other caller is a K-query execution the scheduler never grouped:
# its members would have no cohort accounting, no per-member deadline
# settlement, and no fallback contract — exactly the properties
# tests/test_batcher.py pins on the sanctioned lane.
_RAW_BATCH_RE = re.compile(r"\bbatched_predicate_masks\s*\(")
_BATCH_DEF = os.path.join("parallel", "spmd.py")
_BATCH_ALLOWED = os.path.join("engine", "batcher.py")


def check_batch_seam(package_dir: str):
    """Source lint: no `batched_predicate_masks(...)` calls outside the
    defining module and engine/batcher.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel in (_BATCH_DEF, _BATCH_ALLOWED):
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_BATCH_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: batched-"
                            "program invocation outside the batching "
                            "lane — route it through engine/batcher.py "
                            "so cohort accounting, per-member deadlines,"
                            " and the fallback contract apply")
    return failures


# The legacy per-query-placement mesh join (`parallel/join.py`) is
# DELETED — the born-sharded SPMD lane (`parallel/spmd.py`) is the one
# distributed execution architecture. Any import or call of its entry
# points is a resurrection of the second architecture the deletion
# exists to prevent.
_LEGACY_JOIN_RE = re.compile(
    r"hyperspace_tpu\.parallel\.join\b|"
    r"from\s+hyperspace_tpu\.parallel\s+import\s+(?:[\w,\s]*\b)?join\b|"
    r"\bdistributed_bucketed_join_indices\s*\(|"
    r"\bdistributed_semi_anti_indices\s*\(")


def check_legacy_mesh_path(repo_root: str):
    """Source lint: no references to the deleted legacy mesh-join entry
    points anywhere in the repo (package, tests, benches, scripts)."""
    failures = []
    for root, dirs, files in os.walk(repo_root):
        dirs[:] = [d for d in dirs
                   if d not in ("__pycache__", ".git", "node_modules")]
        for fname in files:
            if not fname.endswith(".py") or fname == os.path.basename(
                    __file__):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, repo_root)
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _LEGACY_JOIN_RE.search(line):
                        failures.append(
                            f"{rel}:{lineno}: reference to the deleted "
                            "legacy mesh join (parallel/join.py) — the "
                            "born-sharded SPMD lane (parallel/spmd.py) "
                            "is the one distributed join architecture")
    return failures


# The ONE sanctioned dictionary-remap constructor: cross-side string
# unification on the SPMD lane goes through
# `parallel/spmd.string_remap_tables` (content-keyed segment-cache
# residency, `spmd.strings.*` accounting, in-program application). A
# remap built elsewhere would re-pay the merge per query and ship
# uncached tables over the link.
_RAW_REMAP_RE = re.compile(r"\bstring_remap_tables\s*\(")
_REMAP_ALLOWED = os.path.join("parallel", "spmd.py")


def check_string_remap_seam(package_dir: str):
    """Source lint: no `string_remap_tables(...)` construction outside
    parallel/spmd.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _REMAP_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_REMAP_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: dictionary-"
                            "remap construction outside parallel/spmd.py"
                            " — remap tables must come from the cached "
                            "seam (string_remap_tables) so warm queries "
                            "never rebuild or reship them")
    return failures


# Doc drift: every counter/gauge/histogram NAME LITERAL registered in
# the package must have a row in docs/telemetry.md. Dynamic names
# (f-strings — per-index, per-entry-point series) are exempt by
# construction: the regex requires a plain string literal as the first
# argument. A metric that ships without its doc row is a series an
# operator cannot interpret from the scrape alone.
_METRIC_NAME_RE = re.compile(
    r'\.(?:counter|gauge|histogram)\(\s*\n?\s*"([^"]+)"')


def _expand_braces(token: str):
    """`a.{x,y}.b` -> `a.x.b`, `a.y.b` (multiple groups expand
    cross-product) — the doc table's compact spelling for metric
    families."""
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        yield token
        return
    for alt in m.group(1).split(","):
        yield from _expand_braces(token[:m.start()] + alt
                                  + token[m.end():])


def check_metric_doc_rows(package_dir: str, repo_root: str):
    """Source lint: every literal metric name must appear in
    docs/telemetry.md (plainly, or inside a backticked
    `family.{a,b}`-style brace pattern)."""
    doc_path = os.path.join(repo_root, "docs", "telemetry.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
    except OSError:
        return [f"{doc_path}: missing — the metrics reference lives "
                "there"]
    documented = set()
    for token in re.findall(r"`([^`\s]+)`", doc):
        if "{" in token:
            documented.update(_expand_braces(token))
    names = {}
    for root, dirs, files in os.walk(package_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            for m in _METRIC_NAME_RE.finditer(src):
                names.setdefault(m.group(1), f"hyperspace_tpu/{rel}")
    failures = []
    for name in sorted(names):
        if name not in doc and name not in documented:
            failures.append(
                f"{names[name]}: metric {name!r} has no row in "
                "docs/telemetry.md — document the series before "
                "shipping it")
    return failures


# The ONE sanctioned tenant-attribution seam: the serving tenant rides
# a telemetry contextvar (`telemetry._tenant`) that the chargeback
# mirror (`charge_tenant`) and the flight/SLO attribution all read.
# The ONLY writers are the declared seam: `telemetry.tenant_scope`
# (the contextvar owner), `HyperspaceSession.tenant` (the sticky
# session default), and the scheduler's collect() (which resolves the
# effective tenant and opens the scope around execution). A raw
# `_tenant.set(...)` — or even a `tenant_scope(...)` entered anywhere
# else in the package — is a query whose device/link/cache charges
# land on a tenant the admission plane never admitted, silently
# breaking the chargeback exactness contract (per-tenant sums ==
# globals: `tests/test_tenancy.py`).
_RAW_TENANT_RE = re.compile(r"\b_tenant\s*\.\s*set\s*\(|"
                            r"\btenant_scope\s*\(")
_TENANT_ALLOWED = (os.path.join("telemetry", "__init__.py"),
                   os.path.join("engine", "scheduler.py"),
                   os.path.join("engine", "session.py"))


def check_tenant_seam(package_dir: str):
    """Source lint: no tenant contextvar writes (`_tenant.set` /
    `tenant_scope`) outside the telemetry owner, the session setter,
    and the scheduler's collect seam."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel in _TENANT_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_TENANT_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: tenant "
                            "contextvar write outside the sanctioned "
                            "seam — set the tenant via "
                            "session.tenant()/collect(tenant=...) so "
                            "admission and chargeback see the same "
                            "identity")
    return failures


# The ONE sanctioned HTTP surface: the operations endpoint
# (`telemetry/ops_server.py` — localhost-bound by default, counted,
# error-guarded). A raw `http.server` anywhere else is a listening
# socket the ops-plane knobs don't govern and the security note
# doesn't cover.
_RAW_HTTP_RE = re.compile(
    r"http\.server|ThreadingHTTPServer|BaseHTTPRequestHandler")
_HTTP_ALLOWED = os.path.join("telemetry", "ops_server.py")


def check_http_server_seam(package_dir: str):
    """Source lint: no `http.server` use outside telemetry/ops_server.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _HTTP_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_HTTP_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: raw "
                            "http.server use outside the ops endpoint "
                            "— serve it through telemetry/ops_server.py "
                            "(bind policy, counters, error guards)")
    return failures


# The ONE sanctioned backoff point: every storage retry routes through
# the policy in utils/retry.py (typed classification, conf-driven
# backoff, io.retries/io.giveups counters, fault-injection coverage).
_RETRY_ALLOWED = os.path.join("utils", "retry.py")


def check_retry_seams(package_dir: str):
    """AST lint: a `sleep` call lexically inside an `except` handler is
    an ad-hoc retry loop — invisible to the retry conf, uncounted by the
    io.* counters, unreachable by the fault-injection tests. Only
    utils/retry.py may back off."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _RETRY_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue  # surfaced by the import walk instead

            class Visitor(ast.NodeVisitor):
                def __init__(self):
                    self.except_depth = 0

                def visit_ExceptHandler(self, node):
                    self.except_depth += 1
                    self.generic_visit(node)
                    self.except_depth -= 1

                def visit_Call(self, node):
                    func = node.func
                    name = (func.attr if isinstance(func, ast.Attribute)
                            else func.id if isinstance(func, ast.Name)
                            else None)
                    if name == "sleep" and self.except_depth:
                        failures.append(
                            f"hyperspace_tpu/{rel}:{node.lineno}: ad-hoc "
                            "retry loop (sleep inside an except block) — "
                            "route the backoff through utils/retry.py")
                    self.generic_visit(node)

            Visitor().visit(tree)
    return failures


# The ONE sanctioned profiling seam: `telemetry/profiler.py` owns both
# instruments — the host stack sampler and the `jax.profiler` device
# capture (jax allows one active trace session per process; the seam's
# lock serializes them, and triggered captures inherit its rate limit
# and keep-N pruning). A raw `jax.profiler` / `cProfile` /
# `sys.setprofile` anywhere else is profiling the overhead gate does
# not measure and the capture policy does not govern.
_RAW_PROFILER_RE = re.compile(
    r"jax\s*\.\s*profiler|\bcProfile\b|sys\s*\.\s*setprofile")
_PROFILER_ALLOWED = os.path.join("telemetry", "profiler.py")


def check_profiler_seam(package_dir: str):
    """Source lint: no jax.profiler / cProfile / sys.setprofile use
    outside telemetry/profiler.py."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel == _PROFILER_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_PROFILER_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: raw "
                            "profiler use outside the profiling seam — "
                            "route it through telemetry/profiler.py "
                            "(device_trace / the sampling profiler)")
    return failures


# The ONE span seam: `telemetry.span(...)` (`telemetry/trace.py`) names a
# block in both sinks — the ring and the running profiler session's
# trace. Every name it is given comes from `SPAN_NAMES`, which
# docs/telemetry.md, PERF.md and the benchmark's reader quote: a span
# opened under a name the table lacks is a layer no reducer groups, and
# a direct `Tracer.complete` elsewhere is a span one sink never sees.
_SPAN_CALL_RE = re.compile(
    r"""\bspan\(\s*(f?)["']([^"']+)["'](\s*\+)?""")
_RING_COMPLETE_RE = re.compile(r"\.complete\(")
_DEVICE_SCOPE_RE = re.compile(r"""\bdevice_scoped\(\s*["']([^"']+)["']""")
_SPAN_SEAM = os.path.join("telemetry", "trace.py")


def _span_pattern(name: str) -> str:
    return re.sub(r"<[^>]*>|\{[^}]*\}", "<>", name)


def check_span_seam(package_dir: str, repo_root: str):
    """Source + doc-drift lint for the span seam: every literal name a
    `span(...)` call opens is in `telemetry.SPAN_NAMES` and every
    literal `device_scoped(...)` scope in `DEVICE_SCOPES`; no
    `Tracer.complete` outside `telemetry/trace.py`; every name of
    `SPAN_NAMES` and `DEVICE_SCOPES` has its row in docs/telemetry.md."""
    from hyperspace_tpu import telemetry
    known = {_span_pattern(n) for n in telemetry.SPAN_NAMES}
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            for m in _SPAN_CALL_RE.finditer(text):
                lineno = text.count("\n", 0, m.start()) + 1
                name = m.group(2) + ("{}" if m.group(3) else "")
                if _span_pattern(name) not in known:
                    failures.append(
                        f"hyperspace_tpu/{rel}:{lineno}: span name "
                        f"{name!r} is not in telemetry.SPAN_NAMES — add "
                        "it to the table (and docs/telemetry.md) or use "
                        "a name that is there")
            for m in _DEVICE_SCOPE_RE.finditer(text):
                if m.group(1) not in telemetry.DEVICE_SCOPES:
                    lineno = text.count("\n", 0, m.start()) + 1
                    failures.append(
                        f"hyperspace_tpu/{rel}:{lineno}: device scope "
                        f"{m.group(1)!r} is not in telemetry.DEVICE_SCOPES "
                        "— add it to the table (and docs/telemetry.md)")
            if rel == _SPAN_SEAM:
                continue
            for lineno, line in enumerate(text.splitlines(), 1):
                if _RING_COMPLETE_RE.search(line):
                    failures.append(
                        f"hyperspace_tpu/{rel}:{lineno}: direct "
                        "Tracer.complete outside the span seam — open a "
                        "telemetry.span (both sinks) instead")
    doc_path = os.path.join(repo_root, "docs", "telemetry.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
    except OSError:
        return failures + [f"{doc_path}: missing — the span-name table "
                           "lives there"]
    for name in list(telemetry.SPAN_NAMES) + list(telemetry.DEVICE_SCOPES):
        if f"`{name}`" not in doc:
            failures.append(
                f"hyperspace_tpu/telemetry/trace.py: span/scope name "
                f"{name!r} has no row in docs/telemetry.md's span table")
    return failures


def check_critpath_doc_rows(repo_root: str):
    """Doc-drift lint for the critical-path family: the per-segment
    counters are emitted with an f-string
    (`critpath.<segment>.seconds`), so the generic literal-name lint
    cannot see them — require a docs/telemetry.md row for every
    segment in the closed set explicitly."""
    from hyperspace_tpu.telemetry import critical_path
    doc_path = os.path.join(repo_root, "docs", "telemetry.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
    except OSError:
        return [f"{doc_path}: missing — the metrics reference lives "
                "there"]
    documented = set(re.findall(r"`([^`\s]+)`", doc))
    for token in list(documented):
        if "{" in token:
            documented.update(_expand_braces(token))
    failures = []
    for segment in critical_path.SEGMENTS:
        name = f"critpath.{segment}.seconds"
        if name not in doc and name not in documented:
            failures.append(
                f"hyperspace_tpu/telemetry/critical_path.py: segment "
                f"counter {name!r} has no row in docs/telemetry.md — "
                "every segment of the closed set must be documented")
    return failures


def check_alert_rule_doc_rows(repo_root: str):
    """Doc-drift lint for the default alert rules: every series a
    shipped rule reads must have a docs/telemetry.md row (the
    `hit_ratio` kind reads the `<series>.{hits,misses}` counter family;
    warm gates read their counter too). An alert an operator cannot
    trace to a documented series is an incident nobody can interpret —
    and each rule's NAME must appear in the default-rule table so its
    conf override knobs are discoverable."""
    from hyperspace_tpu.telemetry import alerts
    doc_path = os.path.join(repo_root, "docs", "telemetry.md")
    try:
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
    except OSError:
        return [f"{doc_path}: missing — the metrics reference lives "
                "there"]
    documented = set(re.findall(r"`([^`\s]+)`", doc))
    for token in list(documented):
        if "{" in token:
            documented.update(_expand_braces(token))
    failures = []
    for rule in alerts.DEFAULT_RULES:
        series = ([f"{rule.series}.hits", f"{rule.series}.misses"]
                  if rule.kind == "hit_ratio" else
                  [rule.series] if rule.series else [])
        if rule.warm_counter:
            series.append(rule.warm_counter)
        for name in series:
            if name not in doc and name not in documented:
                failures.append(
                    f"hyperspace_tpu/telemetry/alerts.py: default rule "
                    f"{rule.name!r} reads series {name!r} which has no "
                    "row in docs/telemetry.md — an undocumented series "
                    "cannot anchor an alert")
        if rule.name not in doc:
            failures.append(
                f"hyperspace_tpu/telemetry/alerts.py: default rule "
                f"{rule.name!r} missing from the docs/telemetry.md "
                "rule table — its conf override knobs are "
                "undiscoverable")
    return failures


# The ONE sanctioned telemetry-history writer: durable segments under
# `<warehouse>/.hyperspace_telemetry/` are written only by
# telemetry/history.py (atomic publish, schema version, age/byte
# pruning, torn-segment skipping on read). The directory-name literal
# is defined once in constants.py (TELEMETRY_HISTORY_DIRNAME); spelling
# it anywhere else in the package is a history file the reader's merge
# and the pruner's budget do not govern.
_RAW_HISTORY_RE = re.compile(r"\.hyperspace_telemetry")
_HISTORY_ALLOWED = ("constants.py",
                    os.path.join("telemetry", "history.py"))


def check_history_write_seam(package_dir: str):
    """Source lint: the telemetry-history directory literal appears
    only in constants.py (the definition) and telemetry/history.py
    (the writer)."""
    failures = []
    for root, _dirs, files in os.walk(package_dir):
        if "__pycache__" in root:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, package_dir)
            if rel in _HISTORY_ALLOWED:
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    if _RAW_HISTORY_RE.search(line):
                        failures.append(
                            f"hyperspace_tpu/{rel}:{lineno}: telemetry-"
                            "history directory literal outside the "
                            "sanctioned writer — history segments are "
                            "written only by telemetry/history.py "
                            "(reference constants."
                            "TELEMETRY_HISTORY_DIRNAME)")
    return failures


def main() -> int:
    import hyperspace_tpu

    import_errors = []
    for mod in pkgutil.walk_packages(hyperspace_tpu.__path__,
                                     prefix="hyperspace_tpu."):
        if "libhyperspace_host" in mod.name:
            continue  # the ctypes-loaded .so, not an importable module
        try:
            importlib.import_module(mod.name)
        except Exception as exc:
            import_errors.append(f"{mod.name}: {exc!r}")

    from hyperspace_tpu.engine.physical import PhysicalNode

    base_execute = PhysicalNode.__dict__["execute"]
    base_bucketed = PhysicalNode.__dict__["execute_bucketed"]
    failures = []
    checked = 0
    for cls in sorted(set(_all_subclasses(PhysicalNode)),
                      key=lambda c: (c.__module__, c.__name__)):
        checked += 1
        for attr, base in (("execute", base_execute),
                           ("execute_bucketed", base_bucketed)):
            fn = getattr(cls, attr, None)
            if fn is None or getattr(fn, "__func__", fn) is base:
                continue  # inherited abstract stub: never executes rows
            if not getattr(fn, "__telemetry_instrumented__", False):
                failures.append(
                    f"{cls.__module__}.{cls.__name__}.{attr} executes "
                    "without emitting a telemetry operator record")

    # Mirror check for index-maintenance actions: run() must resolve to
    # the report-instrumented wrapper on every subclass.
    from hyperspace_tpu.actions.base import Action

    checked_actions = 0
    for cls in sorted(set(_all_subclasses(Action)),
                      key=lambda c: (c.__module__, c.__name__)):
        checked_actions += 1
        fn = getattr(cls, "run", None)
        if fn is None or not getattr(fn, "__action_report_instrumented__",
                                     False):
            failures.append(
                f"{cls.__module__}.{cls.__name__}.run can execute "
                "without emitting an action report")

    failures.extend(check_jit_entry_points(
        os.path.dirname(hyperspace_tpu.__file__),
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "telemetry.md")))
    failures.extend(check_device_put_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_segment_cache_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_engine_thread_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_serving_error_counters())
    failures.extend(check_index_kind_serde())
    failures.extend(check_sketch_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_sharding_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_advisor_build_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_ingest_build_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_batch_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_retry_seams(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_legacy_mesh_path(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    failures.extend(check_string_remap_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_http_server_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_tenant_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_metric_doc_rows(
        os.path.dirname(hyperspace_tpu.__file__),
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    failures.extend(check_profiler_seam(
        os.path.dirname(hyperspace_tpu.__file__)))
    failures.extend(check_span_seam(
        os.path.dirname(hyperspace_tpu.__file__),
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    failures.extend(check_critpath_doc_rows(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    failures.extend(check_alert_rule_doc_rows(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    failures.extend(check_history_write_seam(
        os.path.dirname(hyperspace_tpu.__file__)))

    if import_errors:
        print("check_metrics_coverage: module import failures "
              "(coverage cannot be proven):", file=sys.stderr)
        for line in import_errors:
            print(f"  {line}", file=sys.stderr)
    if failures:
        print("check_metrics_coverage: FAILED", file=sys.stderr)
        for line in failures:
            print(f"  {line}", file=sys.stderr)
    if failures or import_errors:
        return 1
    from hyperspace_tpu.telemetry import compilation
    print(f"check_metrics_coverage: OK "
          f"({checked} PhysicalNode subclasses, {checked_actions} "
          f"Action subclasses, and {len(compilation.REGISTRY)} jit "
          f"entry points instrumented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
