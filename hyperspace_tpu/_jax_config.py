"""Central JAX configuration, imported by every module that touches jax.

x64 is mandatory for data correctness: lake data routinely carries int64
keys and float64 measures, and jax's default 32-bit mode would silently
truncate them. The perf-critical kernels (hashing, sort keys) operate on
32-bit lanes internally (`ops/hash_partition.py`), so the TPU fast path is
not sacrificed.
"""

import os
import re

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the build/join sort programs take minutes
# to compile for a v5e (the multi-operand stable `lax.sort` under x64), at
# a cost that barely depends on row count; a later process reuses the
# on-disk executable. ONE place decides where it lives: when
# JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and no code of
# this package sets another; otherwise the cache sits at one fixed path
# inside the checkout (the path is part of the cache key, so it is never
# derived from a temp name, pid or time). Whoever placed it, every program
# is stored: jax's floors (compile time under a second, small entries)
# would leave out the many small eager programs a query runs between the
# big ones, and a warm process would recompile them all.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# One frame per MLIR location, not the Python call stack. jax strips
# locations from a module before hashing it for the cache, but a Pallas
# kernel travels inside its custom call as an opaque payload whose
# locations stay in the key: with full tracebacks that payload named
# every file on the stack down to the entry script, and an edited line in
# any of them recompiled the build program (a minute on the chip, seen on
# three consecutive chip runs). Now it names kernel source only, and by
# its path inside the checkout: with the absolute path in the key, the
# same commit checked out elsewhere compiled the build program again.
jax.config.update("jax_include_full_tracebacks_in_locations", False)
jax.config.update("jax_hlo_source_file_canonicalization_regex",
                  re.escape(_CHECKOUT + os.sep))
