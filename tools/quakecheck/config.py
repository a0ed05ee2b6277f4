"""Repo-specific registries the quakecheck rules consult.

Everything here is *policy*, not mechanism: which functions are declared
device-resident, which call names produce device values, which serving
classes own write-barrier-guarded state.  New subsystems extend these
tables (or use the inline markers) instead of touching the rule code.
"""
from __future__ import annotations

# --------------------------------------------------------------------------
# QK101 — device-resident functions (host syncs inside these must carry an
# allow-sync pragma).  Entries are bare function names or
# ``ClassName.method`` qualnames.  Functions jitted with ``@jax.jit`` /
# ``functools.partial(jax.jit, ...)`` are registered automatically, as is
# any def whose line carries a ``# quakecheck: device-path`` marker.
# --------------------------------------------------------------------------
DEVICE_RESIDENT_FUNCS = {
    # core/multiquery.py — the batched executor hot path
    "_fused_plan_probes",
    "_aps_probe_counts_batched",
    "_aps_probe_counts_fused",
    "run_round_loop",
    "BatchedSearchExecutor.search",
    "BatchedSearchExecutor.scan_probe_round",
    "BatchedSearchExecutor._search_rounds",
    # core/serving.py — the riding-round scheduler
    "RoundScheduler.step",
}

# Call names (bare or attribute leaf) whose results live on device.  The
# taint pass also treats any ``jnp.*`` / ``jax.*`` call as device-producing
# (except the explicit sync entry points below).
DEVICE_PRODUCING_CALLS = {
    "scan_topk", "scan_selected_topk", "scan_selected_topk_q8",
    "kmeans_assign", "pack_union", "pack_round", "pack_round_masked",
    "topk_merge", "_fused_plan_probes", "scan_probe_round", "_pack_plan",
    "run_round_loop", "device_arrays", "apply_delta", "build_patch",
}

# Explicit sync entry points: calling these on a device value is the
# device->host pull QK101 exists to surface.
HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "np.ascontiguousarray", "numpy.asarray",
    "numpy.array", "jax.device_get", "device_get",
}
HOST_SYNC_BUILTINS = {"float", "int", "bool"}
HOST_SYNC_METHODS = {"item", "tolist", "__array__", "block_until_ready"}

# --------------------------------------------------------------------------
# QK102 — jit cache discipline
# --------------------------------------------------------------------------
# Names that mark a value as bucket-rounded (safe to use as a jit static
# argument / padded shape even though it derives from data).
BUCKET_HINT_NAMES = {"bucket", "pad", "pow2", "align", "tile", "cap"}
BUCKET_CALLS = {"_next_pow2", "_pad_to", "next_pow2", "pad_to"}
# Reducers whose results vary with the *data* (not just operand shapes):
# feeding one of these into a jit static argument fragments the cache.
DATA_DEPENDENT_REDUCERS = {"max", "min", "sum", "argmax", "argmin",
                           "nonzero", "unique", "count_nonzero"}

# --------------------------------------------------------------------------
# QK103 — Pallas kernel contract
# --------------------------------------------------------------------------
# The Mosaic grid / compiler-params API; kernels reach it through
# kernels/pallas_compat.py, never directly, so a change of that API is a
# one-file edit.
PLTPU_COMPAT_ONLY = {
    "CompilerParams", "PrefetchScalarGridSpec", "GridDimensionSemantics",
}
# The one file allowed to touch them.
PALLAS_COMPAT_FILE = "pallas_compat.py"
# Directory (path fragment) the kernel-contract rules apply to.
KERNELS_DIR_FRAGMENT = "kernels"

# --------------------------------------------------------------------------
# QK105 — serving shared state (write-barrier discipline, docs/serving.md)
# --------------------------------------------------------------------------
# owner class -> guarded fields.  Mutating one of these outside a method of
# the owning class bypasses the write barrier.  Reads are always fine;
# calling the owner's public methods is the sanctioned API.
GUARDED_STATE = {
    "ServingRuntime": {"results", "_queue", "_cache_version",
                       "_maintaining", "_next_qid"},
    "ResultCache": {"_store", "_by_key", "_by_part", "_next_eid",
                    "_proj", "hits", "misses", "invalidated"},
    "RoundScheduler": {"active", "done", "_epoch_key", "_snap",
                       "round_streams", "plan_footprints"},
    "PartitionStats": {"hits", "window"},
    # observability layer (src/repro/obs, docs/observability.md)
    "MetricsRegistry": {"_counters", "_gauges", "_histograms"},
    "QueryTracer": {"_open", "_ring"},
    "CalibrationTracker": {"_lat_err", "_rec_err"},
}
# Attribute names that are guarded under *any* owner (the linter cannot
# infer types, so a guarded-name mutation through a non-self base is
# flagged wherever it appears; the owner's own methods use ``self``).
GUARDED_ATTRS = {a for attrs in GUARDED_STATE.values() for a in attrs}

MUTATING_METHODS = {"append", "extend", "clear", "pop", "popitem", "remove",
                    "insert", "update", "setdefault", "discard", "add",
                    "move_to_end", "sort", "fill"}

# --------------------------------------------------------------------------
# QK2xx — lock discipline (docs/serving.md threading model)
# --------------------------------------------------------------------------
# owner class -> {guarded field -> lock attribute that must be held}.
# Layered on GUARDED_STATE: QK105 checks *who* writes, QK201 checks *under
# what lock*.  Lock attributes are unqualified (``_lock``); the analysis
# qualifies them against the owning class (``ResultCache._lock``).
GUARDED_BY = {
    "ServingRuntime": {
        "results": "_lock", "_queue": "_lock", "_cache_version": "_lock",
        "_maintaining": "_lock", "_next_qid": "_lock",
        "_admission_log": "_lock", "_admit_gen": "_lock",
        "queries_submitted": "_lock", "cache_hits": "_lock",
        "write_ops": "_lock",
        # failure / degradation telemetry (docs/serving.md)
        "shed_queries": "_lock", "_status_counts": "_lock",
        "cache_errors": "_lock", "_cache_disabled": "_lock",
        "ticker_errors": "_lock", "ticker_restarts": "_lock",
        "ticker_wedged": "_lock", "maintenance_failures": "_lock",
        "_overflow_since_flush": "_lock", "_govern_steps": "_lock",
        "_pressure_streak": "_lock", "_calm_streak": "_lock",
        "_govern_degrades": "_lock", "_govern_restores": "_lock",
    },
    "RoundScheduler": {
        "active": "_lock", "done": "_lock", "_epoch_key": "_lock",
        "_snap": "_lock", "round_streams": "_lock",
        "plan_footprints": "_lock", "partitions_streamed": "_lock",
        "vectors_streamed": "_lock", "comparisons": "_lock",
        "rounds_run": "_lock",
        # failure / degradation telemetry
        "partials": "_lock", "failures": "_lock",
        "failed_batches": "_lock", "scan_faults": "_lock",
        "scan_retries_used": "_lock", "_last_scan_error": "_lock",
        "target": "_lock", "probe_frac": "_lock",
    },
    "ResultCache": {
        "_store": "_lock", "_by_key": "_lock", "_by_part": "_lock",
        "_next_eid": "_lock", "_proj": "_lock", "_gen": "_lock",
        "hits": "_lock", "misses": "_lock", "invalidated": "_lock",
        "stale_puts": "_lock",
    },
    "MaintenanceScheduler": {
        "ops_since": "_lock", "history": "_lock", "_last_version": "_lock",
        "_last_cost": "_lock", "_last_freqs": "_lock",
    },
    "MetricsRegistry": {
        "_counters": "_lock", "_gauges": "_lock", "_histograms": "_lock",
    },
    "QueryTracer": {
        "_open": "_lock", "_ring": "_lock",
        "emitted": "_lock", "dropped": "_lock",
    },
    "CalibrationTracker": {
        "_lat_err": "_lock", "_rec_err": "_lock",
    },
}

# Declared global lock partial order (qualified names, outermost first).
# Acquiring a lock while holding one that appears *later* in this list is
# a QK202 lock-order violation — the runtime twin is
# ``repro.sanitize.LOCK_ORDER`` (a test asserts the two lists agree).
LOCK_ORDER = [
    "ServingRuntime._engine_lock",
    "ServingRuntime._lock",
    "RoundScheduler._lock",
    "ResultCache._lock",
    "MaintenanceScheduler._lock",
    # observability locks rank innermost: recording under any runtime
    # lock is legal, the reverse never is (docs/observability.md)
    "QueryTracer._lock",
    "CalibrationTracker._lock",
    "MetricsRegistry._lock",
]

# Locks on the admission fast path: holding one of these across a
# blocking call (QK203) stalls every concurrent submit_* caller.  The
# engine lock is deliberately absent — serializing blocking scan /
# maintenance work is its whole job.
ADMISSION_LOCKS = {"ServingRuntime._lock"}

# Call names (leaf) that block: device syncs, host pulls, scans, and
# maintenance entry points.  QK203 flags any of these inside a region
# holding an admission lock.
BLOCKING_CALLS = {
    "block_until_ready", "device_get", "drain", "flush",
    "maybe_maintain", "run_if_due", "kmeans", "kmeans_assign",
    "scan_probe_round", "host_scan_round", "plan_rounds", "plan_batch",
    "sleep", "join",
}

# Attribute -> owner class, for resolving cross-object lock references
# (``self.cache._lock`` inside ServingRuntime -> ``ResultCache._lock``).
INSTANCE_ATTRS = {
    "scheduler": "RoundScheduler",
    "cache": "ResultCache",
    "maintenance": "MaintenanceScheduler",
    "metrics": "MetricsRegistry",
    "tracer": "QueryTracer",
    "calibration": "CalibrationTracker",
}

# --------------------------------------------------------------------------
# QK301 — swallowed exceptions (docs/serving.md failure semantics)
# --------------------------------------------------------------------------
# Directory (path fragment) the swallow rule applies to: runtime code under
# src/repro/ must never silently drop an exception — every failure is
# counted, degraded-to, retried, or documented with
# ``# quakecheck: allow-swallow(<why>)``.
SWALLOW_DIR_FRAGMENT = "repro"

# --------------------------------------------------------------------------
# QK401 — wall-clock / stdout discipline in core runtime paths
# (docs/observability.md).  Scope: paths with both a "repro" and a "core"
# component (src/repro/core and the fixture twins).  In scope,
# ``time.time()`` and ``print()`` are forbidden: runtime code reads the
# injectable monotonic clock (the ``clock`` parameter on ServingRuntime /
# RoundScheduler / run_round_loop, default ``time.perf_counter``) and
# reports through the metrics registry / trace emitter, so fake-clock
# tests stay deterministic and the serving hot path never writes to
# stdout.  Documented exceptions carry
# ``# quakecheck: allow-wallclock(<why>)``.
RUNTIME_CORE_FRAGMENT = "core"
WALLCLOCK_CALLS = {"time.time"}      # dotted call names (plus bare `time`)
STDOUT_CALLS = {"print"}             # bare call names

# --------------------------------------------------------------------------
# QK302 — durability I/O discipline (docs/durability.md)
# --------------------------------------------------------------------------
# Path fragment the durability rules apply to: a path component equal to
# "durability" (fixture dirs) or starting with "durability." (the module
# itself).  In scope, every write-mode ``open`` must be paired with an
# fsync in the same function (or carry # quakecheck: allow-nosync(<why>)),
# and manifest/checkpoint files must be written via the temp + rename
# idiom, never in place.
DURABILITY_PATH_FRAGMENT = "durability"
# Call leaf names that count as making the write durable.
FSYNC_CALLS = {"fsync", "_fsync", "sync", "fdatasync"}
# Call leaf names that count as the atomic-publish step.
RENAME_CALLS = {"rename", "replace", "renames"}
# Lowercase substrings of a written path literal that mark it as a
# manifest / checkpoint (the files whose partial state must never be
# observable in place).
MANIFEST_HINTS = ("manifest", "ckpt", "checkpoint")

# Guarded fields whose values are immutable scalars: reading them without
# the lock can tear a *snapshot* but can never leak a mutable alias, so
# QK204 (escaping reference) skips them.
SCALAR_GUARDED = {
    "emitted", "dropped",
    "_cache_version", "_maintaining", "_next_qid", "_next_eid",
    "_epoch_key", "hits", "misses", "invalidated", "stale_puts",
    "queries_submitted", "cache_hits", "write_ops", "ops_since",
    "partitions_streamed", "vectors_streamed", "comparisons",
    "rounds_run", "_gen", "_last_version", "_last_cost",
    "shed_queries", "cache_errors", "_cache_disabled", "ticker_errors",
    "ticker_restarts", "ticker_wedged", "maintenance_failures",
    "_overflow_since_flush", "_govern_steps", "_pressure_streak",
    "_calm_streak", "_govern_degrades", "_govern_restores",
    "partials", "failures", "failed_batches", "scan_faults",
    "scan_retries_used", "target", "probe_frac",
}

# Copy-producing wrappers: returning ``list(self._queue)`` (or
# ``.copy()`` / ``deepcopy`` / ``sorted`` / ``dict`` ...) hands the
# caller a private snapshot, not an alias, so QK204 allows it.
COPYING_CALLS = {
    "list", "dict", "tuple", "set", "frozenset", "sorted", "copy",
    "deepcopy", "asarray", "array",
}
