# The paper's primary contribution: Quake's adaptive partitioned index.
#   geometry     — hyperspherical-cap recall math (paper §5)
#   cost_model   — lambda(s) latency model + cost deltas (paper §4.1/§4.2.2)
#   kmeans       — jit-compiled clustering (build/split/refine substrate)
#   aps          — Adaptive Partition Scanning (paper §5, Algorithm 1)
#   index        — dynamic multi-level partitioned index (paper §3)
#   maintenance  — estimate/verify/commit maintenance loop (paper §4.2)
#   snapshot     — dense device snapshot + round-robin partition placement
#   distributed  — mesh-sharded serving engine (paper §6, TPU adaptation)
#   multiquery   — batched scan-once-per-partition policy (paper §7.4)
#   journal      — mutation journal: the snapshot invalidation protocol
#                  (per-partition dirty sets, COW delta refresh, §8.2)
#   serving      — online serving runtime: micro-batching queue,
#                  cross-batch union riding, result cache,
#                  drift-triggered maintenance (§3's online loop)
from .index import QuakeConfig, QuakeIndex, SearchResult  # noqa: F401
from .journal import Delta, MutationJournal  # noqa: F401
from .maintenance import Maintainer, MaintenancePolicy  # noqa: F401
from .cost_model import LatencyModel  # noqa: F401
from .snapshot import IndexSnapshot, SnapshotPatch  # noqa: F401
from .distributed import EngineConfig, ShardedQuakeEngine  # noqa: F401
from .serving import (STATUS_FAILED, STATUS_OK,  # noqa: F401
                      STATUS_PARTIAL, STATUS_SHED, TERMINAL_STATUSES,
                      MaintenanceScheduler, MaintenanceTriggers,
                      QueryResult, ResultCache, ServingConfig,
                      ServingRuntime)
