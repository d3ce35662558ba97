"""The paper's contribution: dataset -> object-storage mapping with
storage-side computation (SkyhookDM / HDF5-VOL, in JAX-native form).

Layering (bottom up):
  placement  — CRUSH-like PG/HRW placement from a compact cluster map
  store      — RADOS-like replicated object store + objclass execution,
               digest scrub/heal and the deadline/backoff request layer
  faults     — fault-injection harness (bit rot, torn writes, slow or
               transiently failing OSDs) for the self-healing plane
  format     — physical block format, codecs, layout transformation
  logical    — access-library-facing datasets (rows, columns, units)
  partition  — logical units -> objects (grouping/splitting/sizing)
  expr       — predicate-expression algebra: one tree for evaluation,
               zone-map interval pruning, and the wire form
  objclass   — storage-side op registry (select/project/filter/agg/...)
  scan       — the ONE query surface: Scan builder -> PhysicalPlan ->
               ScanEngine (prune pushdown, per-OSD combine/concat)
  cache      — byte-bounded LRU result cache (one per OSD, version-keyed)
  maintenance — background daemons: continuous scrub walker, small-
               object compaction, live rebalance, versioned GC
  session    — ScanSession: many-client admission front-end
               (single-flight dedup + projection coalescing)
  vol        — GlobalVOL (client plugin) / LocalVOL (storage plugin)
  pushdown_jax — the TPU data plane: compute-at-shard via shard_map
"""

from repro.core.expr import (  # noqa: F401
    And, Between, Cmp, Const, In, Not, Or, StrPrefix, normalize)
from repro.core.logical import (  # noqa: F401
    Column, Dataspace, Hyperslab, LogicalDataset, RowRange)
from repro.core.partition import (  # noqa: F401
    ArrayObjectMap, ObjectMap, PartitionPolicy, load_objmap,
    plan_array_partition, plan_partition)
from repro.core.placement import ClusterMap  # noqa: F401
from repro.core.store import (  # noqa: F401
    CorruptObject, DataLossError, ObjectStore, PartialWriteError,
    RetryPolicy, TokenBucket, TransientOSDError, make_store)
from repro.core.faults import FaultInjector  # noqa: F401
from repro.core.maintenance import MaintenancePlane  # noqa: F401
from repro.core.cache import ResultCache  # noqa: F401
from repro.core.scan import PhysicalPlan, Scan, ScanEngine  # noqa: F401
from repro.core.session import ScanSession  # noqa: F401
from repro.core.vol import ArrayView, GlobalVOL, LocalVOL  # noqa: F401
