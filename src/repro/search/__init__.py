"""Vector-search substrate: brute-force k-NN, recall metrics, IVF-Flat /
PQ / IVF-PQ ANN indexes, the composable index-spec API (pipeline specs +
the tagged index union + ops registry), the batched serving engine that
integrates MPAD reduction, the streaming (mutable) layer on top of it,
snapshot persistence, the durability subsystem (write-ahead log, crash
recovery, maintenance policy), the replication layer (WAL shipping +
follower catch-up, incremental snapshot chains, group commit), the
typed metrics surface, and request-level tracing (program spans on the
profiler's clock, latency histograms, slow-query capture, online recall
estimation)."""
from .knn import (knn_search, knn_search_blocked, masked_topk, recall_at_k,
                  amk_accuracy)
from .ivf import (IVFIndex, balance_cells, build_ivf, cell_vectors,
                  ivf_search, posting_lists, probe_cells)
from .ivfpq import IVFPQIndex, build_ivfpq, ivfpq_search
from .pq import PQIndex, build_pq, pq_search, pq_reconstruct
from .spec import (Coarse, Code, IndexSpec, Reduce, Rerank, format_spec,
                   parse_spec, spec_from_config)
from .reducers import (REDUCER_KINDS, Reducer, ReducerOps, fit_reducer,
                       get_reducer_ops, reduce_vectors, reducer_dim,
                       register_reducer)
from .registry import Index, IndexOps, ScanParams, get_ops, register_index
from .segments import (FrozenParams, MutableEngineState, StreamStore,
                       compact_fn, delete_fn, make_mutable, rebuild_state,
                       upsert_fn)
from .serve import (EngineState, INDEX_KINDS, SearchEngine, ServeConfig,
                    ShardedEngineState, StreamConfig, build_engine,
                    config_from_spec, exact_rerank, search_fn,
                    sharded_search_fn)
from .snapshot import load_engine, save_engine
from .stream import (StreamReplica, replica_from_store,
                     sharded_stream_search_fn, stream_search_fn)
from .durability import (CatchUpStats, Decision, DivergenceError,
                         DurabilityConfig, LocalDirSource, MaintenancePolicy,
                         PolicyConfig, ReplayStats, ReplicationError, Wal,
                         WalError, WalSource, catch_up, replay,
                         replay_records, seed_follower)
from .metrics import (BuildMetrics, CompactMetrics, EngineInfo,
                      EngineMetrics,
                      HistogramSnapshot, LatencyMetrics, MetricsServer,
                      PolicyMetrics, RecallMetrics, ReplicationMetrics,
                      SnapshotMetrics, StreamMetrics, WalMetrics,
                      collect_metrics, render_prometheus)
from .tracing import TraceConfig, Tracer, jax_profile

__all__ = [
    "knn_search", "knn_search_blocked", "masked_topk", "recall_at_k",
    "amk_accuracy",
    "IVFIndex", "balance_cells", "build_ivf", "cell_vectors", "ivf_search",
    "posting_lists", "probe_cells",
    "IVFPQIndex", "build_ivfpq", "ivfpq_search",
    "PQIndex", "build_pq", "pq_search", "pq_reconstruct",
    # the composable index-spec API
    "IndexSpec", "Reduce", "Coarse", "Code", "Rerank",
    "parse_spec", "format_spec", "spec_from_config", "config_from_spec",
    "Index", "IndexOps", "ScanParams", "get_ops", "register_index",
    # the reducer zoo (pluggable Reduce stage)
    "Reducer", "ReducerOps", "register_reducer", "get_reducer_ops",
    "fit_reducer", "reduce_vectors", "reducer_dim", "REDUCER_KINDS",
    # engine + lifecycle
    "SearchEngine", "ServeConfig", "EngineState", "ShardedEngineState",
    "build_engine", "save_engine", "load_engine",
    "search_fn", "sharded_search_fn", "exact_rerank", "INDEX_KINDS",
    # streaming
    "StreamConfig", "StreamStore", "MutableEngineState", "FrozenParams",
    "make_mutable", "upsert_fn", "delete_fn", "compact_fn", "rebuild_state",
    "StreamReplica", "replica_from_store", "stream_search_fn",
    "sharded_stream_search_fn",
    # durability: WAL + crash recovery + maintenance policy
    "DurabilityConfig", "Wal", "WalError", "replay", "ReplayStats",
    "replay_records",
    "PolicyConfig", "MaintenancePolicy", "Decision",
    # replication: WAL shipping + follower catch-up
    "ReplicationError", "DivergenceError", "WalSource", "LocalDirSource",
    "CatchUpStats", "catch_up", "seed_follower",
    # typed metrics / observability
    "EngineMetrics", "EngineInfo", "StreamMetrics", "CompactMetrics",
    "PolicyMetrics", "WalMetrics", "SnapshotMetrics", "ReplicationMetrics",
    "HistogramSnapshot", "LatencyMetrics", "RecallMetrics", "BuildMetrics",
    "collect_metrics", "render_prometheus", "MetricsServer",
    # request-level tracing
    "TraceConfig", "Tracer", "jax_profile",
]
