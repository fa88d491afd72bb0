"""Vectorized (TPU-native) ESTEE simulator."""
from .specs import (GraphSpec, BucketedGraphSpec, BucketGroup, encode_graph,
                    abstract_spec, as_bucketed, bucket_shape, pad_spec,
                    pad_specs, pad_to, stack_specs, t_bucket, T_EDGES)
from .specs import frontier_cap, frontier_caps_for
from .sim import (make_simulator, simulate_batch,
                  make_dynamic_simulator, simulate_dynamic_grid,
                  make_bucket_simulator, make_bucket_dynamic_simulator,
                  DynamicGridRunner, BucketedGridRunner, trace_counter,
                  DOWNLOAD_SLOTS, PAIR_SLOTS, SIM_PHASES, SimResult)
from .api import SimConfig, build, build_for_graph, make_grid_runner
from .engine import (ShardedGridRunner, DoubleBufferQueue,
                     enable_compile_cache, compile_cache_root, cache_counter,
                     cache_event_counts, SETUP_PHASES, setup_seconds,
                     setup_timer, ExecutableStore, exec_counter)
from .scheduling import (VEC_SCHEDULERS, make_vec_scheduler,
                         make_bucket_scheduler,
                         bucket_ready_tasks, frontier_mask,
                         make_static_blevel_scheduler,
                         make_static_tlevel_scheduler,
                         make_static_mcp_scheduler, make_etf_scheduler,
                         make_random_scheduler, make_greedy_placer,
                         make_bucket_greedy_placer,
                         make_blevel_fn, make_tlevel_fn,
                         bucket_blevel, bucket_tlevel, rank_priorities)
from .waterfill import waterfill, waterfill_simple

__all__ = ["GraphSpec", "BucketedGraphSpec", "BucketGroup", "encode_graph",
           "abstract_spec", "as_bucketed", "bucket_shape", "pad_spec",
           "pad_specs", "pad_to", "stack_specs", "t_bucket", "T_EDGES",
           "frontier_cap", "frontier_caps_for",
           "make_simulator", "simulate_batch",
           "make_dynamic_simulator", "simulate_dynamic_grid",
           "make_bucket_simulator", "make_bucket_dynamic_simulator",
           "DynamicGridRunner", "BucketedGridRunner", "trace_counter",
           "DOWNLOAD_SLOTS", "PAIR_SLOTS", "SIM_PHASES", "SimResult",
           "SimConfig", "build", "build_for_graph", "make_grid_runner",
           "ShardedGridRunner", "DoubleBufferQueue",
           "enable_compile_cache", "compile_cache_root", "cache_counter",
           "cache_event_counts", "SETUP_PHASES", "setup_seconds",
           "setup_timer", "ExecutableStore", "exec_counter",
           "VEC_SCHEDULERS", "make_vec_scheduler", "make_bucket_scheduler",
           "bucket_ready_tasks", "frontier_mask",
           "make_static_blevel_scheduler", "make_static_tlevel_scheduler",
           "make_static_mcp_scheduler", "make_etf_scheduler",
           "make_random_scheduler", "make_greedy_placer",
           "make_bucket_greedy_placer",
           "make_blevel_fn", "make_tlevel_fn",
           "bucket_blevel", "bucket_tlevel", "rank_priorities",
           "waterfill", "waterfill_simple"]
