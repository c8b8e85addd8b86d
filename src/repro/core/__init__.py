"""Cheetah core: the pruning abstraction and per-query pruning algorithms.

Paper: "Cheetah: Accelerating Database Queries with Switch Pruning"
(Tirmazi, Ben Basat, Gao, Yu — 2020). A pruner A_Q maps a stream D to a
keep-mask selecting A_Q(D) ⊆ D with Q(A_Q(D)) = Q(D); the master completes
the query on the survivors.
"""
from .pruning import PruneResult, compact, compact_argsort, prune_rate_vs_opt
from .hashing import (mix32, hash_mod, hash_mod_dyn, multi_hash,
                      fingerprint, fingerprint_bits_thm4)
from .distinct import (distinct_prune, master_complete_distinct,
                       opt_keep_distinct, thm1_bound)
from .topn import (topn_rand_prune, topn_det_prune, thm2_w, thm2_opt_d,
                   thm3_forwarded_bound, opt_keep_topn, master_complete_topn)
from .join import (join_prune, join_prune_asymmetric, master_complete_join,
                   join_oracle)
from .having import having_prune, master_complete_having, having_oracle
from .skyline import (skyline_prune, skyline_oracle, opt_keep_skyline,
                      master_complete_skyline, score_sum, score_aph)
from .groupby import groupby_prune, master_complete_groupby, groupby_oracle
from .filter import (Pred, And, Or, TRUE, relax, filter_prune, evaluate,
                     evaluate_truthtable, master_complete_filter)
from .engine import (ALGORITHMS, MODES, MODES_BATCH, PASS2,
                     BatchPruneResult, DistinctMerged,
                     TopNDetMerged, apply_merged, calibrate_merge_cost,
                     default_mesh, engine_prune, engine_prune_batch,
                     execute_plan, execute_plan_batch, merge_states,
                     reset_caches, shard_stack, unshard_mask,
                     unshard_mask_batch)
from .streaming import (PruneStream, StreamResult, engine_prune_stream,
                        lane_view)
from .planner import (SwitchProfile, ResourceFootprint, footprint,
                      pack_queries, rule_count, PackingPlan,
                      MultiSwitchPlan, plan_multi_switch, optimal_shards,
                      optimal_pass2, pass2_time, MEASURED_MERGE_COSTS,
                      QueryBatchPlan, plan_query_batch,
                      RESIDENT_OVERHEAD_ENTRIES, optimal_merge_interval,
                      DEFAULT_STALENESS_RATE, Plan, TuneResult,
                      TUNE_MODES, analytic_plan, candidate_plans, tune,
                      resolve_plan, meshless_lanes, meshless_plan)
from .encoding import (DictEncoding, dict_encode, normalize_encodings,
                       rle_encode, rle_expand)
from .options import DECODE_MODES, ExecOptions
from .plancache import PlanCache, cache_key
from .sketches import (BloomFilter, bloom_build, bloom_query, CountMin,
                       cms_build, cms_query)

__all__ = [n for n in dir() if not n.startswith("_")]
