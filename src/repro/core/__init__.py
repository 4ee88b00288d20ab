"""The paper's primary contribution: dynamic user-defined similarity search.

Layers (top first — the typed API is the public surface):
  api         SearchRequest/SearchResponse + Retriever facade over engines
  fields      multi-field vector-space corpus (concat layout)
  weights     query-side dynamic weight embedding (the paper's §4 theorem)
  cluster     pluggable Clusterer backends: fpf / fpf_fused / kmeans / random
  index       ClusterPruneIndex — T independent clusterings + pruned search,
              incremental add_documents/remove_documents maintenance
  celldec     CellDec weight-region baseline [Singitham et al. VLDB'04]
  metrics     competitive recall, NAG, brute-force ground truth
  engine      pluggable SearchEngine backends: reference / fused (1..N devices)
  calibrate   per-index recall->probes ladder (sample -> sweep -> isotonic fit)
  distributed shard_map substrate of the "fused" backend's mesh
"""

from .fields import FieldSpec, concat_fields, normalize_fields, split_fields
from .weights import (
    aggregate_similarity,
    cosine_distance,
    expand_weights,
    nwd,
    validate_weights,
    weighted_query,
)
from .cluster import (
    CLUSTERERS,
    Clusterer,
    ClusteringResult,
    assign_refine,
    assign_to_centers,
    assign_to_centers_multi,
    available_clusterers,
    fpf_centers,
    fpf_cluster,
    get_clusterer,
    kmeans_cluster,
    pick_clusterer,
    random_leader_cluster,
    register_clusterer,
)
from .index import (
    LADDER_DRIFT_THRESHOLD, SUPPORTED_PACK_DTYPES, ClusterPruneIndex,
    CorruptIndexError, pack_buckets, validate_pack_dtype,
)
from .engine import (
    BACKENDS,
    SearchEngine,
    available_backends,
    get_engine,
    pick_backend,
    register_backend,
    resolve_backend,
    split_probes,
    sweep_probes,
)
from .calibrate import ProbeLadder, calibrate_index, isotonic_fit
from .api import (
    ExecShape,
    Hit,
    Retriever,
    SearchRequest,
    SearchResponse,
    decompose_scores,
    exec_shape,
    plan_probes,
)
from .celldec import CellDecIndex, region_of, region_weights
from .metrics import (
    brute_force_bottomk,
    brute_force_topk,
    competitive_recall,
    normalized_aggregate_goodness,
    quality_report,
    recall_fraction,
)

__all__ = [
    "SearchRequest", "SearchResponse", "Hit", "Retriever",
    "ExecShape", "exec_shape",
    "plan_probes", "decompose_scores",
    "FieldSpec", "concat_fields", "normalize_fields", "split_fields",
    "aggregate_similarity", "cosine_distance", "expand_weights", "nwd",
    "validate_weights", "weighted_query",
    "ClusteringResult", "assign_to_centers", "assign_to_centers_multi",
    "fpf_centers", "fpf_cluster",
    "kmeans_cluster", "random_leader_cluster",
    "CLUSTERERS", "Clusterer", "assign_refine", "available_clusterers",
    "get_clusterer", "pick_clusterer", "register_clusterer",
    "ClusterPruneIndex", "CorruptIndexError", "LADDER_DRIFT_THRESHOLD",
    "pack_buckets", "validate_pack_dtype", "SUPPORTED_PACK_DTYPES",
    "BACKENDS", "SearchEngine", "available_backends", "get_engine",
    "pick_backend", "register_backend", "resolve_backend", "split_probes",
    "sweep_probes",
    "ProbeLadder", "calibrate_index", "isotonic_fit",
    "CellDecIndex", "region_of", "region_weights",
    "brute_force_bottomk", "brute_force_topk", "competitive_recall",
    "normalized_aggregate_goodness", "quality_report", "recall_fraction",
]
