"""Core: the paper's frequency-aware two-tier software cache."""
from repro.core.cache import CacheConfig, CacheState, init_cache, prepare, flush, warmup
from repro.core.cached_embedding import (
    CachedEmbeddingConfig,
    CachedEmbeddingState,
    init_state,
    prepare_ids,
    embed_onehot,
    embed_bag,
    apply_row_grads,
    flush_state,
)
from repro.core.collection import (
    EmbeddingCollection,
    FeatureBatch,
    Placement,
    PlacementPlan,
    PlacementPlanner,
    ShardAssignment,
    TableConfig,
)
from repro.core.freq import FreqStats, build_freq_stats, collect_counts, coverage
from repro.core.policies import Policy
from repro.core.sharded import ShardedEmbeddingCollection
