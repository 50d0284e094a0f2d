from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import (
    DATA_AXIS,
    NODES_AXIS,
    init_distributed,
    make_mesh,
    replicated,
    sharded_along,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.pagerank_sharded import (
    ShardedGraph,
    ShardedPageRank,
    auto_select_strategy,
    partition_graph,
    run_pagerank_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.tfidf_sharded import (
    run_tfidf_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.parallel.workloads_sharded import (
    run_components_sharded,
    run_hits_sharded,
    run_ppr_sharded,
)

__all__ = [
    "DATA_AXIS",
    "NODES_AXIS",
    "init_distributed",
    "make_mesh",
    "replicated",
    "sharded_along",
    "ShardedGraph",
    "ShardedPageRank",
    "auto_select_strategy",
    "partition_graph",
    "run_pagerank_sharded",
    "run_tfidf_sharded",
    "run_components_sharded",
    "run_hits_sharded",
    "run_ppr_sharded",
]
