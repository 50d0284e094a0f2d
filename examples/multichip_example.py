"""Multi-chip PageRank + TF-IDF via the library API (SURVEY.md §2.2 R1–R3).

Demonstrates both sharded layouts over a device mesh — on real chips when a
TPU pod is attached, or on simulated devices anywhere:

    JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/multichip_example.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import run_pagerank
from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
    auto_select_strategy,
    make_mesh,
    run_pagerank_sharded,
    run_tfidf_sharded,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    PageRankConfig,
    TfidfConfig,
)

mesh = make_mesh()  # all visible devices
d = int(mesh.devices.size)
graph = synthetic_powerlaw(20_000, 120_000, seed=3)
cfg = PageRankConfig(iterations=30, dangling="redistribute", init="uniform",
                     dtype="float64")
single = run_pagerank(graph, cfg).ranks

print(f"mesh: {d} devices; auto strategy -> "
      f"{auto_select_strategy(graph, d)!r}")
for strategy in ("hybrid", "owned"):
    res = run_pagerank_sharded(graph, cfg, mesh=mesh, strategy=strategy)
    l1 = np.abs(res.ranks - single).sum()
    print(f"pagerank[{strategy:6s}] on {d} devices: L1 vs single-chip {l1:.2e}")

docs = [f"alpha w{i % 17} w{i % 5} beta{i % 3}" for i in range(512)]
chunks = [docs[i:i + 64] for i in range(0, len(docs), 64)]
out = run_tfidf_sharded(iter(chunks), TfidfConfig(vocab_bits=14), mesh=mesh)
print(f"tfidf sharded: {out.n_docs} docs, nnz={out.nnz} "
      f"(DF psum over {d} devices, replicated IDF broadcast)")
