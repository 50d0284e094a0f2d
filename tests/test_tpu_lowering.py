"""Cross-platform TPU lowering pins (no chip needed).

``jax.export`` with ``platforms=["tpu"]`` runs the lowering to StableHLO
for the TPU platform, Pallas kernels included (their Mosaic dialect is
emitted, not compiled), so ops that have no TPU lowering fail HERE instead
of on the benchmark chip.  This caught a previous kernel design that used
1-D vector gathers and ``jnp.cumsum`` inside a kernel (no Pallas TPU
lowering).  It runs no TPU compiler: what the chip's compiler would refuse
(unaligned tiling, VMEM overuse, memory fit) is checked by the compiles
for a described chip in tests/test_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import export

from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as tf_ops
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    IdfMode,
    PageRankConfig,
    TfMode,
)


@pytest.fixture(scope="module")
def device_graph():
    g = synthetic_powerlaw(5000, 40000, seed=1)
    return g, ops.put_graph(g, "float32")


@pytest.mark.parametrize("impl", ["segment", "bcoo", "cumsum", "cumsum_mxu", "pallas"])
def test_pagerank_runner_lowers_for_tpu(device_graph, impl):
    g, dg = device_graph
    cfg = PageRankConfig(iterations=5, dangling="redistribute", init="uniform",
                         dtype="float32", spmv_impl=impl)
    runner = ops.make_pagerank_runner(g.n_nodes, cfg)
    e = jnp.asarray(ops.restart_vector(g.n_nodes, cfg))
    r0 = jnp.asarray(ops.init_ranks(g.n_nodes, cfg))
    exp = export.export(runner, platforms=["tpu"])(dg, r0, e)
    module = exp.mlir_module()
    assert module
    if impl == "pallas":
        # the kernel really went through Mosaic, not an interpret fallback
        assert "tpu_custom_call" in module


def test_pagerank_tolerance_runner_lowers_for_tpu(device_graph):
    g, dg = device_graph
    cfg = PageRankConfig(iterations=50, tol=1e-8, dangling="redistribute",
                         init="uniform", dtype="float32", spmv_impl="cumsum")
    runner = ops.make_pagerank_runner(g.n_nodes, cfg)
    e = jnp.asarray(ops.restart_vector(g.n_nodes, cfg))
    r0 = jnp.asarray(ops.init_ranks(g.n_nodes, cfg))
    assert export.export(runner, platforms=["tpu"])(dg, r0, e).mlir_module()


@pytest.mark.parametrize("dangling", ["redistribute", "drop"])
@pytest.mark.parametrize("tol", [0.0, 1e-8], ids=["scan", "while"])
@pytest.mark.parametrize("layout", ["hybrid", "hybrid-headless", "owned"])
def test_sharded_runner_lowers_for_tpu(layout, tol, dangling):
    """The multi-chip shard_map program (collectives included) must lower
    for the TPU platform — the CPU dryrun alone cannot prove that — under
    a fixed iteration count (a scan) and a tolerance (a while loop)."""
    import numpy as np

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_edges
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        ShardedPageRank,
        make_mesh,
    )

    g = synthetic_powerlaw(2000, 10000, seed=1)
    if layout == "hybrid-headless":  # weighted: no dense head
        w = np.random.default_rng(2).uniform(0.5, 2.0, g.n_edges)
        g = from_edges(g.src, g.dst, weight=w, compact_ids=False)
    cfg = PageRankConfig(iterations=3, tol=tol, dangling=dangling,
                         init="uniform", dtype="float32")
    with jax.enable_x64(False):
        job = ShardedPageRank(g, cfg, mesh=make_mesh(8),
                              strategy=layout.split("-")[0])
        sg, ex = job.exec.sg, job.exec
        assert (sg.strategy == "owned"
                or bool((sg.head_src != sg.n_pad).any()) is (layout == "hybrid"))
        runner = ex.make_runner(cfg)
        args = ex.args(ex.put_ranks(ops.init_ranks(sg.n, cfg)))
        exp = export.export(runner, platforms=["tpu"])(*args)
    assert exp.mlir_module()


def test_tfidf_sharded_kernel_lowers_for_tpu():
    """The vocab-sharded TF-IDF ingest kernel (psum'd DF) must lower for
    the TPU platform."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import make_mesh
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.tfidf_sharded import (
        make_sharded_counts_kernel,
    )

    mesh = make_mesh(8)
    kernel = make_sharded_counts_kernel(mesh, vocab=4096)
    docs = jnp.zeros((8, 256), jnp.int32)
    terms = jnp.zeros((8, 256), jnp.int32)
    valid = jnp.ones((8, 256), bool)
    assert export.export(kernel, platforms=["tpu"])(docs, terms, valid).mlir_module()


def test_tfidf_passes_lower_for_tpu():
    ids = jnp.zeros(1024, jnp.int32)
    docs = jnp.zeros(1024, jnp.int32)
    valid = jnp.ones(1024, bool)

    def full(doc_ids, term_ids, token_valid):
        counts = tf_ops.count_pairs(doc_ids, term_ids, token_valid=token_valid)
        df = tf_ops.document_frequency(counts, 4096)
        idf = tf_ops.idf_vector(df, 64.0, IdfMode.SMOOTH)
        dl = jax.ops.segment_sum(
            token_valid.astype(jnp.float32), doc_ids, num_segments=64
        )
        vals = tf_ops.tf_values(counts, dl, TfMode.LOGNORM)
        return counts, df, idf, vals

    exp = export.export(jax.jit(full), platforms=["tpu"])(docs, ids, valid)
    assert exp.mlir_module()
