"""Compiles of the main-path programs for a described v5e chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip guide §2): what it refuses here — a
kernel Mosaic rejects, a program that does not fit the chip's 16 GB — it
would refuse on the chip.  Nothing runs, so these tests say nothing about
results or times.  ``tests/test_tpu_lowering.py`` only lowers; these
compile.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.  Programs are compiled in this process with f32 pinned (conftest
turns x64 on) and with the persistent compilation cache off (entries
compiled for a described chip cannot be read back without one).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw
from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
from page_rank_and_tfidf_using_apache_spark_tpu.ops import tfidf as tf_ops
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    IdfMode,
    PageRankConfig,
    TfMode,
)

V5E_HBM_BYTES = 16 * 10**9
# chip_smoke.py's sizes: web-Google-shaped PageRank, 20NG-shaped TF-IDF
PR_NODES, PR_EDGES = 875_000, 5_100_000
TFIDF_DOCS, TFIDF_TOKENS, VOCAB = 19_000, 19_000 * 180, 1 << 18
INDEX_NNZ = 2_200_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.compilation_cache.reset_cache()
        try:
            try:
                t = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def f32():
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def web_graph():
    return synthetic_powerlaw(PR_NODES, PR_EDGES, seed=7)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("impl", ["segment", "hybrid", "pallas"])
def test_pagerank_runner_compiles_for_v5e(impl, web_graph, one_chip, f32):
    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import (
        put_graph_for,
    )

    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float32", spmv_impl=impl)
    n = web_graph.n_nodes
    dg = _shapes(put_graph_for(web_graph, cfg), one_chip)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = ops.make_pagerank_runner(n, cfg).lower(dg, vec, vec).compile()
    # the Pallas kernel is in (hybrid: rowsum_pallas; pallas: cumsum_pallas)
    assert ("tpu_custom_call" in compiled.as_text()) == (impl != "segment")
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_tfidf_index_build_compiles_for_v5e(one_chip, f32):
    """The batch TF-IDF pipeline and the BM25 weights ``--save-index``
    bundles, at the 19K-doc corpus size."""
    from page_rank_and_tfidf_using_apache_spark_tpu.dataflow.bm25 import bm25_weights

    tok = jax.ShapeDtypeStruct((TFIDF_TOKENS,), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((TFIDF_DOCS,), jnp.int32, sharding=one_chip)
    compiled = tf_ops.tfidf_pipeline.lower(
        tok, tok, lens, n_docs=TFIDF_DOCS, vocab=VOCAB, tf_mode=TfMode.RAW,
        idf_mode=IdfMode.SMOOTH, l2_normalize=True,
    ).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES

    pairs = jax.ShapeDtypeStruct((INDEX_NNZ,), jnp.int32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((INDEX_NNZ,), jnp.float32, sharding=one_chip)
    df = jax.ShapeDtypeStruct((VOCAB,), jnp.float32, sharding=one_chip)
    compiled = bm25_weights.lower(
        pairs, pairs, count, lens, df, n_docs=TFIDF_DOCS, k1=1.5, b=0.75,
    ).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_serve_scoring_compiles_for_v5e(one_chip, f32):
    """The served micro-batch scorer at the largest batch the server pads
    to, against the 19K-doc index."""
    batch, q_slots = 16, 16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = tf_ops.score_query_batch.lower(
        s((INDEX_NNZ,), jnp.int32), s((INDEX_NNZ,), jnp.int32),
        s((INDEX_NNZ,), jnp.float32), s((INDEX_NNZ,), jnp.float32),
        s((batch, q_slots), jnp.int32), s((batch, q_slots), jnp.float32),
        s((batch, q_slots), jnp.float32), s((TFIDF_DOCS,), jnp.float32),
        n_docs=TFIDF_DOCS, vocab=VOCAB, k=10, use_prior=False,
    ).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("strategy", ["auto", "auto-headless", "owned"])
def test_sharded_runner_compiles_for_v5e_mesh(strategy, topo, f32):
    """The 4-chip sharded PageRank program, collectives included, on a mesh
    of the described chips: ``auto`` (what the selector picks for a graph
    whose replicated state fits: ``hybrid``, with a dense head, and
    without one for the same arcs weighted) and ``owned`` (the
    memory-scaling layout)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        pagerank_sharded as ps,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import NODES_AXIS

    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import from_edges

    g = synthetic_powerlaw(200_000, 1_200_000, seed=7)
    if strategy == "auto-headless":
        w = np.random.default_rng(1).uniform(0.5, 2.0, g.n_edges).astype(np.float32)
        g = from_edges(g.src, g.dst, weight=w, compact_ids=False)
    if strategy.startswith("auto"):
        strategy = ps.auto_select_strategy(g, 4, hbm_bytes=V5E_HBM_BYTES)
        assert strategy == "hybrid"
    mesh = Mesh(np.array(topo.devices[:4]), (NODES_AXIS,))
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float32")
    sg = ps.partition_graph(g, 4, strategy=strategy, dtype="float32")
    graph_args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                  for a, sh in ps.sharded_graph_layout(sg, mesh)]
    if strategy == "owned":
        sh = sg.owned
        tail = NamedSharding(mesh, P(NODES_AXIS))
        repl = NamedSharding(mesh, P())
        t_len = int(np.prod(sh.inv_tail.shape))
        h_len = int(np.prod(sh.inv_head.shape))
        state = (jax.ShapeDtypeStruct((t_len,), jnp.float32, sharding=tail),
                 jax.ShapeDtypeStruct((h_len,), jnp.float32, sharding=repl),
                 jax.ShapeDtypeStruct((4,), jnp.float32, sharding=tail),
                 jax.ShapeDtypeStruct((), jnp.float32, sharding=repl))
        args = (state, *graph_args, state[0], state[1])
    else:
        assert bool((sg.head_src == sg.n_pad).all()) is (g.weight is not None)
        vec = jax.ShapeDtypeStruct((sg.n_pad,), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
        args = (vec, *graph_args, vec)
    compiled = ps.make_sharded_runner(sg, cfg, mesh).lower(*args).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "collective-permute" in text
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_sharded_hybrid_gathers_read_the_rank_table_from_vmem(topo, f32):
    """The ``hybrid`` step at ``pagerank.graph500-25.4chip``'s shapes a
    chip (130,940,928 tail arcs, 1,077,248 head rows of 128, 17,072,128
    nodes): each per-edge gather, the tail's from the rank table and the
    head rows' from the table with its zero slot, reads its table from
    VMEM (``S(1)``), because each runs in chunks of at most
    ``ops.GATHER_CAP`` indices.  Whole, both read it from HBM.  The graph
    is zero-stride views: only the shapes are compiled."""
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
        pagerank_sharded as ps,
    )
    from page_rank_and_tfidf_using_apache_spark_tpu.parallel.mesh import NODES_AXIS

    d, e_dev, rows, width, block = 4, 130_940_928, 1_077_248, 128, 4_268_032
    n_pad = d * block

    def zeros(shape, dtype):
        return np.broadcast_to(np.zeros((), dtype), shape)

    sg = ps.ShardedGraph(
        strategy="hybrid", n=n_pad, n_pad=n_pad, block=block,
        src=zeros((d, e_dev), np.int32), dst=zeros((d, e_dev), np.int32),
        valid=zeros((d, e_dev), np.float32),
        inv_outdeg=zeros((n_pad,), np.float32),
        dangling=zeros((n_pad,), np.float32), pad_frac=0.0,
        local_indptr=zeros((d, n_pad + 1), np.int32),
        head_src=zeros((d, rows, width), np.int32),
        head_node=zeros((d, rows), np.int32),
    )
    assert ps.shard_gather_chunks(sg) == {"tail": 16, "head": 17}
    mesh = Mesh(np.array(topo.devices[:d]), (NODES_AXIS,))
    cfg = PageRankConfig(iterations=20, dangling="redistribute", init="uniform",
                         dtype="float32")
    graph_args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                  for a, sh in ps.sharded_graph_layout(sg, mesh)]
    vec = jax.ShapeDtypeStruct((n_pad,), jnp.float32,
                               sharding=NamedSharding(mesh, P()))
    text = ps.make_sharded_runner(sg, cfg, mesh).lower(
        vec, *graph_args, vec).compile().as_text()
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+) ", text, re.M))
    tables = [shapes[name] for name in re.findall(r" gather\(%([^,\s)]+)", text)]
    # the per-edge gathers read the [n_pad] or [n_pad + 1] rank table; the
    # read at each node's last edge gathers from the edge-long scan
    rank_tables = [t for t in tables
                   if t.startswith((f"f32[{n_pad}]", f"f32[{n_pad + 1}]"))]
    assert {t.split("]")[0] for t in rank_tables} == {
        f"f32[{n_pad}", f"f32[{n_pad + 1}"}
    assert all("S(1)" in t for t in rank_tables), rank_tables
