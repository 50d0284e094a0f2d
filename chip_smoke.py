"""Bring-up smoke test: the three user paths on one TPU chip, end to end.

Each phase calls a CLI's ``main(argv)`` in this one process, the way a
user runs it, and checks its output against a plain host reference:

- PageRank (``cli.pagerank``) at web-Google shape (BASELINE config 1:
  ``synthetic:875000,5100000,7``, 20 iterations), once with the default
  SpMV and once with ``--spmv-impl hybrid`` (the Pallas ``rowsum_pallas``
  kernel), each within L1 1e-3 of a scipy float64 power iteration;
- TF-IDF with an index build (``cli.tfidf --save-index``) at 20-Newsgroups
  shape (BASELINE config 2), a seeded sample of documents within atol 1e-5
  of a numpy TF-IDF of the same hashed counts;
- serving (``cli.serve --top-k 10``) of 64 seeded queries from that index,
  top-10 ids and scores against host brute-force scoring.

``--chips 4`` runs only the 4-chip sharded PageRank at soc-LiveJournal1
shape (BASELINE config 3) under ``--shard-strategy auto`` and under
whichever of hybrid/owned auto did not pick, against the same reference.

Each PageRank run checkpoints after its first ``PR_SEGMENT`` iterations, so
its second segment runs a compiled program: the smoke prints that segment's
per-iteration time.  The synthetic graph is generated once and handed to
every ``cli.pagerank`` run on it.

Without a TPU it exits non-zero before any phase.  Any exception, any
mismatch and any ``degraded``/``exhausted`` record fails the run.  The last
line of stdout is the JSON verdict ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

SEED = 7
PR_ITERS = 20
PR_SEGMENT = 10  # --checkpoint-every: the second segment runs warm
PR_ONE_CHIP = "synthetic:875000,5100000,7"  # web-Google shape
PR_FOUR_CHIPS = "synthetic:4800000,69000000,7"  # soc-LiveJournal1 shape
L1_BOUND = 1e-3  # prefix-sum SpMVs sit near 2e-4 relative in f32 (README)
TFIDF_DOCS, TFIDF_TOKENS_PER_DOC, VOCAB_BITS = 19_000, 180, 18
TFIDF_SAMPLE_DOCS = 600
N_QUERIES, TOP_K = 64, 10
ATOL = 1e-5

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",  # includes a cache fetch
)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileClock:
    """Sums JAX's compile-event durations (trace, lowering, backend compile
    or persistent-cache fetch) and counts persistent-cache hits/misses."""

    def __init__(self) -> None:
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.secs += secs

    def on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.secs, self.hits, self.misses


class FaultSink:
    """obs-bus sink keeping every ``degraded``/``exhausted`` event."""

    def __init__(self) -> None:
        self.events: list[dict] = []

    def emit(self, event: dict) -> None:
        if event.get("kind") in ("degraded", "exhausted"):
            self.events.append(event)

    def close(self) -> None:
        pass


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, faults: FaultSink):
    secs0, hits0, miss0 = clock.snapshot()
    n_faults = len(faults.events)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    secs, hits, miss = clock.snapshot()
    print(f"[{name}] wall_s={wall:.3f} compile_s={secs - secs0:.3f} "
          f"cache_hits={hits - hits0} cache_misses={miss - miss0}", flush=True)
    new = faults.events[n_faults:]
    require(not new, f"{name}: degraded/exhausted events {new}")


def check_metrics(name: str, path: str) -> list[dict]:
    with open(path) as f:
        records = json.load(f)["records"]
    bad = [r for r in records if r.get("event") in ("degraded", "exhausted")]
    require(not bad, f"{name}: degraded/exhausted records {bad}")
    return records


# ------------------------------------------------------------- PageRank


def pagerank_reference(graph, iterations: int, damping: float = 0.85):
    """Power iteration in float64 with scipy CSR: uniform init and
    restart, dangling mass redistributed uniformly."""
    import scipy.sparse as sp

    n = graph.n_nodes
    a = sp.csr_matrix(
        (np.ones(graph.n_edges), (graph.dst, graph.src)), shape=(n, n)
    )
    outdeg = graph.out_degree.astype(np.float64)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1.0), 0.0)
    dangling = outdeg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(iterations):
        r = (1.0 - damping) / n + damping * (a @ (r * inv) + r[dangling].sum() / n)
    return r


def read_ranks(path: str, graph) -> np.ndarray:
    """``<original id>\\t<rank>`` lines (cli.pagerank --output) as ranks
    aligned with the graph's compacted ids."""
    with open(path) as f:
        rows = np.array(f.read().split(), dtype=np.float64).reshape(-1, 2)
    require(rows.shape[0] == graph.n_nodes,
            f"{path}: {rows.shape[0]} ranks for {graph.n_nodes} nodes")
    ranks = np.zeros(graph.n_nodes)
    ranks[np.searchsorted(graph.node_ids, rows[:, 0].astype(np.int64))] = rows[:, 1]
    return ranks


def graph_for(spec: str):
    from page_rank_and_tfidf_using_apache_spark_tpu.io.graph import synthetic_powerlaw

    n, e, seed = (int(x) for x in spec.split(":", 1)[1].split(","))
    return synthetic_powerlaw(n, e, seed=seed)


@contextlib.contextmanager
def shared_graph(spec: str, graph):
    """``cli.pagerank`` generates a ``synthetic:`` input itself; hand it
    the graph already generated for the reference instead (same sizes and
    seed, so the same graph).  Generation is host set-up: about a minute a
    run at soc-LiveJournal1 shape."""
    from page_rank_and_tfidf_using_apache_spark_tpu.cli import pagerank as cli

    made = cli.synthetic_powerlaw
    sizes = tuple(int(x) for x in spec.split(":", 1)[1].split(","))

    def generate(n, e, seed=0):
        return graph if (n, e, seed) == sizes else made(n, e, seed=seed)

    cli.synthetic_powerlaw = generate
    try:
        yield
    finally:
        cli.synthetic_powerlaw = made


def run_pagerank_cli(name, spec, iterations, extra, tmp, clock, faults):
    """One ``cli.pagerank`` run in segments of ``PR_SEGMENT`` iterations;
    prints the warm per-iteration time (the last segment's) and returns
    (ranks file, metrics records)."""
    from page_rank_and_tfidf_using_apache_spark_tpu.cli import pagerank as cli

    out = os.path.join(tmp, f"{name}.ranks.tsv")
    mj = os.path.join(tmp, f"{name}.metrics.json")
    with phase(name, clock, faults):
        rc = cli.main([spec, str(iterations), "--dangling", "redistribute",
                       "--init", "uniform", "--output", out,
                       "--checkpoint-every", str(PR_SEGMENT),
                       "--checkpoint-dir", os.path.join(tmp, f"{name}.ckpt"),
                       "--metrics-json", mj, *extra])
    require(rc == 0, f"{name}: cli.pagerank exited {rc}")
    records = check_metrics(name, mj)
    segs = [r for r in records if "iter" in r and "secs" in r]
    print(f"[{name}] segment_s={[round(r['secs'], 6) for r in segs]} "
          f"warm_step_s={segs[-1]['secs'] / PR_SEGMENT:.6f}", flush=True)
    return out, records


def check_l1(name: str, ranks: np.ndarray, ref: np.ndarray) -> None:
    l1 = float(np.abs(ranks - ref).sum())
    print(f"[{name}] l1_vs_f64_reference={l1:.3e} (bound {L1_BOUND:g}) "
          f"rank_sum={ranks.sum():.6f}", flush=True)
    require(l1 < L1_BOUND, f"{name}: L1 {l1:.3e} >= {L1_BOUND:g}")


def hybrid_program_check(graph, iterations: int) -> None:
    """Compile the hybrid runner ``cli.pagerank --spmv-impl hybrid`` ran
    (same config, same graph) and require the Pallas kernel in its text;
    then time ``block_until_ready`` against a following scalar fetch — if
    the first fences, the fetch after it is only a D2H copy."""
    import jax

    from page_rank_and_tfidf_using_apache_spark_tpu.models.pagerank import put_graph_for
    from page_rank_and_tfidf_using_apache_spark_tpu.ops import pagerank as ops
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import PageRankConfig

    cfg = PageRankConfig(iterations=iterations, dangling="redistribute",
                         init="uniform", dtype="float32", spmv_impl="hybrid")
    n = graph.n_nodes
    dg = put_graph_for(graph, cfg)
    e = jax.device_put(ops.restart_vector(n, cfg))
    r0 = ops.init_ranks(n, cfg)
    compiled = ops.make_pagerank_runner(n, cfg).lower(dg, jax.device_put(r0), e).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"[pagerank[hybrid]] tpu_custom_call_in_program={has_kernel}", flush=True)
    require(has_kernel, "hybrid program has no tpu_custom_call (Pallas kernel)")
    for rep in range(2):
        ranks0 = jax.device_put(r0)
        float(ranks0[0])
        t0 = time.perf_counter()
        ranks, _iters, delta = compiled(dg, ranks0, e)
        t1 = time.perf_counter()
        jax.block_until_ready(ranks)
        t2 = time.perf_counter()
        float(delta)
        t3 = time.perf_counter()
        print(f"[fence] rep={rep} dispatch_s={t1 - t0:.6f} "
              f"block_until_ready_s={t2 - t1:.6f} "
              f"scalar_fetch_after_s={t3 - t2:.6f}", flush=True)


def pagerank_phase(spec: str, iterations: int, tmp: str, clock, faults) -> None:
    graph = graph_for(spec)
    ref = pagerank_reference(graph, iterations)
    for impl in ("segment", "hybrid"):
        name = f"pagerank[{impl}]"
        extra = [] if impl == "segment" else ["--spmv-impl", impl]
        with shared_graph(spec, graph):
            out, _ = run_pagerank_cli(name, spec, iterations, extra, tmp,
                                      clock, faults)
        check_l1(name, read_ranks(out, graph), ref)
    with phase("pagerank[hybrid] program check", clock, faults):
        hybrid_program_check(graph, iterations)


def sharded_phase(spec: str, iterations: int, n_devices: int, tmp: str,
                  clock, faults) -> None:
    graph = graph_for(spec)
    ref = pagerank_reference(graph, iterations)
    mesh = ["--mesh", str(n_devices)]
    with shared_graph(spec, graph):
        out, recs = run_pagerank_cli("pagerank_sharded[auto]", spec, iterations,
                                     [*mesh, "--shard-strategy", "auto"], tmp,
                                     clock, faults)
        chosen = next(r["chosen"] for r in recs
                      if r.get("event") == "auto_strategy")
        print(f"[pagerank_sharded[auto]] chose={chosen}", flush=True)
        runs = [("auto", out, recs)]
        for strategy in ("hybrid", "owned"):
            if strategy != chosen:
                name = f"pagerank_sharded[{strategy}]"
                o, r = run_pagerank_cli(name, spec, iterations,
                                        [*mesh, "--shard-strategy", strategy],
                                        tmp, clock, faults)
                runs.append((strategy, o, r))
    for strategy, out, recs in runs:
        name = f"pagerank_sharded[{strategy}]"
        place = next(r for r in recs if r.get("event") == "ranks_placement")
        print(f"[{name}] strategy={place['strategy']} "
              f"ranks_devices={place['devices']} "
              f"bytes_in_use={json.dumps(place['bytes_in_use'])}", flush=True)
        require(place["devices"] == n_devices,
                f"{name}: ranks live on {place['devices']} of {n_devices} devices")
        check_l1(name, read_ranks(out, graph), ref)


# ------------------------------------------------------- TF-IDF + serve


def hashed_ids(text: str) -> np.ndarray:
    from page_rank_and_tfidf_using_apache_spark_tpu.io import text as tio

    toks = tio.tokenize(text)
    return tio.hash_to_vocab(tio.fnv1a_64(toks), VOCAB_BITS)


def tfidf_reference(docs: list[str], sample: np.ndarray):
    """Smooth-IDF, L2-normalized raw-count TF-IDF of the hashed counts, in
    float64: ``{doc: (sorted term ids, weights)}`` for ``sample``."""
    ids = [hashed_ids(d) for d in docs]
    pairs = [np.unique(t) for t in ids]
    df = np.bincount(np.concatenate(pairs), minlength=1 << VOCAB_BITS)
    idf = np.log((1.0 + len(docs)) / (1.0 + df)) + 1.0
    out = {}
    for d in sample:
        terms, counts = np.unique(ids[d], return_counts=True)
        w = counts * idf[terms]
        out[int(d)] = (terms, w / np.sqrt((w * w).sum()))
    return out


def tfidf_phase(n_docs: int, tokens_per_doc: int, tmp: str, clock, faults):
    from page_rank_and_tfidf_using_apache_spark_tpu.cli import tfidf as cli
    from page_rank_and_tfidf_using_apache_spark_tpu.io.text import synthetic_corpus_lines
    from page_rank_and_tfidf_using_apache_spark_tpu.serving import load_index

    docs = synthetic_corpus_lines(n_docs, tokens_per_doc, SEED)
    corpus = os.path.join(tmp, "corpus.txt")
    with open(corpus, "w") as f:
        f.write("\n".join(docs) + "\n")
    index_dir = os.path.join(tmp, "index")
    mj = os.path.join(tmp, "tfidf.metrics.json")
    with phase("tfidf", clock, faults):
        rc = cli.main([corpus, "--lines", "--vocab-bits", str(VOCAB_BITS),
                       "--idf-mode", "smooth", "--l2-normalize",
                       "--save-index", index_dir, "--metrics-json", mj])
    require(rc == 0, f"tfidf: cli.tfidf exited {rc}")
    check_metrics("tfidf", mj)

    index = load_index(index_dir)
    require(index.n_docs == len(docs), f"tfidf: {index.n_docs} docs indexed")
    rng = np.random.default_rng(SEED)
    sample = np.sort(rng.choice(len(docs), min(TFIDF_SAMPLE_DOCS, len(docs)),
                                replace=False))
    ref = tfidf_reference(docs, sample)
    doc = np.asarray(index.doc)
    term = np.asarray(index.term)
    weight = np.asarray(index.weight, np.float64)
    order = np.argsort(doc, kind="stable")
    bounds = np.searchsorted(doc[order], [sample, sample + 1])
    worst = 0.0
    for d, lo, hi in zip(sample, *bounds):
        rows = order[lo:hi]
        by_term = np.argsort(term[rows])
        ref_terms, ref_w = ref[int(d)]
        require(np.array_equal(term[rows][by_term], ref_terms),
                f"tfidf: doc {d} term set differs from the reference")
        worst = max(worst, float(np.abs(weight[rows][by_term] - ref_w).max()))
    print(f"[tfidf] sample_docs={len(sample)} max_abs_err={worst:.3e} "
          f"(atol {ATOL:g}) nnz={doc.shape[0]}", flush=True)
    require(worst <= ATOL, f"tfidf: max abs weight error {worst:.3e} > {ATOL:g}")
    return docs, index_dir


def serve_reference(index, queries: list[str]):
    """Host brute force over the same index: per query, every document's
    score as a float64 sum of query-count x weight, ranked by score then
    by lower doc id (the server's lax.top_k tie order)."""
    doc = np.asarray(index.doc)
    term = np.asarray(index.term)
    weight = np.asarray(index.weight, np.float64)
    out = []
    for q in queries:
        qdense = np.zeros(1 << VOCAB_BITS)
        np.add.at(qdense, hashed_ids(q), 1.0)
        scores = np.bincount(doc, weights=weight * qdense[term],
                             minlength=index.n_docs)
        out.append(scores)
    return out


def serve_phase(docs: list[str], index_dir: str, tmp: str, clock, faults) -> None:
    from page_rank_and_tfidf_using_apache_spark_tpu.cli import serve as cli
    from page_rank_and_tfidf_using_apache_spark_tpu.serving import load_index

    rng = np.random.default_rng(SEED + 1)
    queries = []
    for _ in range(N_QUERIES):
        words = docs[rng.integers(len(docs))].split()
        queries.append(" ".join(rng.choice(words, rng.integers(1, 5))))
    qfile = os.path.join(tmp, "queries.txt")
    with open(qfile, "w") as f:
        f.write("\n".join(queries) + "\n")
    buf = io.StringIO()
    with phase("serve", clock, faults), contextlib.redirect_stdout(buf):
        rc = cli.main([index_dir, "--queries", qfile, "--top-k", str(TOP_K)])
    require(rc == 0, f"serve: cli.serve exited {rc}")
    served: dict[int, list[tuple[int, float]]] = {}
    for line in buf.getvalue().splitlines():
        qid, d, s = line.split("\t")
        served.setdefault(int(qid), []).append((int(d), float(s)))

    ref_scores = serve_reference(load_index(index_dir), queries)
    exact, worst = 0, 0.0
    for qid, scores in enumerate(ref_scores):
        ranked = np.lexsort((np.arange(scores.shape[0]), -scores))[:TOP_K]
        ranked = ranked[scores[ranked] > 0]
        got = served.get(qid, [])
        require(len(got) == ranked.shape[0],
                f"serve: query {qid} returned {len(got)} hits, "
                f"reference {ranked.shape[0]}")
        for i, (d, s) in enumerate(got):
            # the served doc really holds the i-th best score; with ties
            # inside atol any of the tied docs is a correct answer
            err = max(abs(s - scores[ranked[i]]), abs(scores[d] - scores[ranked[i]]))
            worst = max(worst, err)
            exact += int(d == ranked[i])
        require(len({d for d, _ in got}) == len(got), f"serve: query {qid} repeats a doc")
    total = sum(len(v) for v in served.values())
    print(f"[serve] queries={len(queries)} hits={total} exact_id_matches={exact} "
          f"max_abs_score_err={worst:.3e} (atol {ATOL:g})", flush=True)
    require(worst <= ATOL, f"serve: score error {worst:.3e} > {ATOL:g}")


# ------------------------------------------------------------------ main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip sharded PageRank path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's default device is {dev.platform} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from page_rank_and_tfidf_using_apache_spark_tpu import obs
    from page_rank_and_tfidf_using_apache_spark_tpu.utils import native
    from page_rank_and_tfidf_using_apache_spark_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    print(f"device_kind={dev.device_kind} device_count={len(devices)} "
          f"jax={jax.__version__} libtpu={importlib.metadata.version('libtpu')}",
          flush=True)
    print(f"compile_cache_dir={cache_dir}", flush=True)
    print(f"native_helper_loaded={native.available()}", flush=True)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    faults = FaultSink()
    obs.bus().attach(faults)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            sharded_phase(PR_FOUR_CHIPS, PR_ITERS, 4, tmp, clock, faults)
        else:
            pagerank_phase(PR_ONE_CHIP, PR_ITERS, tmp, clock, faults)
            docs, index_dir = tfidf_phase(TFIDF_DOCS, TFIDF_TOKENS_PER_DOC,
                                          tmp, clock, faults)
            serve_phase(docs, index_dir, tmp, clock, faults)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.secs:.3f} cache_hits={clock.hits} "
          f"cache_misses={clock.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
