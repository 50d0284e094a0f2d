"""TF-IDF numeric core: hashed-vocabulary TF / DF / weight passes on device.

Reference counterpart (SURVEY.md §3.2, BASELINE.json:5): Spark's
``flatMap(tokenize) → reduceByKey`` term-count pass, the ``distinct →
reduceByKey`` document-frequency pass, and the ``tf.join(idf)`` weight join
— three shuffles over ((term, doc), count) records.

TPU-native design: tokens arrive as flat hashed ``(doc_id, term_id)`` int32
arrays (io/text.py).  Both `reduceByKey` passes become **one sort + one
run-length encoding**: sort tokens by the composite key ``term·D + doc``;
each maximal run of equal keys is one (term, doc) pair, so

- TF  = run lengths                       (``segment_sum`` of ones over runs)
- DF  = number of runs per term           (``segment_sum`` of run-starts)
- the tf·idf "join" = a gather of ``idf[term]`` into each run

All shapes are static (outputs padded to ``n_tokens`` with a validity mask),
so the whole pipeline is one ``jit``-compiled XLA program per (n_tokens,
vocab) shape — the streaming ingest path (models/tfidf.py) feeds fixed-size
chunks precisely so this compiles once (SURVEY.md §7 "fixed shapes under
jit").
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import IdfMode, TfMode


class SparseCounts(NamedTuple):
    """Padded COO of per-(doc, term) counts — the materialized result of the
    reference's TF `reduceByKey`.  Rows ``[0, n_pairs)`` are valid, sorted by
    (term, doc); the padding tail repeats harmless zeros."""

    doc: jax.Array  # int32 [cap]
    term: jax.Array  # int32 [cap]
    count: jax.Array  # f[cap]
    n_pairs: jax.Array  # int32 scalar — number of valid rows
    valid: jax.Array  # f[cap] — 1.0 for valid rows


class TfidfResult(NamedTuple):
    """Sparse per-(doc, term) TF-IDF weights + the dense IDF vector (the
    reference's joined A10 output plus the broadcast IDF table R3).

    ``count`` carries the raw per-pair term counts alongside the
    finalized weights: the BM25 ranker (dataflow/bm25.py) re-weights the
    SAME postings from counts, so the pipeline exports them instead of
    forcing a second corpus pass.  Optional (None) for legacy callers
    that build a result by hand."""

    doc: jax.Array  # int32 [cap]
    term: jax.Array  # int32 [cap]
    weight: jax.Array  # f[cap]
    n_pairs: jax.Array  # int32 scalar
    valid: jax.Array  # f[cap]
    idf: jax.Array  # f[vocab]
    df: jax.Array  # f[vocab]
    count: jax.Array | None = None  # f[cap] raw per-pair counts


def count_pairs(
    doc_ids: jax.Array,
    term_ids: jax.Array,
    *,
    token_valid: jax.Array | None = None,
) -> SparseCounts:
    """The TF pass: ((term, doc), 1) → reduceByKey(add), as sort + RLE.

    ``token_valid`` masks padding tokens (streaming chunks); masked tokens
    sort to a sentinel key past every real pair and are excluded.
    """
    cap = doc_ids.shape[0]
    dtype = jnp.float32
    if cap == 0:  # empty corpus/chunk: keep every downstream shape valid
        zf = jnp.zeros(0, dtype)
        zi = jnp.zeros(0, jnp.int32)
        return SparseCounts(doc=zi, term=zi, count=zf, n_pairs=jnp.array(0, jnp.int32), valid=zf)
    # Lexicographic (valid-first, term-major, doc-minor) sort — avoids a
    # composite int key, which would overflow int32 at vocab 2^18 × many docs.
    # Multi-operand lax.sort instead of jnp.lexsort: the sorted doc/term/
    # validity arrays come out directly (no int64 permutation vector, no
    # post-sort gathers), so every aval in the trace stays at the declared
    # 32-bit widths — the tier-2 implicit-promotion gate traces this under
    # x64 and fails on any 64-bit leak.  Unstable: every operand is a key
    # (or, for the validity flag, a function of one), so any order of equal
    # keys is the same output — and the TPU compiler builds an unstable
    # sort in about half the time (21 s vs 46 s for the 19K-doc pipeline,
    # tests/test_tpu_compile.py).
    if token_valid is not None:
        _, term_s, doc_s, tok_valid_s = jax.lax.sort(
            (~token_valid, term_ids, doc_ids, token_valid),
            num_keys=3,
            is_stable=False,
        )
    else:
        term_s, doc_s = jax.lax.sort((term_ids, doc_ids), num_keys=2, is_stable=False)
        tok_valid_s = jnp.ones(cap, dtype=bool)

    changed = jnp.logical_or(term_s[1:] != term_s[:-1], doc_s[1:] != doc_s[:-1])
    run_start = jnp.concatenate([jnp.ones(1, bool), changed])
    run_start = jnp.logical_and(run_start, tok_valid_s)
    run_idx = jnp.cumsum(run_start.astype(jnp.int32)) - 1  # run id per token
    n_pairs = run_idx[-1] + 1
    # All tokens of a run share doc/term, so duplicate scatters write the
    # same value — order doesn't matter.
    safe_run = jnp.where(tok_valid_s, run_idx, cap - 1)
    doc_o = jnp.zeros(cap, doc_ids.dtype).at[safe_run].set(doc_s)
    term_o = jnp.zeros(cap, term_ids.dtype).at[safe_run].set(term_s)
    count_o = jax.ops.segment_sum(
        tok_valid_s.astype(dtype), safe_run, num_segments=cap
    )
    valid = (jnp.arange(cap, dtype=jnp.int32) < n_pairs).astype(dtype)
    return SparseCounts(
        doc=doc_o, term=term_o, count=count_o * valid, n_pairs=n_pairs, valid=valid
    )


def document_frequency(counts: SparseCounts, vocab: int) -> jax.Array:
    """The DF pass: distinct (term, doc) → (term, 1) → reduceByKey(add).
    Each valid COO row *is* one distinct pair, so DF is a segment_sum of the
    validity mask over terms."""
    return jax.ops.segment_sum(counts.valid, counts.term, num_segments=vocab)


def idf_vector(df: jax.Array, n_docs: jax.Array | float, mode: IdfMode) -> jax.Array:
    """IDF formula variants (SURVEY.md §4 — the reference's exact smoothing
    is unverifiable, so every common variant is pinned behind the flag).
    Terms with df == 0 get idf 0 (they never appear, weight is 0 anyway) —
    avoids inf under CLASSIC."""
    n = jnp.asarray(n_docs, df.dtype)
    safe_df = jnp.maximum(df, 1.0)
    if mode is IdfMode.CLASSIC:
        idf = jnp.log(n / safe_df)
    elif mode is IdfMode.MLLIB:
        idf = jnp.log((n + 1.0) / (df + 1.0))
    elif mode is IdfMode.SMOOTH:
        idf = jnp.log((1.0 + n) / (1.0 + df)) + 1.0
    else:
        raise ValueError(f"unknown idf mode {mode}")
    return jnp.where(df > 0, idf, 0.0)


def tf_values(
    counts: SparseCounts, doc_lengths: jax.Array, mode: TfMode
) -> jax.Array:
    """TF variants over the raw per-pair counts."""
    if mode is TfMode.RAW:
        return counts.count
    if mode is TfMode.FREQ:
        dl = jnp.maximum(doc_lengths[counts.doc].astype(counts.count.dtype), 1.0)
        return counts.count / dl
    if mode is TfMode.LOGNORM:
        return jnp.where(counts.count > 0, 1.0 + jnp.log(counts.count), 0.0) * counts.valid
    raise ValueError(f"unknown tf mode {mode}")


@functools.partial(
    jax.jit,
    static_argnames=("n_docs", "vocab", "tf_mode", "idf_mode", "l2_normalize"),
)
def tfidf_pipeline(
    doc_ids: jax.Array,
    term_ids: jax.Array,
    doc_lengths: jax.Array,
    *,
    n_docs: int,
    vocab: int,
    tf_mode: TfMode = TfMode.RAW,
    idf_mode: IdfMode = IdfMode.CLASSIC,
    l2_normalize: bool = False,
) -> TfidfResult:
    """The full batch pipeline as one XLA program: TF pass → DF pass → IDF
    vector → weight join (→ optional per-doc L2 norm, sklearn-style)."""
    counts = count_pairs(doc_ids, term_ids)
    df = document_frequency(counts, vocab)
    idf = idf_vector(df, float(n_docs), idf_mode)
    tf = tf_values(counts, doc_lengths, tf_mode)
    w = tf * idf[counts.term] * counts.valid
    if l2_normalize:
        sq = jax.ops.segment_sum(w * w, counts.doc, num_segments=n_docs)
        norm = jnp.sqrt(jnp.maximum(sq, 1e-30))
        w = w / norm[counts.doc]
    return TfidfResult(
        doc=counts.doc, term=counts.term, weight=w,
        n_pairs=counts.n_pairs, valid=counts.valid, df=df, idf=idf,
        count=counts.count,
    )


@functools.partial(
    jax.jit, static_argnames=("n_docs", "tf_mode", "l2_normalize"))
def finalize_weights(
    doc: jax.Array,  # int32 [nnz]
    count: jax.Array,  # f[nnz]
    doc_lengths: jax.Array,  # int32 [n_docs]
    idf_per_pair: jax.Array,  # f[nnz] — idf[term] pre-gathered on host
    *,
    n_docs: int,
    tf_mode: TfMode,
    l2_normalize: bool,
) -> jax.Array:
    """Device-side second pass of the streaming ingest (SURVEY.md §5.7):
    TF weighting + idf join + optional per-doc L2 norm over the accumulated
    COO.  One compile at the final nnz; the elementwise math and the two
    doc-segment reductions are where the numpy finalize spent its time at
    Wikipedia scale."""
    if tf_mode is TfMode.RAW:
        tf = count
    elif tf_mode is TfMode.FREQ:
        tf = count / jnp.maximum(doc_lengths[doc].astype(count.dtype), 1.0)
    elif tf_mode is TfMode.LOGNORM:
        tf = jnp.where(count > 0, 1.0 + jnp.log(jnp.maximum(count, 1.0)), 0.0)
    else:
        raise ValueError(f"unknown tf mode {tf_mode}")
    w = tf * idf_per_pair
    if l2_normalize:
        sq = jax.ops.segment_sum(w * w, doc, num_segments=n_docs)
        w = w / jnp.sqrt(jnp.maximum(sq, 1e-30))[doc]
    return w


@functools.partial(jax.jit, static_argnames=("vocab",))
def chunk_counts(
    doc_ids: jax.Array,
    term_ids: jax.Array,
    token_valid: jax.Array,
    *,
    vocab: int,
) -> tuple[SparseCounts, jax.Array]:
    """Streaming-ingest kernel: one fixed-shape chunk → (per-pair counts,
    per-term DF increment).  Compiles once for the chunk shape; every chunk
    reuses the executable (SURVEY.md §5.7)."""
    counts = count_pairs(doc_ids, term_ids, token_valid=token_valid)
    df = document_frequency(counts, vocab)
    return counts, df


@functools.partial(
    jax.jit, static_argnames=("vocab",), donate_argnums=(3,))
def chunk_counts_carry(
    doc_ids: jax.Array,
    term_ids: jax.Array,
    token_valid: jax.Array,
    df_carry: jax.Array,
    *,
    vocab: int,
) -> tuple[SparseCounts, jax.Array]:
    """The production streaming-ingest kernel: one fixed-shape chunk →
    (per-pair counts, **updated device-resident DF accumulator**).

    Unlike :func:`chunk_counts` (which returns a per-chunk DF *increment*
    for the host to add up), the DF vector lives on device across the whole
    stream and ``df_carry`` is **donated**: XLA writes the accumulated DF
    back into the same buffer every chunk instead of allocating a fresh
    vocab-sized vector, and the host never pulls DF per chunk — only at
    checkpoint commit points and finalize (models/tfidf.py).  At vocab 2^18
    that removes a ~1 MB device→host transfer per chunk from the streaming
    hot loop.  The tier-3 donation verifier (analysis/cost.py) holds the
    donation against the lowered computation's input/output aliasing.
    """
    counts = count_pairs(doc_ids, term_ids, token_valid=token_valid)
    df = document_frequency(counts, vocab)
    return counts, df_carry + df


@functools.partial(jax.jit, static_argnames=("n_docs", "k"))
def score_query(
    result: TfidfResult,
    query_weights: jax.Array,  # f[vocab] — query's weight per term
    *,
    n_docs: int,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """A11 top-k query scoring: score(doc) = Σ_t w[doc,t]·q[t], then top-k.
    The sparse dot rides the same segment_sum machinery as everything else."""
    per_pair = result.weight * query_weights[result.term] * result.valid
    scores = jax.ops.segment_sum(per_pair, result.doc, num_segments=n_docs)
    return jax.lax.top_k(scores, k)


@functools.partial(
    jax.jit, static_argnames=("n_docs", "vocab", "k", "use_prior"))
def score_query_batch(
    doc: jax.Array,  # int32 [nnz] postings (device-resident across calls)
    term: jax.Array,  # int32 [nnz]
    weight: jax.Array,  # f[nnz]
    valid: jax.Array,  # f[nnz]
    q_term: jax.Array,  # int32 [B, Q] hashed query term ids (padded)
    q_weight: jax.Array,  # f[B, Q] per-term query weights
    q_valid: jax.Array,  # f[B, Q] 1.0 for real query slots
    doc_prior: jax.Array,  # f[n_docs] additive prior (e.g. scaled PageRank)
    *,
    n_docs: int,
    vocab: int,
    k: int,
    use_prior: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The warm serving path's batched A11 scorer (ISSUE 8): one compiled
    program scores a padded micro-batch of sparse queries against the
    device-resident postings and returns per-query top-k — the full
    ``[B, n_docs]`` score matrix never crosses device→host.

    Queries arrive *sparse* ([B, Q] term ids + weights, Q fixed) so the
    per-request H2D transfer is bytes, not a vocab-sized vector; the dense
    per-query lookup table is scattered on device.  Padding slots carry
    ``q_valid`` 0 and term id 0, scattering nothing.  Per query the math is
    exactly :func:`score_query`'s (same multiply order, same segment_sum),
    so a served result is bit-equal to the one-shot path — pinned by
    tests/test_serving.py.  ``use_prior`` (static) fuses an additive
    per-document prior — the PageRank ranks riding in the serving artifact
    — into the score before top-k.
    """
    b = q_term.shape[0]
    qdense = jnp.zeros((b, vocab), weight.dtype)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    qdense = qdense.at[rows, q_term].add(q_weight * q_valid)

    def one(qrow):
        per_pair = weight * qrow[term] * valid
        scores = jax.ops.segment_sum(per_pair, doc, num_segments=n_docs)
        if use_prior:
            scores = scores + doc_prior
        return scores

    scores = jax.vmap(one)(qdense)
    return jax.lax.top_k(scores, k)


@functools.partial(
    jax.jit,
    static_argnames=("n_docs", "batch", "bucket_width", "k", "use_prior"))
def score_impacted_batch(
    doc,  # int32 [nnz] CSC-by-term postings: doc ids, term-major order
    weight,  # f[nnz] ranker weight table over the SAME rows
    bucket_start,  # int32 [C] postings offset of each bucket's first row
    bucket_len,  # int32 [C] live rows in the bucket (0 for pad buckets)
    bucket_row,  # int32 [C] padded query row the bucket scores into
    bucket_qw,  # f[C] query weight of the bucket's term (0 for pads)
    doc_prior,  # f[n_docs] additive prior (e.g. scaled PageRank)
    *,
    n_docs: int,
    batch: int,
    bucket_width: int,
    k: int,
    use_prior: bool = False,
):
    """The latency-shaped serving scorer (ISSUE 13): score a padded query
    micro-batch against ONLY the batch's query terms' posting runs.

    :func:`score_query_batch` is throughput-shaped — every dispatch pays a
    ``[B, vocab]`` scatter plus a ``[B, nnz]`` gather over the WHOLE
    postings table, so p50 grows with corpus nnz whatever the query asks.
    Here the host (serving/server.py) slices each query term's posting run
    out of the CSC-by-term layout (``term_offsets`` in the index artifact)
    and pads the runs into fixed-width buckets — ``sort_shuffle``'s
    fixed-bucket trick applied to postings — so the device program is pure
    reshape → gather → scatter-add over ``C·W`` postings rows, where
    ``C·W ≈ Σ df(query terms)``, independent of corpus nnz.

    Byte-equality with the full-COO path is load-bearing (the serving A/B
    is pinned, not hoped): per (row, doc) the contributions arrive in the
    same order the COO path adds them — query terms ascending (the host
    planner walks the canonical term-sorted query), docs ascending within
    a run (the artifact is (term, doc)-sorted) — and every pad slot
    contributes an exact ``±0.0``, which IEEE addition absorbs.  The same
    multiply association ``(weight · q) · mask`` is kept so rounding is
    identical.

    Pad buckets carry ``len 0, row 0, qw 0``; dead lanes of a partial
    bucket are masked the same way.  ``batch``/``bucket_width`` are static
    (the compile signature is one (batch cap, bucket cap) point of the
    serving shape matrix); the outputs are per-query top-k over the
    LOCAL doc-id space — the segment merge (:func:`topk_merge`)
    globalizes ids.
    """
    lane = jnp.arange(bucket_width, dtype=jnp.int32)[None, :]  # [1, W]
    idx = bucket_start[:, None] + lane  # [C, W]
    live = lane < bucket_len[:, None]  # bool [C, W]
    safe = jnp.where(live, idx, 0)
    mask = live.astype(weight.dtype)
    contrib = weight[safe] * bucket_qw[:, None] * mask
    rows = jnp.broadcast_to(bucket_row[:, None], safe.shape)
    cols = jnp.where(live, doc[safe], 0)
    scores = jnp.zeros((batch, n_docs), weight.dtype).at[rows, cols].add(
        contrib
    )
    if use_prior:
        scores = scores + doc_prior
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_merge(seg_scores, seg_ids, seg_bases, *, k: int):
    """Device-side merge of per-segment top-k candidates (ISSUE 13):
    ``seg_scores``/``seg_ids`` are tuples of per-segment ``[B, k_i]``
    arrays (local doc ids), ``seg_bases`` the per-segment global doc-id
    bases.  Candidates are globalized and re-ranked in ONE fused program,
    so only ``[B, k]`` ever crosses device→host however many live
    segments a query fans out over.  Ties keep the earlier (older,
    lower-base) segment — ``lax.top_k`` is stable in input position."""
    scores = jnp.concatenate(list(seg_scores), axis=1)
    ids = jnp.concatenate(
        [i + jnp.asarray(b, i.dtype) for i, b in zip(seg_ids, seg_bases)],
        axis=1,
    )
    top, pos = jax.lax.top_k(scores, k)
    # Row-wise gather, not take_along_axis: jax 0.9 widens its indices to
    # int64 under x64.
    return top, jax.vmap(lambda row, p: row[p])(ids, pos)
