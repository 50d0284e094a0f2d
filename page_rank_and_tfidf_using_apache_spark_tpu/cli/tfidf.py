"""TF-IDF CLI — the reference's ``spark-submit tfidf.py <corpus>`` entry
point (SURVEY.md A6, §2.2 R10).

Usage::

    python -m page_rank_and_tfidf_using_apache_spark_tpu.cli.tfidf \
        corpus_dir --output weights.tsv --idf-mode classic
    python -m ...cli.tfidf corpus.txt --lines --streaming --chunk-docs 1000
"""

from __future__ import annotations

import argparse
import json
import sys

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.io.text import (
    iter_corpus_chunks,
    iter_corpus_dir,
    iter_corpus_lines,
    load_corpus_dir,
    load_corpus_lines,
)
from page_rank_and_tfidf_using_apache_spark_tpu.models.tfidf import (
    resume_point,
    run_tfidf,
    run_tfidf_streaming,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.compile_cache import enable_compile_cache
from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
    TfidfConfig,
    load_tuned_profile,
    tuned_config,
)
from page_rank_and_tfidf_using_apache_spark_tpu.utils.metrics import MetricsRecorder
from page_rank_and_tfidf_using_apache_spark_tpu.utils.profiling import trace


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tfidf",
        description="TPU-native TF-IDF over a text corpus (hashed vocabulary).",
    )
    p.add_argument("input", help="corpus directory (one doc per file) or flat file")
    p.add_argument("--lines", action="store_true",
                   help="input is a flat file with one document per line")
    p.add_argument("--output", help="write '<doc>\\t<term_id>\\t<weight>' lines here")
    p.add_argument("--vocab-bits", type=int, default=18)
    p.add_argument("--ngram", type=int, choices=[1, 2], default=1)
    p.add_argument("--tf-mode", choices=["raw", "freq", "lognorm"], default="raw")
    p.add_argument("--idf-mode", choices=["classic", "mllib", "smooth"], default="classic")
    p.add_argument("--l2-normalize", action="store_true")
    p.add_argument("--min-token-len", type=int, default=1)
    p.add_argument("--streaming", action="store_true")
    p.add_argument("--chunk-docs", type=int, default=1024,
                   help="docs per streaming chunk")
    p.add_argument("--chunk-tokens", type=int, default=0,
                   help="fixed token capacity per chunk (0 = auto)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="chunks between checkpoints")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--mesh", type=int, default=0,
                   help="with --streaming: data-parallel ingest over this "
                        "many devices (the BASELINE config-5 'TPU mesh' "
                        "path); 0 = single device")
    p.add_argument("--prefetch", type=int, default=None,
                   help="tokenizer chunks to double-buffer ahead of device "
                        "compute (0 = serial; default: tuned profile, then "
                        "TUNABLE_DEFAULTS)")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   help="H2D-staged chunks the ingest transfer thread may "
                        "hold in device memory — chunk N+1's device_put "
                        "runs under chunk N's compute (0 = stage inline; "
                        "default: tuned profile, then TUNABLE_DEFAULTS)")
    p.add_argument("--pack-target", type=int, default=None, metavar="TOKENS",
                   help="re-pack incoming chunks to ~TOKENS tokens each "
                        "before padding, so half-full chunks stop paying "
                        "full-cap compute (0 = keep the source chunking; "
                        "resume runs must re-use the same value; default: "
                        "tuned profile, then TUNABLE_DEFAULTS)")
    p.add_argument("--tuned-profile", default=None, metavar="PATH",
                   help="tuned-profile artifact to resolve unset knobs "
                        "from ('off' disables profile loading; default: "
                        "$GRAFT_TUNED_PROFILE, then the committed "
                        "tuned_profile_<backend>.json)")
    p.add_argument("--save-index", default=None, metavar="DIR",
                   help="serialize the result as the next servable index "
                        "version under DIR (serving/artifact.py) — the "
                        "input of `cli.serve`")
    p.add_argument("--index-ranks", default=None, metavar="NPY",
                   help="with --save-index: bundle this [n_docs] PageRank "
                        "prior (.npy) into the artifact")
    p.add_argument("--no-index-bm25", action="store_true",
                   help="with --save-index: skip bundling the BM25 "
                        "second-ranker weights (bundled by default — "
                        "same postings, different weighting; enables "
                        "cli.serve --ranker bm25 / per-request A/B)")
    p.add_argument("--bm25-k1", type=float, default=1.5,
                   help="BM25 k1 (term-frequency saturation; default 1.5)")
    p.add_argument("--bm25-b", type=float, default=0.75,
                   help="BM25 b (length normalization; default 0.75)")
    p.add_argument("--query", nargs="+", default=None, metavar="TERM",
                   help="score docs against these terms, print top-k")
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument("--metrics-json")
    p.add_argument("--profile-dir")
    p.add_argument("--trace-dir", default=None,
                   help="obs run-telemetry dir: write <name>.<pid>.trace.jsonl"
                        " + manifest here (default: $GRAFT_TRACE_DIR)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.mesh and not args.streaming:
        raise SystemExit("--mesh requires --streaming (chunked ingest)")
    # The traced run covers the whole driver: manifest at startup, every
    # span/retry/checkpoint event flushed per-event to the JSONL trace,
    # run-end summary at exit (no-op without --trace-dir/GRAFT_TRACE_DIR).
    with obs.run("tfidf", trace_dir=args.trace_dir):
        return _main(args)


def _main(args) -> int:
    metrics = MetricsRecorder()

    if args.streaming:
        # Lazy iteration: the corpus never fully materializes on host.
        docs = (iter_corpus_lines if args.lines else iter_corpus_dir)(args.input)
        names: list[str] = []
    else:
        docs, names = (load_corpus_lines if args.lines else load_corpus_dir)(args.input)
    # knob resolution ladder: explicit flag > tuned profile (same-backend
    # only, ProvenanceError otherwise) > TUNABLE_DEFAULTS
    profile = (None if args.tuned_profile == "off"
               else load_tuned_profile(path=args.tuned_profile))
    cfg = tuned_config(
        TfidfConfig, profile,
        vocab_bits=args.vocab_bits,
        ngram=args.ngram,
        tf_mode=args.tf_mode,
        idf_mode=args.idf_mode,
        l2_normalize=args.l2_normalize,
        min_token_len=args.min_token_len,
        chunk_tokens=args.chunk_tokens,
        prefetch=args.prefetch,
        pipeline_depth=args.pipeline_depth,
        pack_target_tokens=args.pack_target,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
    )
    # On resume, probe the checkpoint for the restart chunk so the chunker
    # never materializes the already-ingested prefix on host (chunk-level
    # resumable streaming: indices stay stable, documents are not re-read).
    # The checkpoint's ingested doc count rides along so a changed
    # --chunk-docs is rejected instead of silently skipping the wrong docs.
    skip, skip_docs = 0, None
    if args.streaming and args.resume:
        skip = resume_point(cfg)
        if skip:
            from page_rank_and_tfidf_using_apache_spark_tpu.utils import (
                checkpoint as ckpt,
            )

            meta = ckpt.peek_meta(ckpt.latest_checkpoint(cfg.checkpoint_dir))
            skip_docs = int(meta["extra"]["n_docs"])
    with trace(args.profile_dir):
        if args.streaming and args.mesh:
            from page_rank_and_tfidf_using_apache_spark_tpu.parallel import (
                run_tfidf_sharded,
            )

            out = run_tfidf_sharded(
                iter_corpus_chunks(docs, args.chunk_docs, skip_chunks=skip,
                                   expect_skipped_docs=skip_docs),
                cfg, n_devices=args.mesh, metrics=metrics, resume=args.resume,
            )
        elif args.streaming:
            out = run_tfidf_streaming(
                iter_corpus_chunks(docs, args.chunk_docs, skip_chunks=skip,
                                   expect_skipped_docs=skip_docs),
                cfg, metrics=metrics, resume=args.resume,
            )
        else:
            out = run_tfidf(docs, cfg, metrics=metrics, doc_names=names)

    if args.save_index:
        import numpy as np

        from page_rank_and_tfidf_using_apache_spark_tpu.serving import save_index
        from page_rank_and_tfidf_using_apache_spark_tpu.utils.config import (
            Bm25Config,
        )

        ranks = np.load(args.index_ranks) if args.index_ranks else None
        bm25 = (None if args.no_index_bm25 or out.count is None
                else Bm25Config(k1=args.bm25_k1, b=args.bm25_b))
        path = save_index(args.save_index, out, cfg, ranks=ranks, bm25=bm25)
        print(json.dumps({"index": path, "bm25": bm25 is not None}),
              file=sys.stderr)

    if args.output:
        with open(args.output, "w") as f:
            for d, t, w in zip(out.doc, out.term, out.weight):
                f.write(f"{names[d] if d < len(names) else d}\t{t}\t{w:.10g}\n")

    if args.query:
        import jax.numpy as jnp
        import numpy as np

        from page_rank_and_tfidf_using_apache_spark_tpu.io.text import (
            fnv1a_64,
            hash_to_vocab,
        )
        from page_rank_and_tfidf_using_apache_spark_tpu.ops.tfidf import TfidfResult, score_query

        q = np.zeros(cfg.vocab_size, np.float32)
        terms = [t.lower() if cfg.lowercase else t for t in args.query]
        q[hash_to_vocab(fnv1a_64(terms), cfg.vocab_bits)] = 1.0
        res = TfidfResult(
            doc=jnp.asarray(out.doc), term=jnp.asarray(out.term),
            weight=jnp.asarray(out.weight),
            n_pairs=jnp.asarray(out.nnz), valid=jnp.ones(out.nnz, jnp.float32),
            idf=jnp.asarray(out.idf), df=jnp.asarray(out.df),
        )
        k = min(args.top_k, max(out.n_docs, 1))
        scores, idx = score_query(res, jnp.asarray(q), n_docs=max(out.n_docs, 1), k=k)
        for s, i in zip(scores, idx):
            if float(s) > 0:
                print(f"{names[int(i)] if int(i) < len(names) else int(i)}\t{float(s):.10g}")

    print(json.dumps({"docs": out.n_docs, "nnz": out.nnz}), file=sys.stderr)
    if args.metrics_json:
        metrics.dump(args.metrics_json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
