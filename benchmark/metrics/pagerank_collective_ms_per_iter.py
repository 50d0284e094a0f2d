"""Device time of the collectives per sharded PageRank iteration (ms),
mean per chip, over the iterations run in the window.  A collective op
is named in the trace after the JAX primitive that made it (``psum.12``
for the step's all-reduce on a v5e, ``collective-permute-done`` for a
ppermute's second half) or after its HLO opcode; both families count,
their async start and done halves included."""

import re

COLLECTIVE = re.compile(
    r"^(psum|pmax|pmin|ppermute|all[-_]reduce|reduce[-_]scatter|all[-_]gather"
    r"|all[-_]to[-_]all|collective[-_]permute)")


def read(run):
    t = run.trace
    iters = run.window.counts.get("iterations")
    if t is None or t.n_devices == 0 or not iters:
        return None
    secs = sum(s for op, s in t.op_s.items() if COLLECTIVE.match(op))
    return 1e3 * secs / iters
