"""Operations and bytes of the work the benchmark measures, from sizes
alone, so the same work is counted whatever implementation runs it."""


def pagerank_iteration_bytes(n_nodes: int, n_edges: int) -> int:
    """Least HBM bytes one float32 PageRank iteration must move: per edge
    its source index and the gathered source rank (8 B); per node its row
    pointer, inverse out-degree, teleport weight, and old and new rank
    (20 B).  The iteration does about 2 flops per edge, far below the
    chip's flops per byte, so it is bound by bytes."""
    return 8 * n_edges + 20 * n_nodes
