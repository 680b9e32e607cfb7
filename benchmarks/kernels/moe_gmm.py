"""Grouped matrix products over rows sorted by expert
(``ops/grouped_matmul.py``): ``moe_gmm`` (rows x an expert's matrix),
``moe_gmm_t`` (rows x its transpose, the input gradient) and ``moe_tgmm``
(rows^T x rows into each expert's float32 weight gradient). Operations
and bytes the algorithm needs, from shapes: only rows that exist count;
the padding of each expert's run to whole tiles is the kernels' own cost."""

TRACE_NAMES = ("moe_gmm", "moe_gmm_t", "moe_tgmm")


def expected_rows(sizes: dict, tokens: float) -> float:
    """Rows a layer's held experts are expected to see from ``tokens``
    tokens: each token picks ``num_experts_per_tok`` of the router's
    ``router_experts``, of which ``n_routed_experts`` are held here."""
    return tokens * sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes.get("router_experts", sizes["n_routed_experts"])


def count(rows: float, k: int, n: int, experts: int, dtype_bytes: int = 2,
          variant: str = "moe_gmm") -> dict:
    """One call over ``rows`` existing rows, ``[rows, k] x [experts, k, n]``:
    every row is multiplied once; rows in and out and each expert's matrix
    move once. ``moe_tgmm`` reads both row operands and reads and writes
    the float32 accumulator."""
    if variant == "moe_tgmm":
        moved = rows * (k + n) * dtype_bytes + 2 * experts * k * n * 4
    else:
        moved = (rows * (k + n) + experts * k * n) * dtype_bytes
    return {"flops": 2 * rows * k * n, "bytes": moved}
