"""The held experts' grouped products in the two serving programs
(``ops/grouped_matmul.py:moe_gmm`` under the names ``moe_gmm_decode`` and
``moe_gmm_prefill``): rows sorted by expert times the expert's matrix.

What the algorithm needs of one call ``[rows, k] x [experts hit, k, n]``:
every row multiplied once; the rows in and out and the matrix of each
expert that has a row moved once. The padding of each expert's run to whole
tiles is the kernel's own cost. Rows and experts hit are the harness's
EXPECTATION from the cell's traffic, never a number the program reports."""

TRACE_NAMES = ("moe_gmm_decode", "moe_gmm_prefill")


def expected_rows(sizes: dict, tokens: float) -> float:
    """Rows a layer's held experts are expected to see from ``tokens``
    tokens: each picks ``num_experts_per_tok`` of ``router_experts``, of
    which ``num_experts`` are held here."""
    return tokens * sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["router_experts"]


def expected_experts_hit(sizes: dict, tokens: float) -> float:
    """Held experts expected to get at least one row from ``tokens``
    tokens: a token picks ``num_experts_per_tok`` DISTINCT experts, so it
    misses a given one with probability ``1 - k / router_experts``."""
    miss = 1.0 - sizes["num_experts_per_tok"] / sizes["router_experts"]
    return sizes["num_experts"] * (1.0 - miss ** tokens)


def count(rows: float, experts_hit: float, k: int, n: int,
          dtype_bytes: int = 2) -> dict:
    return {"flops": 2 * rows * k * n,
            "bytes": (rows * (k + n) + experts_hit * k * n) * dtype_bytes}
