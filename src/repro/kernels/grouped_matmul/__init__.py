from .kernel import gmm, tgmm
from .ops import grouped_matmul, row_tiling
from .rows import held_lists, rows_gather, rows_pack, rows_sum

__all__ = ["gmm", "grouped_matmul", "held_lists", "row_tiling", "rows_gather", "rows_pack",
           "rows_sum", "tgmm"]
