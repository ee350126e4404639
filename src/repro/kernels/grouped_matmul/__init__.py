from .kernel import gmm, tgmm
from .ops import grouped_matmul

__all__ = ["gmm", "grouped_matmul", "tgmm"]
