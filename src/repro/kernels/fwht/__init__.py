from .ops import fwht, randomized_fwht
from .ref import fwht_ref, fwht_mxu_ref, hadamard_matrix, lane_factors

__all__ = ["fwht", "randomized_fwht", "fwht_ref", "fwht_mxu_ref",
           "hadamard_matrix", "lane_factors"]
