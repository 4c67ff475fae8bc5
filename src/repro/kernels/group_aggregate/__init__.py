from repro.kernels.group_aggregate.ops import (DENSE_MAX_GROUPS,
                                               finalize_grouped,
                                               group_sum_count,
                                               group_sum_count_batched,
                                               group_sum_count_packed,
                                               rle_group_accumulate,
                                               rle_group_accumulate_batched)

__all__ = [
    "DENSE_MAX_GROUPS",
    "finalize_grouped",
    "group_sum_count",
    "group_sum_count_batched",
    "group_sum_count_packed",
    "rle_group_accumulate",
    "rle_group_accumulate_batched",
]
