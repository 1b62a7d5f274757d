"""Block-shape arithmetic shared by the Pallas kernels.

A TPU vector register holds an (8, 128) tile of 32-bit values (16 sublanes
for bfloat16).  A kernel block's last dim must be a multiple of the lane
count and its second-to-last a multiple of the sublane count — or equal
the whole array dim.
"""
LANES = 128      # records per block are a multiple of the lane width
SUBLANES = 8     # field and tree blocks are a multiple of the sublane count
STAT_ROWS = 16   # bfloat16 stats rows pad to the bfloat16 sublane tile


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m
