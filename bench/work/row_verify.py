"""Work of verifying candidate rows: each (query, candidate) pair reads
the candidate's raw row, ``length`` float32 values, and takes a
subtraction, a multiplication and an addition per value.  Only rows that
are real candidates count, whatever the program pads."""


def bytes_moved(rows_verified: int, length: int) -> float:
    return 4.0 * rows_verified * length


def flops(rows_verified: int, length: int) -> float:
    return 3.0 * rows_verified * length
