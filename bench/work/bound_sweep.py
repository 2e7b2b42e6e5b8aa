"""Work of one lower-bound sweep: read the whole symbolic mirror once
and write one float32 bound per (query, row)."""


def bytes_moved(rep_bytes: int, queries: int, rows: int) -> float:
    return float(rep_bytes) + 4.0 * queries * rows
