"""Peak device memory of the run, in GB: `memory_stats()["peak_bytes_in_use"]`
after the window, on the fullest of the cell's chips."""


def read(record: dict):
    peak = record["memory_peak_bytes"]
    return peak / 1e9 if peak else None
