"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window, before the reference runs."""


def read(trace: dict, run: dict):
    peak = run.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
