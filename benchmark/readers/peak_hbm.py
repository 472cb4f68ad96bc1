"""Peak device memory after the window, on the fullest chip, in GB (1e9)."""


def read(obs, params):
    peak = obs.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
