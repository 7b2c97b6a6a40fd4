"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, GB (10^9 bytes)."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
