"""Bytes the store's backend fetched (``BackendStats.bytes_fetched``) per
unit of the window, in MB (10^6 bytes)."""


def read(r):
    units = r.counters.get("units", 0)
    if not units:
        return None
    return r.counters["fetched_bytes"] / units / 1e6
