"""Host syncs of the lossless codec (``lossless_batch.STATS.host_syncs``)
per chunk written in the window."""


def read(r):
    chunks = r.counters.get("chunks", 0)
    if not chunks:
        return None
    return r.counters["codec_host_syncs"] / chunks
