"""Memory (the page ledger): KV bytes the cache reserves (every slot at
max_seq) over the most the rows ever held (`pages_resident_peak` pages
of `page_size` positions); the bytes per token cancel."""


def read(run):
    if not hasattr(run.family, "kv_bytes_per_token"):
        return None
    c = run.counters
    used = c["pages_resident_peak"] * c["page_size"]
    if used <= 0:
        return None
    return c["batch"] * c["max_seq"] / used
