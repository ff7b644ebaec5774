"""Bytes of the K, V and index-key pools (the program's two gauges, the pools as allocated) over the rows they back: what one resident token holds over all layers; 13,824 with the index key held 128 wide, where the mathematics requires 13,056."""


def read(run):
    g = run.get("gauges", {})
    server = run["cfg"]["runner"]["server"]
    if not g.get("kv_cache_bytes") or "index_key_cache_bytes" not in g:
        return None
    return (g["kv_cache_bytes"] + g["index_key_cache_bytes"]) \
        / ((server["n_pages"] + 1) * server["block_size"])
