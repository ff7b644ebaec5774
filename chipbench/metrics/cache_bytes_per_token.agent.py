"""Bytes of the K and V pools, the snapshot store and the slots' state (the program's three gauges, all as allocated) over the rows the pools back: what one cached token holds over all layers; 4,096 B of K and V, 448 B of its share of a snapshot every 128 rows, 5 B of the slots."""


def read(run):
    g = run.get("gauges", {})
    server = run["cfg"]["runner"]["server"]
    if not g.get("kv_cache_bytes") or "state_snapshot_bytes" not in g:
        return None
    return (g["kv_cache_bytes"] + g["state_snapshot_bytes"]
            + g.get("recurrent_state_bytes", 0)) \
        / ((server["n_pages"] + 1) * server["block_size"])
