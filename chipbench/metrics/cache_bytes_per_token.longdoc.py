"""Bytes of the latent-row and index-key pools (the program's gauge) over the rows the pools back: what one resident token holds over all layers."""


def read(run):
    held = run.get("gauges", {}).get("latent_cache_bytes")
    server = run["cfg"]["runner"]["server"]
    if not held:
        return None
    return held / ((server["n_pages"] + 1) * server["block_size"])
