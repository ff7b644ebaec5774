"""Index scores of the window's prefill chunks (16 heads x 64 x 2 operations a query and row scored, the index keys of the rows a chunk holds read once) at the chip's peaks over the chunk executable's device time under indexer (projections, scores and selection), both sides a call."""
from chipbench import families, phases


def read(run):
    pairs = run.get("counters", {}).get("dsa_rows_scored_prefill")
    if not pairs:
        return None
    family = families.of(run["cfg"])
    chunk = run["cfg"]["runner"]["server"]["prefill_chunk"]
    return family.roofline_share(
        run, phases.PREFILL_CHUNK, ("indexer",),
        *family.indexer_cost(run["cfg"], chunk, pairs), "indexer")
