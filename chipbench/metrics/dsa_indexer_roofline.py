"""Index scores of the window's prefill chunks (32 heads x 128 x 2 operations a query and row scored, the index keys of the rows a chunk holds read once) at the chip's peaks over the chunk executable's device time under indexer: projections, scores and selection."""
from chipbench import costs, families, phases


def read(run):
    a = phases.of_run(run)
    pairs = run.get("counters", {}).get("dsa_rows_scored_prefill")
    if not a or not run.get("peaks") or not pairs:
        return None
    seconds = sum(v for k, v in a["by_scope"].get(phases.PREFILL_CHUNK,
                                                  {}).items()
                  if "indexer" in k.split("/"))
    if not seconds:
        return None
    chunk = run["cfg"]["runner"]["server"]["prefill_chunk"]
    flops, nbytes = families.of(run["cfg"]).indexer_cost(
        run["cfg"], chunk, pairs)
    least, bound = costs.roofline_seconds(flops, nbytes / chunk,
                                          run["peaks"])
    run.setdefault("notes", {})["indexer"] = {
        "bound": bound, "seconds": seconds, "least_s": least}
    return 100.0 * least / seconds
