"""Matched rows that a cut handed back to be prefilled again (serving.prefix_rows_cut_total) over the rows matched (those served from the cache and those cut); 0 where nothing matched."""


def read(run):
    c = run.get("counters", {})
    if "prefix_rows_cut" not in c or "hit_tokens" not in run:
        return None
    matched = run["hit_tokens"] + c["prefix_rows_cut"]
    # what the window did to the two caches, beside the share (trace_notes)
    run.setdefault("notes", {})["prefix_cache"] = {
        k: c.get(k, 0) for k in (
            "prefix_matches", "prefix_hits_cut", "prefix_rows_cut",
            "prefix_evictions", "state_snapshots_taken",
            "state_snapshots_restored", "state_snapshots_reclaimed")}
    return 100.0 * c["prefix_rows_cut"] / matched if matched else 0.0
