"""Rows whose K and V the decode steps' attention read after selection over the rows their indexer scored (the program's counters, counted on the device)."""


def read(run):
    c = run.get("counters", {})
    scored = c.get("dsa_rows_scored_decode")
    if not scored:
        return None
    return 100.0 * c.get("dsa_rows_selected_decode", 0) / scored
