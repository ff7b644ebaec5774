"""Prefix matches cut back for want of a state snapshot at their end (the match ends between two boundaries, or the boundary's snapshot was reclaimed), over the matches found (the program's counters); 0 where nothing matched."""


def read(run):
    c = run.get("counters", {})
    if "prefix_matches" not in c:
        return None
    found = c["prefix_matches"]
    return 100.0 * c.get("prefix_hits_cut", 0) / found if found else 0.0
