"""Bytes of both page groups' pages that running sequences hold, as allocated, over their resident rows, a decode step (the program's histogram): 16,384 where no window page went back."""


def read(run):
    c = run.get("counters", {})
    n = c.get("kv_bytes_per_resident_row.count")
    return c["kv_bytes_per_resident_row.sum"] / n if n else None
