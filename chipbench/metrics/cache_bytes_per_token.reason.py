"""Bytes of cache a running sequence holds (its recurrent state and window rings whatever its length, one layer's K/V a row) over its resident rows, both summed over the window's decode steps."""
from chipbench import families


def read(run):
    rows = run.get("decode_context_tokens")
    if not rows or not run.get("occupancy_sum"):
        return None
    family = families.of(run["cfg"])
    held = run["occupancy_sum"] * family.slot_state_bytes(run["cfg"]) \
        + rows * family.kv_bytes_per_row(run["cfg"])
    return held / rows
