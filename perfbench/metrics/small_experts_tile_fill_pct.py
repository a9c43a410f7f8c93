"""How full the row tiles are that the grouped products visit, in per cent:
held pairs a step over (``step_window.moe_tile_visits`` x the row tile of
``flops_mixed.ROW_TILE``), over the ledger windows inside the measured
window. Where a held expert's group is about one tile (512 tokens an expert)
a group seldom starts on a tile's edge, so it overlaps two tiles and each is
half its own; groups of many tiles read near 100. None where the program
writes no such field."""
from perfbench import flops_mixed, lm_mixed_trace


def read(run):
    seen = lm_mixed_trace.counters(run)
    if seen is None or not seen.get("tile_visits"):
        return None
    return 100.0 * seen["moe_pairs"] / (seen["tile_visits"] * flops_mixed.ROW_TILE)
