"""Scheduled over total (R tile, S tile) pairs of the window's batches,
counted by ``MegastepEngine.tile_counts`` after the window."""


def read(run):
    t = run.tiles
    if not t or not t["total"]:
        return None
    return t["visited"] / t["total"]
