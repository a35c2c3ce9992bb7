"""The collector's pauses inside the window are counted for the side
file."""
import gc

from bench import harness


def test_gc_pauses_counted_by_generation():
    with harness.GcPauses() as pauses:
        gc.collect()
        gc.collect(0)
    s = pauses.summary()
    assert s["collections"]["2"] >= 1 and s["collections"]["0"] >= 1
    assert s["longest_s"] > 0.0 and s["longest_generation"] in (0, 2)
    assert pauses._on not in gc.callbacks
