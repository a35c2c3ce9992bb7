"""``correct``: sound runs pass, and the control and each fault a cell
can have come out not correct. CPU runs of the real cells cut to a test
size (``bench_small``); the harness's look for a chip is skipped and the
rest of a run is driven as on the chip."""
import numpy as np
import pytest

from bench import control, correct
from bench_small import run_small, small

SEED = 2**31 + 101


@pytest.mark.parametrize("cell,arrivals", [
    ("forest10.join", None), ("sift1m.bulk", None),
    ("sift1m.online", None), ("sift1m.online", "bursty")],
    ids=["forest10.join", "sift1m.bulk", "sift1m.online",
         "sift1m.online-bursty"])
def test_sound_run_is_correct(cell, arrivals, tmp_path, monkeypatch):
    mix = {"arrivals": arrivals, "burst": 8} if arrivals else None
    res = run_small(cell, SEED, tmp_path, monkeypatch=monkeypatch, mix=mix)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("cell", ["forest10.join", "sift1m.online"])
def test_control_is_not_correct(cell, monkeypatch):
    spec, cfg, mix = small(cell)
    for seed in (1, 2, 3):
        r = control.readings(cell, seed, 2.0, require_tpu=False, cfg=cfg,
                             mix=mix)
        assert not r["correct"], r["check"]


def _alter_answers(d, ids):
    """The nearest id of every 8th row replaced by another row's: the
    check reads a sample, so the fault has to reach some of it."""
    ids = ids.copy()
    ids[::8, 0] = (ids[::8, 0] + 1) % 1000
    return d, ids


def _drop_half(d, ids):
    n = d.shape[0]
    if n < 2:
        return d, ids
    h = n // 2
    d, ids = d.copy(), ids.copy()
    d[h:], ids[h:] = d[:n - h], ids[:n - h]
    return d, ids


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell", ["forest10.join", "sift1m.online"])
def test_fault_in_timed_path_is_not_correct(cell, fault, tmp_path,
                                            monkeypatch):
    from repro.core.megastep import MegastepEngine
    real = MegastepEngine.finalize

    def broken(self, handle, **kw):
        d, ids = real(self, handle, **kw)
        return fault(d, ids)

    monkeypatch.setattr(MegastepEngine, "finalize", broken)
    res = run_small(cell, SEED + 1, tmp_path, monkeypatch=monkeypatch)
    assert not res["correct"], res["check"]


def test_gaps_catch_a_swapped_id_with_its_distance_right():
    rng = np.random.default_rng(0)
    s = rng.normal(size=(50, 3))
    q = rng.normal(size=(4, 3))
    full = np.sqrt(((q[:, None] - s[None]) ** 2).sum(-1))
    ids = np.argsort(full, axis=1)[:, :5]
    dref = np.take_along_axis(full, ids, axis=1)

    def exact(q_, s_, i_):
        return np.sqrt(((q_[:, None] - s_[i_]) ** 2).sum(-1))

    ok = correct.gaps(q, s, dref, ids, dref, exact)
    assert ok["bad_ids"] == 0 and ok["rank_gap"] == 0.0
    bad = ids.copy()
    bad[1, 2] = np.argsort(full[1])[20]
    g = correct.gaps(q, s, dref, bad, dref, exact)
    assert g["rank_gap"] > 0.01
    dup = ids.copy()
    dup[2, 1] = dup[2, 0]
    assert correct.gaps(q, s, dref, dup, dref, exact)["bad_ids"] == 1


def test_verdict_needs_every_limit():
    ok, table = correct.verdict({"a": 1.0}, {"a": 2.0})
    assert ok and table == {"a": {"value": 1.0, "limit": 2.0}}
    assert not correct.verdict({"a": 3.0}, {"a": 2.0})[0]
    with pytest.raises(KeyError):
        correct.verdict({"b": 0.0}, {"a": 2.0})
