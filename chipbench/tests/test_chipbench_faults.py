"""A run with the timed path broken underneath must come out not
correct, for each fault a served one-chip cell can have (it has no
exchange between chips to leave out)."""
import dataclasses

import pytest

from chipbench import harness
from chipbench.tests.conftest import TINY


def _stale(orig):
    """A step that returns its state unchanged: every batch gets the
    first batch's answers."""
    def search(self, queries, params):
        res = orig(self, queries, params)
        if not hasattr(self, "_first_result"):
            self._first_result = res
        return self._first_result
    return search


def _half_batch(orig):
    """Half of the batch left out: the second half's rows get the first
    half's answers."""
    def search(self, queries, params):
        res = orig(self, queries, params)
        h = res.ids.shape[0] // 2
        return dataclasses.replace(
            res, ids=res.ids.at[h:].set(res.ids[:res.ids.shape[0] - h]),
            dists=res.dists.at[h:].set(res.dists[:res.dists.shape[0] - h]))
    return search


def _altered(orig):
    """An answer altered where it is produced: one id of each batch's
    first row is replaced by another real id."""
    def search(self, queries, params):
        res = orig(self, queries, params)
        n = self.index.n
        return dataclasses.replace(
            res, ids=res.ids.at[0, 0].set((res.ids[0, 0] + n // 2) % n))
    return search


def _run(seed=11):
    return harness.execute("sift1m.closed", seed, 0.5, False,
                           require_chip=False, config_override=TINY,
                           say=lambda s: None)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "recall_at_10", "build_s",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_stale, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.anns.backends.ivf import IvfBackend
    monkeypatch.setattr(IvfBackend, "search", fault(IvfBackend.search))
    res = _run()
    assert not res["correct"], res["checks"]


def test_raised_nprobe_is_not_correct():
    """A build whose small cells lift the probed cells above the stated
    nprobe does other work than the configuration states."""
    over = harness._merge(TINY, {"index": {"nlist": 512, "nprobe": 1}})
    res = harness.execute("sift1m.closed", 11, 0.5, False,
                          require_chip=False, config_override=over,
                          say=lambda s: None)
    assert res["checks"]["probe_floor"]["value"] > 1
    assert not res["correct"], res["checks"]
