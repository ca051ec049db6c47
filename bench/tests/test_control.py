"""The control and the planted faults must come out not correct.

The control is the reference in the program's place, stopped one changing
round short of its fixpoint: an approximate answer where the configuration
states an exact one.  The faults break the timed path underneath an
otherwise normal run (the harness's look for a chip skipped): an answer
altered where it is produced, a fixpoint that returns its state unchanged,
and half of a microbatch's instances answered with another's solution.
"""
import numpy as np
import pytest

from test_rehearsal import cells, tiny_run, _cache  # noqa: F401


@pytest.mark.parametrize("workload", cells())
def test_control_is_not_correct(workload):
    res = tiny_run(workload, control=True)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def _flip_one_survivor(monkeypatch):
    from repro.core import pruning

    orig = pruning.prune_triples

    def altered(soi, chi, g):
        mask, stats = orig(soi, chi, g)
        mask = mask.copy()
        mask[0] = ~mask[0]
        return mask, stats

    monkeypatch.setattr(pruning, "prune_triples", altered)


def _state_unchanged(monkeypatch):
    from repro.core import bitops
    from repro.engine import plan

    orig = plan.CompiledPlan.execute

    def unchanged(self, bindings):
        chi, sweeps = orig(self, bindings)
        init = np.asarray(self.operands.init)[:, : self.n_nodes]
        if self._packed_chi:
            init = bitops.unpack_np(bitops.pack_np(init), self.n_nodes)
        rows = self.const_rows(bindings)
        init = init.copy()
        for j, r in enumerate(self._scatter_ids):
            init[r] &= rows[j]
        return init, sweeps

    monkeypatch.setattr(plan.CompiledPlan, "execute", unchanged)


def _half_batch(monkeypatch):
    from repro.engine import plan

    orig = plan.CompiledPlan.execute

    def half(self, bindings):
        chi, sweeps = orig(self, bindings)
        chi = chi.copy()
        first = chi[self.layout.chi_slice(0)]
        for i in range(len(bindings) // 2, len(bindings)):
            if bindings[i] != bindings[0]:
                chi[self.layout.chi_slice(i)] = first
        return chi, sweeps

    monkeypatch.setattr(plan.CompiledPlan, "execute", half)


@pytest.mark.parametrize("fault", [_flip_one_survivor, _state_unchanged,
                                   _half_batch])
@pytest.mark.parametrize("workload", cells())
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    # an open loop offered far above capacity, so microbatches fill
    res = tiny_run(workload, rate=400.0)
    assert not res["correct"], (fault.__name__, res["checks"])
