"""Unit tests for the SSS/SAS/CA-SAS/DAS partitioners (paper Sections 4, 5.2, 5.4)."""

import numpy as np
import pytest

from repro.core import schedule as S
from repro.core.asymmetric import AsymmetricMesh, DeviceClass


class TestStatic:
    def test_sss_equal(self):
        t = S.sss_partition(100, 4)
        assert t.sizes() == [25, 25, 25, 25]

    def test_sss_remainder(self):
        t = S.sss_partition(10, 3)
        assert sum(t.sizes()) == 10
        assert max(t.sizes()) - min(t.sizes()) <= 1

    def test_sas_ratio(self):
        # Paper Figure 8: ratio 3 -> fast cluster gets 3x the slow one.
        t = S.sas_partition(80, ratios=[3.0, 1.0])
        assert t.sizes() == [60, 20]

    def test_sas_workers(self):
        t = S.sas_partition(100, ratios=[1.0, 1.0], workers=[4, 1])
        assert t.sizes() == [80, 20]

    def test_ca_sas_tile_alignment(self):
        t = S.ca_sas_partition(1000, ratios=[5.0, 1.0], tiles=[152, 32])
        sizes = t.sizes()
        assert sum(sizes) == 1000
        assert sizes[0] % 152 == 0  # big cluster aligned to its m_c

    def test_ca_sas_starved_class_alone_goes_partial(self):
        # Regression: one starved class (tile > its share) must not strip
        # alignment from everyone — only the starved class takes a partial
        # panel; the big class keeps its m_c alignment.
        t = S.ca_sas_partition(1000, ratios=[20.0, 1.0], tiles=[152, 64])
        sizes = t.sizes()
        assert sum(sizes) == 1000
        assert sizes[0] % 152 == 0  # big stays aligned (was unaligned pre-fix)
        assert sizes[1] > 0  # little runs a partial panel + the residue

    def test_ca_sas_three_classes_starvation_localized(self):
        # Middle class starved; the other two keep their own alignment.
        t = S.ca_sas_partition(2048, ratios=[8.0, 0.2, 4.0], tiles=[128, 200, 64])
        sizes = t.sizes()
        assert sum(sizes) == 2048
        assert sizes[0] % 128 == 0
        assert sizes[2] % 64 == 0
        assert 0 < sizes[1] < 200

    def test_validate_rejects_bad_table(self):
        tb = S.ChunkTable(10, (S.Chunk(0, 0, 4), S.Chunk(1, 5, 5)))
        with pytest.raises(ValueError):
            tb.validate()


class TestDynamic:
    def test_das_covers_everything(self):
        r = S.das_schedule(1000, rates=[4.0, 1.0], strides=[152, 32])
        assert sum(r.sizes()) == 1000

    def test_das_balances_by_rate(self):
        r = S.das_schedule(10000, rates=[4.0, 1.0], strides=[100, 100])
        sizes = r.sizes()
        assert 3.0 < sizes[0] / max(sizes[1], 1) < 5.5

    def test_das_makespan_beats_sss(self):
        # The paper's core claim: dynamic beats the oblivious 50/50 split.
        rates, strides = [4.0, 1.0], [152, 32]
        dyn = S.das_schedule(2000, rates=rates, strides=strides)
        half = 1000 / rates[0], 1000 / rates[1]
        sss_makespan = max(half)
        assert dyn.makespan < sss_makespan * 0.6

    def test_das_deterministic(self):
        a = S.das_schedule(500, rates=[2.0, 1.0], strides=[50, 20])
        b = S.das_schedule(500, rates=[2.0, 1.0], strides=[50, 20])
        assert a.assignments == b.assignments

    def test_das_dead_pod_skipped(self):
        # Regression: a zero-rate class used to raise ZeroDivisionError;
        # now the dead pod simply never grabs work.
        r = S.das_schedule(1000, rates=[4.0, 0.0, 1.0], strides=[152, 32, 32])
        sizes = r.sizes()
        assert sum(sizes) == 1000
        assert sizes[1] == 0
        assert sizes[0] > sizes[2] > 0

    def test_das_all_dead_raises(self):
        with pytest.raises(ValueError, match="zero"):
            S.das_schedule(100, rates=[0.0, 0.0], strides=[8, 8])

    def test_das_zero_units_trivial(self):
        r = S.das_schedule(0, rates=[0.0, 0.0], strides=[8, 8])
        assert r.assignments == [] and r.makespan == 0.0


class TestDynamicScheduler:
    def test_converges_to_measured_ratio(self):
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1.0], tiles=[8, 8])
        for _ in range(20):
            t = d.table(256)
            s = t.sizes()
            # pod0 is 3x faster: time proportional to units/rate
            d.observe(s, [s[0] / 3.0 + 1e-9, s[1] / 1.0 + 1e-9])
        s = d.table(256).sizes()
        assert 2.0 < s[0] / max(s[1], 1) < 4.5

    def test_starvation_floor(self):
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1e-6], tiles=[1, 1])
        d.observe([10, 0], [0.1, 0.1])
        assert d.rates[1] >= 0.02 * d.rates[0] * 0.99

    def test_rebalance_counter(self):
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1.0], tiles=[1, 1])
        d.table(100)
        d.observe([50, 50], [0.1, 0.4])
        d.table(100)
        assert d.rebalances >= 1

    def test_hysteresis_holds_table_below_threshold(self):
        # Sub-threshold drift must NOT re-derive the partition: the table
        # object is reused verbatim and no rebalance is counted, even
        # though a fresh SAS split of the drifted rates would differ.
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1.0], tiles=[1, 1],
                               rebalance_threshold=0.05)
        t0 = d.table(100)
        assert t0.sizes() == [50, 50]
        d.rates = np.array([1.06, 1.0])  # fresh SAS would give [51, 49]
        assert not d.needs_rebalance()   # normalized drift ~2.9% < 5%
        t1 = d.table(100)
        assert t1 is t0
        assert d.rebalances == 0

    def test_hysteresis_releases_past_threshold(self):
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1.0], tiles=[1, 1],
                               rebalance_threshold=0.05)
        d.table(100)
        d.rates = np.array([1.3, 1.0])
        assert d.needs_rebalance()       # drift ~13% > 5%
        t1 = d.table(100)
        assert t1.sizes() == [57, 43]
        assert d.rebalances == 1
        # The new rates become the hysteresis anchor.
        assert not d.needs_rebalance()

    def test_hysteresis_different_n_units_rederives_without_counting(self):
        # A different unit count always re-derives (the cached sizes can't
        # cover it) but is not a "rebalance" — the split didn't drift.
        d = S.DynamicScheduler(2, init_ratios=[2.0, 1.0], tiles=[1, 1])
        a = d.table(90)
        b = d.table(60)
        assert sum(a.sizes()) == 90 and sum(b.sizes()) == 60
        assert d.rebalances == 0

    def test_drift_before_any_table_is_infinite(self):
        d = S.DynamicScheduler(2)
        assert d.drift() == float("inf") and d.needs_rebalance()

    def test_observe_rejects_wrong_arity(self):
        # Regression: a caller passing per-pod lists to a per-class
        # scheduler used to corrupt the rate vector silently (numpy
        # broadcast); now it is a hard error naming both lengths.
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1.0], tiles=[1, 1])
        with pytest.raises(ValueError, match="expects 2 per-class"):
            d.observe([10, 10, 10], [0.1, 0.1, 0.1])
        with pytest.raises(ValueError):
            d.observe([10, 10], [0.1, 0.1, 0.1])
        with pytest.raises(ValueError):
            d.observe([10], [0.1, 0.1])

    def test_drift_floored_class_does_not_thrash(self):
        # Regression: drift used to normalize each class's share delta by
        # its OWN reference share, so a class pinned at the 2% starvation
        # floor turned ±50% jitter in its tiny rate into ~50% "drift" and
        # re-partitioned every step.  Normalizing by the max reference
        # share keeps sub-threshold absolute movement sub-threshold.
        d = S.DynamicScheduler(2, init_ratios=[1.0, 1e-6], tiles=[1, 1],
                               rebalance_threshold=0.05)
        d.observe([10, 0], [0.1, 0.1])        # floors class 1 at 2%
        d.table(100)
        floor_rate = d.rates[1]
        d.rates = np.array([d.rates[0], floor_rate * 1.5])  # 50% jitter
        assert d.drift() < 0.05
        assert not d.needs_rebalance()
        # A genuine shift in the class *ratio* still releases: the small
        # class growing to 20% of the big one moves the split ~15%.
        d.rates = np.array([d.rates[0], d.rates[0] * 0.2])
        assert d.needs_rebalance()

    def test_balanced_ratio(self):
        assert S.balanced_ratio([9.6, 2.4]) == pytest.approx(4.0)

    def test_balanced_ratio_order_and_arity(self):
        # Regression: used to hardcode rates[0]/rates[1] — crashed on one
        # class and silently inverted on unsorted rates.
        assert S.balanced_ratio([2.4, 9.6]) == pytest.approx(4.0)
        assert S.balanced_ratio([5.0]) == 1.0
        assert S.balanced_ratio([1.0, 4.0, 2.0]) == pytest.approx(4.0)
        with pytest.raises(ValueError):
            S.balanced_ratio([])
        with pytest.raises(ValueError):
            S.balanced_ratio([1.0, 0.0])


class TestSlotBudgets:
    def test_slot_spill_prefers_aggregate_throughput(self):
        # Regression: spill used to rank by rel_throughput alone, so a
        # one-chip pod with high per-chip throughput absorbed spill before
        # a many-chip pod with far more aggregate capacity.  chips 1/2/8 at rel 1.0/0.9/0.5 → aggregate
        # 1.0/1.8/4.0 → spill lands on pod 2 first.
        classes = [
            DeviceClass(name="solo", chips_per_pod=1, rel_throughput=1.0),
            DeviceClass(name="duo", chips_per_pod=2, rel_throughput=0.9),
            DeviceClass(name="octo", chips_per_pod=8, rel_throughput=0.5),
        ]
        asym = AsymmetricMesh(classes, strategy="ca-das", batch_tile=1)
        budgets = asym.slot_budgets(4, 10)
        assert sum(budgets) == 10
        assert budgets[2] == 4  # largest aggregate pod saturates first
        assert budgets == [2, 4, 4]
