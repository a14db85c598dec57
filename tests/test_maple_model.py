"""Accelerator event-model invariants + paper-direction checks (Layer A)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytestmark = pytest.mark.tier1


from repro.core import (analyze_spgemm, compare, simulate, sparsity,
                        matraptor_baseline, matraptor_maple,
                        extensor_baseline, extensor_maple)
from repro.core.csr import CSR
from repro.core.maple import baseline_pe_cycles, maple_pe_cycles


def _clone(ab="sc", scale=0.02, seed=0):
    return sparsity.generate(sparsity.TABLE_I[ab], scale=scale, seed=seed)


def test_stats_exact_small():
    d = np.array([[1, 0, 2], [0, 3, 0], [4, 0, 0]], np.float32)
    a = CSR.from_dense(d)
    st_ = analyze_spgemm(a)
    # row0 refs B rows 0,2 (len 2, 1); row1 refs row1 (len 1); row2 row0 (2)
    assert st_.partial_products == 2 + 1 + 1 + 2
    c = d @ d
    assert st_.nnz_c == int((c != 0).sum())


def test_estimated_output_close_to_exact():
    a = _clone("cc", 0.05)
    exact = analyze_spgemm(a, exact_output=True)
    est = analyze_spgemm(a, exact_output=False)
    assert est.partial_products == exact.partial_products
    assert 0.5 < est.nnz_c / exact.nnz_c < 2.0


@settings(max_examples=10, deadline=None)
@given(macs=st.sampled_from([2, 4, 8, 16]), seed=st.integers(0, 99))
def test_maple_cycles_bounds(macs, seed):
    """m MACs speed up by at most m and never slow down (per PE)."""
    a = _clone("wv", 0.1, seed)
    st_ = analyze_spgemm(a)
    base = baseline_pe_cycles(st_, n_pes=1)
    mpl = maple_pe_cycles(st_, macs_per_pe=macs, n_pes=1)
    assert mpl <= base + 1e-9
    assert mpl >= base / macs - 1e-9


def test_iso_mac_counts():
    assert (matraptor_baseline().total_macs
            == matraptor_maple().total_macs == 8)
    assert (extensor_baseline().total_macs
            == extensor_maple().total_macs == 128)


@pytest.mark.parametrize("family", ["matraptor", "extensor"])
def test_paper_directions(family):
    """Maple must win on energy and area for every Table-I clone family."""
    for ab in ["wg", "sc", "fb"]:
        st_ = analyze_spgemm(_clone(ab, 0.03))
        cmp_ = compare(family, st_)
        assert cmp_.energy_benefit_pct > 0, (family, ab)
        assert cmp_.area_ratio > 1.0, (family, ab)
        assert cmp_.onchip_energy_benefit_pct > 0, (family, ab)


def test_maple_moves_less_l0_l1():
    st_ = analyze_spgemm(_clone("sc", 0.03))
    rb = simulate(matraptor_baseline(), st_)
    rm = simulate(matraptor_maple(), st_)
    # one memory level: Maple-Matraptor has zero L1 traffic (paper §IV.B.1)
    assert rm.events["l1_access"] == 0
    assert rb.events["l1_access"] > 0
    # no merge / intersection / C-D work in the Maple PE
    assert rm.events["merge_op"] == 0
    assert rm.events["cd_op"] == 0


def test_extensor_pob_elimination():
    st_ = analyze_spgemm(_clone("fb", 0.2))
    rb = simulate(extensor_baseline(), st_)
    rm = simulate(extensor_maple(), st_)
    # baseline moves partial sums through L1 (POB); Maple-Extensor's L1
    # traffic is the LLB stream only — strictly less
    assert rm.events["l1_access"] < rb.events["l1_access"]
    assert rm.events["intersect_op"] == 0 < rb.events["intersect_op"]


def test_energy_table_ordering():
    from repro.core.energy import ENERGY_PER_EVENT as E
    # Fig. 3 ordering: arithmetic < L0 ≤ PE↔PE < L1 < L2
    assert E["merge_op"] < E["l0_access"]
    assert E["l0_access"] <= E["pe_transfer"] < E["l1_access"] < E["l2_access"]
