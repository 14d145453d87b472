"""AGE (micro-architecture generator) unit tests — paper §4 semantics."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import age, pathfinder, techlib
from repro.core.age import Budgets
from repro.core.sweeprunner import SweepSpec


@pytest.fixture(scope="module")
def tech():
    return techlib.make_tech_config("N7", "HBM2E", "IB-NDR-X8")


def test_generate_produces_positive_parameters(tech):
    arch = age.generate(tech, Budgets.default())
    assert float(arch.compute_throughput) > 0
    assert float(arch.dram_bw) > 0
    assert float(arch.dram_capacity) > 0
    assert all(float(c) > 0 for c in arch.mem_capacity)
    assert all(float(b) > 0 for b in arch.mem_bw)
    assert float(arch.net_inter_bw) > 0
    assert float(arch.net_intra_bw) > 0


def test_more_core_area_more_throughput(tech):
    lo = Budgets.default()
    hi = dataclasses.replace(lo, area_frac={**lo.area_frac, "core": 0.55},
                             power_frac={**lo.power_frac, "core": 0.75})
    a_lo = age.generate(tech, lo)
    a_hi = age.generate(tech, hi)
    assert float(a_hi.compute_throughput) > float(a_lo.compute_throughput)


def test_power_budget_limits_throughput(tech):
    """Halving power while keeping area fixed must not increase throughput
    (V/f scaling, paper §4.4.1)."""
    b = Budgets.default()
    starved = dataclasses.replace(b, power_w=60.0)
    a_full = age.generate(tech, b)
    a_starved = age.generate(tech, starved)
    assert float(a_starved.compute_throughput) \
        <= float(a_full.compute_throughput)
    # frequency must actually have been scaled down
    assert float(a_starved.core_frequency) < float(a_full.core_frequency)


def test_eq4_dram_devices_limited_by_each_term(tech):
    b = Budgets.default()
    # starve controller area: DRAM capacity must drop
    starved = dataclasses.replace(
        b, area_frac={**b.area_frac, "dram": 0.002})
    assert float(age.generate(tech, starved).dram_capacity) \
        < float(age.generate(tech, b).dram_capacity)
    # starve perimeter: capacity must drop too
    starved_p = dataclasses.replace(
        b, perim_frac={**b.perim_frac, "dram": 0.02})
    assert float(age.generate(tech, starved_p).dram_capacity) \
        < float(age.generate(tech, b).dram_capacity)


def test_logic_scaling_increases_mcu_count():
    """N12 -> N5: 1.8x area scaling per node => more MCUs in the same area."""
    b = Budgets.default()
    t12 = techlib.make_tech_config("N12", "HBM2E", "IB-NDR-X8")
    t5 = techlib.make_tech_config("N5", "HBM2E", "IB-NDR-X8")
    n12 = float(age.generate(t12, b).n_mcu)
    n5 = float(age.generate(t5, b).n_mcu)
    assert n5 > 2.0 * n12


def test_hbm_generation_increases_bandwidth():
    b = Budgets.default()
    bws = []
    for gen in techlib.HBM_GENERATIONS:
        t = techlib.make_tech_config("N7", gen, "IB-NDR-X8")
        bws.append(float(age.generate(t, b).dram_bw))
    assert bws == sorted(bws)
    assert bws[-1] > bws[0]


def test_differentiable_path(tech):
    """The smooth AGE must yield finite nonzero grads w.r.t. budgets."""
    like = Budgets.default()

    def f(w):
        arch = age.generate(tech, Budgets.from_vector(w, like),
                            discrete=False)
        return (arch.compute_throughput / 1e12
                + arch.dram_bw / 1e12 + arch.mem_bw[2] / 1e13)

    g = jax.grad(f)(like.as_vector())
    assert jnp.all(jnp.isfinite(g))
    assert float(jnp.linalg.norm(g)) > 0


def test_budget_vector_roundtrip():
    b = Budgets.default()
    v = b.as_vector()
    b2 = age.Budgets.from_vector(v, b)
    assert jnp.allclose(b2.as_vector(), v)


def test_tpu_v5e_fixed_entry():
    arch = age.tpu_v5e_microarch()
    assert abs(float(arch.compute_throughput) / (197e12 * 0.85) - 1) < 1e-6
    assert float(arch.dram_bw) == pytest.approx(819e9)
    assert float(arch.net_inter_bw) == pytest.approx(50e9)


# ---------------------------------------------------------------- batched AGE
# the Mistral sweep grid's technologies (the query grid's N5/HBM3/IB-NDR-X8
# among them) over fixed budget scales drawn like the sweep traffic's
GRID = [techlib.make_tech_config(lg, hbm, net)
        for lg, hbm, net in itertools.product(
            ("N7", "N5", "N3"), ("HBM3", "HBM4"), ("IB-NDR-X8", "IB-XDR-X8"))]
SCALES = np.round(np.random.default_rng(14).uniform(0.7, 1.3, 500), 5)
FLOORED = ("n_mcu", "dram_capacity", "net_intra_links", "net_inter_links")


_SPEC = SweepSpec(arches=("qwen1.5-0.5b",), mesh_shapes=((2, 2),),
                  scenario="train")


def _budgets(scale):
    return _SPEC.budgets(float(scale))


@pytest.fixture(scope="module")
def grid_rows():
    """All 12 x 500 rows in ONE batched call (6,000 rows padded to
    8,192, technologies mixed), with the counters it moved."""
    before = age.age_stats()
    rows = age.generate_rows([t for t in GRID for _ in SCALES],
                             [_budgets(s) for _ in GRID for s in SCALES])
    after = age.age_stats()
    return rows, {k: after[k] - before[k] for k in after}


def _assert_same_row(got, want, what):
    for f in FLOORED:               # the discrete floors: exactly equal
        assert float(getattr(got, f)) == float(getattr(want, f)), (what, f)
    # bank counts x bank bytes, exactly equal too
    assert tuple(map(float, got.mem_capacity)) \
        == tuple(map(float, want.mem_capacity)), what
    g, w = pathfinder.pack_hw(got), pathfinder.pack_hw(want)
    for i, f in enumerate(pathfinder.HW_FIELDS):
        assert abs(g[i] - w[i]) <= 1e-6 * abs(w[i]), (what, f, g[i], w[i])


@pytest.mark.parametrize("ti", range(len(GRID)),
                         ids=[t.name.replace("/", "-") for t in GRID])
def test_generate_rows_matches_eager(grid_rows, ti):
    """Every row of the batched call equals eager `generate` of its own
    technology and scale: identical floors, every packed column within
    1e-6, so the padding rows never land in a result."""
    rows, _ = grid_rows
    tech = GRID[ti]
    for si, scale in enumerate(SCALES):
        got = rows[ti * len(SCALES) + si]
        assert got.tech is tech
        _assert_same_row(got, age.generate(tech, _budgets(scale)),
                         (tech.name, scale))


def test_generate_rows_counts_and_padding(grid_rows):
    rows, delta = grid_rows
    assert len(rows) == len(GRID) * len(SCALES)
    assert delta == {"calls": 1, "rows": 6000, "padded_rows": 8192,
                     "eager_rows": 0}
    before = age.age_stats()["eager_rows"]
    age.generate(GRID[0], _budgets(1.0))
    assert age.age_stats()["eager_rows"] == before + 1
    assert age.generate_rows([], []) == []
    with pytest.raises(ValueError):
        age.generate_rows(GRID[:2], [_budgets(1.0)])


def test_generate_rows_batch_of_one_equals_batch_of_64(grid_rows):
    rows, _ = grid_rows
    picks = [(ti, si) for ti in range(len(GRID))
             for si in range(0, len(SCALES), 94)][:64]
    assert len(picks) == 64
    batch = age.generate_rows([GRID[ti] for ti, _ in picks],
                              [_budgets(SCALES[si]) for _, si in picks])
    for (ti, si), got in zip(picks, batch):
        one, = age.generate_rows([GRID[ti]], [_budgets(SCALES[si])])
        want = rows[ti * len(SCALES) + si]
        for r in (one, got):
            assert np.array_equal(pathfinder.pack_hw(r),
                                  pathfinder.pack_hw(want))
            assert float(r.n_mcu) == float(want.n_mcu)
