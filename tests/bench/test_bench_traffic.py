"""The traffic generator: the same seed gives the same requests, every seed
the same amount of work, and no run draws a design point twice."""

import pytest

from bench import generator, manifest

BENCH = manifest.load()
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
SEEDS = (0, 7, 2**31 + 11, 2**33 + 5)


def _draw(mix, seed, n=6):
    r = generator.Requests(mix, seed)
    warm = r.warmup()
    window = [req for req, _ in zip(r.window(), range(n))]
    return warm, window


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = manifest.traffic(name)
    for seed in SEEDS:
        assert _draw(mix, seed) == _draw(mix, seed)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_values_not_in_work(name):
    mix = manifest.traffic(name)
    runs = [_draw(mix, seed) for seed in SEEDS]
    shapes = {tuple(len(req["scales"]) for req in warm + window)
              for warm, window in runs}
    assert len(shapes) == 1
    firsts = {tuple(window[0]["scales"]) for _, window in runs}
    assert len(firsts) == len(SEEDS)


@pytest.mark.parametrize("name", MIXES)
def test_no_scale_drawn_twice_and_all_in_range(name):
    mix = manifest.traffic(name)
    warm, window = _draw(mix, 2**31 + 99, n=40)
    scales = [s for req in warm + window for s in req["scales"]]
    assert len(scales) == len(set(scales))
    lo, hi = mix["scale_range"]
    assert all(lo <= s <= hi for s in scales)
    # a point key prints the scale with 6 significant digits: no two
    # scales of a run may print alike
    assert len({f"{s:g}" for s in scales}) == len(scales)
    if mix["mode"] == "query":
        qlo, qhi = mix["qps_range"]
        assert all(qlo <= req["qps"] <= qhi for req in warm + window)


def test_unknown_mode_is_refused():
    with pytest.raises(ValueError):
        generator.Requests({"mode": "burst", "scale_range": [1, 2],
                            "scale_step": 0.1}, 0)
