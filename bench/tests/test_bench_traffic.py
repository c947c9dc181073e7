"""The traffic generator: a function of the seed, the same work for every
seed, and the same geometries as the program's own generator."""
import itertools

import numpy as np
import pytest

from bench import traffic

MIX = {"loop": "open", "rate_per_s": 5.0, "pool_ids": [1, 2, 3, 4, 5, 6, 7, 8],
       "nu": 64, "nv": 32}


def test_open_schedule_is_a_function_of_the_seed():
    a = traffic.open_schedule(MIX, 2**33 + 17, 20.0)
    assert a == traffic.open_schedule(MIX, 2**33 + 17, 20.0)
    b = traffic.open_schedule(MIX, 5, 20.0)
    assert a != b
    # every seed: the same count, the same gaps and geometries, reordered
    assert len(a) == len(b) == 100
    gaps = [np.sort(np.diff([t for t, _ in s] + [20.0])) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-12)
    assert sorted(i for _, i in a) == sorted(i for _, i in b)
    assert a[0][0] == 0.0 and a[-1][0] < 20.0


def test_closed_order_passes_over_the_pool():
    order = list(itertools.islice(traffic.closed_order(MIX, 99), 24))
    for k in range(3):
        assert sorted(order[8 * k:8 * k + 8]) == list(range(8))
    assert order == list(itertools.islice(traffic.closed_order(MIX, 99), 24))


def test_car_surface_matches_the_program_generator():
    from repro.data import geometry as geo
    for g in (1, 7):
        p = traffic.car_params(g)
        assert p == vars(geo.sample_params(g))
        v, f = traffic.car_surface(p, 64, 32)
        v0, f0 = geo.car_surface(geo.sample_params(g), 64, 32)
        np.testing.assert_array_equal(f, f0)
        np.testing.assert_array_equal(v, v0)


def test_a_mix_names_the_cell_module_that_drives_it(tmp_path, monkeypatch):
    mix = traffic.load_mix("sweep")
    assert mix["kind"] == "serve"
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "odd.json").write_text('{"kind": "nothing"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(FileNotFoundError, match="bench/cells/nothing.py"):
        traffic.load_mix("odd")
