"""FleetConfig / SearchTask wire-schema contract (COMPAT.md "FleetConfig
contract"):

* FleetConfig <-> JSON round-trip, unknown-field/version rejection, and
  the process-local mesh refusing to serialize,
* deprecated ``MultiSearch(**kwargs)`` aliases: warn, stay bit-identical
  to ``config=FleetConfig(...)``, and conflict loudly when both given,
* ``SearchTask.es_kw`` deprecation with merge semantics preserved,
* SearchTask <-> JSON round-trip: content-equal workload (cache_key),
  density models by registered family, platform by registry name,
  ``runtime_kw`` kept off the wire,
* property tests (hypothesis, shim fallback) over random spmm geometry
  and density families.
"""
import dataclasses
import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.core import search
from repro.core.density import (Banded, BlockNM, Uniform,
                                density_from_dict, density_to_dict)
from repro.core.search import FleetConfig, MultiSearch, SearchTask
from repro.core.workload import (spmm, workload_from_dict,
                                 workload_to_dict)

BUDGET = 300


def wl(name="fc_wl", m=16, k=16, n=8, dp=0.5, dq=0.5):
    return spmm(name, m, k, n, dp, dq)


# ------------------------------------------------- FleetConfig JSON


def test_fleet_config_json_round_trip():
    cfg = FleetConfig(align_signatures=False, device_rounds=4,
                      pipeline=False, compile_ahead=False)
    back = FleetConfig.from_json(cfg.to_json())
    assert back == cfg
    # defaults round-trip too
    assert FleetConfig.from_json(FleetConfig().to_json()) == FleetConfig()


def test_fleet_config_rejects_unknown_fields_and_versions():
    d = FleetConfig().to_json_dict()
    d["warp_factor"] = 9
    with pytest.raises(ValueError, match="warp_factor"):
        FleetConfig.from_json(d)
    d2 = FleetConfig().to_json_dict()
    d2["version"] = 99
    with pytest.raises(ValueError):
        FleetConfig.from_json(d2)


def test_fleet_config_stack_batches_is_a_validated_constant():
    """The fleet always mega-batches: ``stack_batches=True`` (as the
    benchmark configurations spell it) is accepted and changes nothing,
    and ``False`` is refused, from the constructor and from JSON."""
    assert FleetConfig(stack_batches=True) == FleetConfig()
    assert FleetConfig.from_json({"stack_batches": True,
                                  "device_rounds": 1}) == \
        FleetConfig(device_rounds=1)
    with pytest.raises(ValueError, match="per-task dispatch path"):
        FleetConfig(stack_batches=False)
    with pytest.raises(ValueError, match="per-task dispatch path"):
        FleetConfig.from_json('{"version": 1, "stack_batches": false}')


def test_fleet_config_mesh_is_process_local():
    cfg = FleetConfig(mesh=object())
    with pytest.raises(ValueError, match="mesh"):
        cfg.to_json_dict()


def test_fleet_config_validates_device_rounds():
    with pytest.raises(ValueError):
        FleetConfig(device_rounds=0)
    v, src = FleetConfig(device_rounds=3).resolved_device_rounds()
    assert (v, src) == (3, "explicit")
    v, src = FleetConfig().resolved_device_rounds()
    assert v >= 1 and src.startswith("default:")


# ------------------------------------- deprecated MultiSearch kwargs


def test_legacy_kwargs_warn_and_match_config():
    """``MultiSearch(tasks, compile_ahead=False)`` must warn AND give
    bit-identical results to the FleetConfig spelling."""
    def tasks():
        return [SearchTask(wl("lg1"), "cloud", budget=BUDGET, seed=7),
                SearchTask(wl("lg2", m=24), "cloud", budget=BUDGET,
                           seed=7)]
    with pytest.warns(DeprecationWarning, match="FleetConfig"):
        ms_old = MultiSearch(tasks(), compile_ahead=False)
    assert ms_old.config == FleetConfig(compile_ahead=False)
    old = ms_old.run()
    new = MultiSearch(tasks(), FleetConfig(compile_ahead=False)).run()
    for name in old:
        assert old[name].best_edp == new[name].best_edp
        assert np.array_equal(old[name].history, new[name].history)


def test_legacy_kwargs_conflict_with_config_is_an_error():
    t = [SearchTask(wl(), "cloud", budget=BUDGET)]
    with pytest.raises(ValueError, match="config"):
        MultiSearch(t, FleetConfig(), compile_ahead=False)


def test_config_spelling_does_not_warn():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        MultiSearch([SearchTask(wl(), "cloud", budget=BUDGET)],
                    FleetConfig())
        search.run_method_sweep(
            ["random_mapper"], [wl()], "cloud", budget=BUDGET,
            config=FleetConfig())


# ------------------------------------------------ es_kw deprecation


def test_es_kw_warns_and_merges():
    with pytest.warns(DeprecationWarning, match="es_kw"):
        t = SearchTask(wl(), "cloud", budget=BUDGET,
                       es_kw={"pop": 32, "elite_frac": 0.5},
                       method_kw={"pop": 48})
    # explicit method_kw wins over the deprecated alias
    assert t.method_kw["pop"] == 48
    assert t.method_kw["elite_frac"] == 0.5


# -------------------------------------------- density / workload JSON


def test_density_dict_round_trip_all_families():
    for m in (Uniform(0.3), Banded(0.2, 0.5), BlockNM(2, 4)):
        d = density_to_dict(m)
        json.dumps(d)                       # wire-safe
        assert density_from_dict(d) == m
    # plain float normalizes to Uniform
    assert density_from_dict(density_to_dict(0.25)) == Uniform(0.25)


def test_density_from_dict_unknown_family_names_registry():
    with pytest.raises(ValueError, match="uniform"):
        density_from_dict({"family": "fractal", "fields": {}})


def test_unregistered_density_model_refuses_to_serialize():
    @dataclasses.dataclass(frozen=True)
    class Ghost(Uniform):
        family = "ghost_unregistered"
    with pytest.raises(ValueError, match="not registered"):
        density_to_dict(Ghost(0.5))


def test_workload_json_round_trip_is_cache_key_equal():
    w = spmm("wire", 100, 64, 48, Banded(0.2, 0.5), 0.6)
    back = workload_from_dict(workload_to_dict(w))
    assert back.cache_key() == w.cache_key()
    assert back.structured_density == w.structured_density


# ------------------------------------------------- SearchTask JSON


def test_search_task_json_round_trip():
    t = SearchTask(wl("stj", m=48), "edge", budget=1234, seed=9,
                   method="pso", method_kw={"swarm": 16})
    back = SearchTask.from_json(t.to_json())
    assert back.workload.cache_key() == t.workload.cache_key()
    assert (back.platform, back.budget, back.seed, back.method,
            back.method_kw) == ("edge", 1234, 9, "pso", {"swarm": 16})


def test_search_task_json_excludes_runtime_kw():
    t = SearchTask(wl(), "cloud", budget=BUDGET)
    t.runtime_kw["state_out"] = {}
    t.runtime_kw["warm_seeds"] = np.zeros((1, 4))
    d = t.to_json_dict()
    json.dumps(d)                           # must stay wire-safe
    assert "runtime_kw" not in d and "es_kw" not in d
    assert SearchTask.from_json(d).runtime_kw == {}


def test_search_task_json_rejects_unknown_fields():
    d = SearchTask(wl(), "cloud", budget=BUDGET).to_json_dict()
    d["favorite_color"] = "blue"
    with pytest.raises(ValueError, match="favorite_color"):
        SearchTask.from_json(d)


def test_search_task_json_round_trip_same_search_result():
    """The deserialized task must search identically: same evaluator
    (shared via cache_key), same trajectory at a fixed seed."""
    t = SearchTask(wl("same_res"), "cloud", budget=BUDGET, seed=11)
    t2 = SearchTask.from_json(t.to_json())
    a = MultiSearch([t], FleetConfig()).run()["same_res@cloud"]
    b = MultiSearch([t2], FleetConfig()).run()["same_res@cloud"]
    assert a.best_edp == b.best_edp
    assert np.array_equal(a.history, b.history)


# ---------------------------------------------------- property tests


@st.composite
def spmm_args(draw):
    return dict(m=draw(st.integers(4, 200)),
                k=draw(st.integers(4, 200)),
                n=draw(st.integers(4, 200)),
                dp=draw(st.floats(0.05, 1.0)),
                dq=draw(st.floats(0.05, 1.0)))


@settings(max_examples=25, deadline=None)
@given(spmm_args())
def test_property_search_task_round_trip(kw):
    t = SearchTask(spmm("prop", kw["m"], kw["k"], kw["n"],
                        kw["dp"], kw["dq"]),
                   "mobile", budget=500, seed=1)
    back = SearchTask.from_json(json.loads(t.to_json()))
    assert back.workload.cache_key() == t.workload.cache_key()
    assert back.to_json() == t.to_json()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 1), st.integers(0, 1), st.integers(0, 1),
       st.integers(1, 8))
def test_property_fleet_config_round_trip(align, ahead, pipe, dr):
    cfg = FleetConfig(align_signatures=bool(align),
                      compile_ahead=bool(ahead), pipeline=bool(pipe),
                      device_rounds=dr)
    assert FleetConfig.from_json(cfg.to_json()) == cfg
