"""The search reference against the program, on the CPU, where the two
have to agree exactly or to float32 rounding: the start points a seed
gives, the model's loss at them, rounding and the ordering descent."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import search_ref
from repro.core.archspec import (GEMMINI_SPEC, TPU_V5E_SPEC, compile_spec,
                                 sites_per_dim)
from repro.core.mapping import seed_population
from repro.core.problem import Layer, Workload
from repro.core.rounding import round_population
from repro.core.search import SearchConfig, _cd_orderings, _make_loss_fn

BM = harness.benchmark()
CASES = [("gemmini-dosa4", "bert", GEMMINI_SPEC),
         ("tpuv5e-jamba-decode32k", "decode32k", TPU_V5E_SPEC)]


def _setup(config, workload):
    cfg = harness.config_for(BM, config)
    layers = cfg["workloads"][workload]["layers"]
    dims = np.array([lay["dims"] for lay in layers])
    return cfg, layers, dims, search_ref.Tables(cfg["spec"])


@pytest.mark.parametrize("config,workload,spec", CASES)
def test_starts_equal_the_programs(config, workload, spec):
    cfg, layers, dims, tab = _setup(config, workload)
    seed, members = 2**31 + 17, 6
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    f_p, _, o_p = seed_population(dims, members, key,
                                  spec=compile_spec(spec),
                                  pe_cap=tab.cap, mode="cosa")
    u_f, u_o = search_ref.seed_uniforms(seed, members, len(layers), tab)
    f_r, o_r = search_ref.cosa_starts(dims, u_f, u_o, tab)
    assert np.array_equal(np.asarray(f_p), f_r)
    assert np.array_equal(np.asarray(o_p), o_r)


@pytest.mark.parametrize("config,workload,spec", CASES)
def test_loss_rounding_and_orderings_equal_the_programs(config, workload,
                                                        spec):
    cfg, layers, dims, tab = _setup(config, workload)
    seed, members = 5, 4
    u_f, u_o = search_ref.seed_uniforms(seed, members, len(layers), tab)
    f0, orders = search_ref.cosa_starts(dims, u_f, u_o, tab)
    wl = Workload(layers=tuple(
        Layer(dims=tuple(lay["dims"]), wstride=lay["wstride"],
              hstride=lay["hstride"], repeat=lay["repeat"],
              name=lay["name"]) for lay in layers), name=workload)
    loss, *_ = _make_loss_fn(wl, SearchConfig(spec=spec))
    cspec = compile_spec(spec)
    theta = np.where(cspec.free_mask, np.log(f0), 0.0).astype(np.float32)
    want = np.asarray(jax.vmap(loss)(jnp.asarray(theta),
                                     jnp.asarray(orders)))
    with jax.enable_x64(True):
        strides = jnp.asarray([[lay["wstride"], lay["hstride"]]
                               for lay in layers], dtype=jnp.float64)
        reps = jnp.asarray([lay["repeat"] for lay in layers],
                           dtype=jnp.float64)
        got_loss, tables = search_ref._model(tab, strides, reps)
        got = [float(got_loss(jnp.asarray(f0[m]), jnp.asarray(orders[m]),
                              cfg["protocol"]["penalty_weight"]))
               for m in range(members)]
        e, lat = jax.vmap(tables)(jnp.asarray(f0))
    np.testing.assert_allclose(got, want, rtol=1e-5)

    # rounding: continuous factors near the starts land where the
    # program's nearest-divisor projection puts them
    rng = np.random.default_rng(0)
    f_cont = f0 * np.exp(rng.normal(0.0, 0.4, f0.shape)) * tab.free \
        + f0 * ~tab.free
    mine = search_ref.round_population(f_cont, dims, tab)
    theirs = round_population(f_cont, orders, dims, spec=cspec)
    for m in range(members):
        assert np.array_equal(mine[m], np.stack([x.f for x in theirs[m]]))

    # ordering descent on the same tables
    e, lat = np.asarray(e), np.asarray(lat)
    choice = search_ref.coordinate_descent(e, lat)
    for m in range(members):
        assert np.array_equal(
            choice[m], np.asarray(_cd_orderings(jnp.asarray(e[m]),
                                                jnp.asarray(lat[m]))))


def test_tables_are_the_specs():
    for config, _, spec in CASES:
        tab = search_ref.Tables(harness.config_for(BM, config)["spec"])
        cspec = compile_spec(spec)
        assert np.array_equal(tab.free, cspec.free_mask)
        assert np.array_equal(tab.combos, cspec.combos)
        assert tab.cap == cspec.pe_cap
        assert json.dumps(tab.sites_per_dim) == json.dumps(
            sites_per_dim(cspec))
