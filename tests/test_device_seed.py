"""On-device population seeding (`mapping.seed_population`).

The device kernel and the numpy twin consume the same pre-drawn
uniforms (`mapping.seed_uniforms`), so parity is exact — the float32
index arithmetic (pick = floor(u * n_valid)) matches XLA's bit for bit.
Golden values pin the seeded draws across refactors.  They follow the
installed jax's default threefry stream: jax 0.5 made
`jax_threefry_partitionable` the default, which changed the bits drawn
for a key (the goldens captured on jax 0.4.37 reproduce with
JAX_THREEFRY_PARTITIONABLE=0), so they were re-pinned on jax 0.9.0.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.archspec import EDGE_SPEC, TPU_V5E_SPEC, resolve_spec
from repro.core.cosa import cosa_seed_population
from repro.core.mapping import (random_mapping_population, seed_population,
                                seed_population_host, seed_uniforms,
                                unstack_mappings)
from repro.core.problem import Layer, Workload

SPECS = ((None, "gemmini"), (TPU_V5E_SPEC, "tpu_v5e"), (EDGE_SPEC, "edge"))


@pytest.fixture(scope="module")
def workload() -> Workload:
    return Workload(layers=(Layer.conv(64, 64, 3, 56, name="c1"),
                            Layer.matmul(512, 1024, 768, name="m1")),
                    name="two")


@pytest.mark.parametrize("spec,name", SPECS, ids=[n for _, n in SPECS])
@pytest.mark.parametrize("mode", ["random", "cosa"])
def test_device_matches_host_twin(workload, spec, name, mode):
    dims = workload.dims_array()
    key = jax.random.PRNGKey(7)
    f_d, theta, o_d = seed_population(dims, 5, key, spec=spec, mode=mode)
    u_f, u_o = seed_uniforms(dims, 5, key, spec=spec)
    f_h, o_h = seed_population_host(dims, u_f, u_o, spec=spec, mode=mode)
    assert np.array_equal(np.asarray(f_d), f_h)
    assert np.array_equal(np.asarray(o_d), o_h)
    assert np.isfinite(np.asarray(theta)).all()


@pytest.mark.parametrize("spec,name", SPECS, ids=[n for _, n in SPECS])
@pytest.mark.parametrize("mode", ["random", "cosa"])
def test_seeded_mappings_are_valid(workload, spec, name, mode):
    dims = workload.dims_array()
    f, _, o = seed_population(dims, 4, jax.random.PRNGKey(3), spec=spec,
                              mode=mode)
    f, o = np.asarray(f, dtype=float), np.asarray(o)
    for p in range(4):
        for li, m in enumerate(unstack_mappings(f[p], o[p])):
            m.validate(dims[li], spec=spec)


def test_entry_points_alias_modes(workload):
    dims = workload.dims_array()
    key = jax.random.PRNGKey(1)
    for a, b in zip(random_mapping_population(dims, 3, key),
                    seed_population(dims, 3, key, mode="random")):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(cosa_seed_population(dims, 3, key),
                    seed_population(dims, 3, key, mode="cosa")):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_golden_random_draw(workload):
    """Pin the seeded stream: same key => same integer factors, across
    refactors of the kernel (threefry is stable per jax key and
    `jax_threefry_partitionable` setting; see the module doc)."""
    dims = workload.dims_array()
    f, _, o = seed_population(dims, 2, jax.random.PRNGKey(7))
    assert np.asarray(f)[0, 0].astype(int).tolist() == [
        [[1, 1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 16, 1, 1],
         [1, 1, 1, 1, 1, 4, 1], [1, 1, 1, 1, 1, 1, 1]],
        [[1, 1, 7, 1, 1, 1, 1], [3, 1, 1, 1, 1, 16, 1],
         [1, 3, 8, 28, 4, 1, 1], [1, 1, 1, 2, 1, 1, 1]]]
    assert np.asarray(o)[0].tolist() == [[2, 2, 1, 1], [0, 1, 2, 2]]


def test_golden_cosa_spatial_fill(workload):
    """CoSA mode takes the largest valid divisor at each spatial site:
    on Gemmini's conv layer (C=64, K=64, cap 128) the C site fills the
    array, and the K site takes all that the level-1 temporal K draw
    (16 with this key) leaves of K."""
    dims = workload.dims_array()
    f, _, _ = seed_population(dims, 2, jax.random.PRNGKey(7), mode="cosa")
    f0 = np.asarray(f)[0, 0].astype(int)
    cspec = resolve_spec(None)
    picks = [int(f0[0, lvl, d]) for (lvl, d) in cspec.spatial_sites]
    assert picks == [64, 4]
    assert picks[1] * f0[1, 1, 5] == 64      # spatial K x temporal K
    # spatial factors never exceed the PE cap, any member, any layer
    sp = np.asarray(f)[:, :, 0]
    assert (sp <= cspec.pe_cap).all()


def test_seed_population_rejects_unknown_mode(workload):
    with pytest.raises(ValueError, match="mode"):
        seed_population(workload.dims_array(), 2, jax.random.PRNGKey(0),
                        mode="exhaustive")
