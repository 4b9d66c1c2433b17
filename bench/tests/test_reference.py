"""The plain reference against the program it judges, on the CPU.

The reference imports nothing of the program; these tests do, to show
that the two agree where the program is right, that the configuration
files hold what the program serves, and that the control (the
reference in float32) is told apart by the limit."""
import math

import numpy as np
import pytest

import harness
import reference
from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.core.archspec import (GEMMINI_SPEC, TPU_V5E_SPEC, bucket_dim,
                                 bucket_workload, compile_spec)
from repro.core.mapping import random_mapping
from repro.core.oracle import evaluate_workload
from repro.core.problem import Layer
from repro.workloads import dnn_zoo
from repro.workloads.lm_extract import extract

BM = harness.benchmark()
GEMMINI = harness.config_for(BM, "gemmini-dosa4")
JAMBA = harness.config_for(BM, "tpuv5e-jamba-decode32k")
CASES = [(GEMMINI, GEMMINI_SPEC, "resnet50"), (GEMMINI, GEMMINI_SPEC, "bert"),
         (JAMBA, TPU_V5E_SPEC, "decode32k")]


def _layers(cfg, name):
    return cfg["workloads"][name]["layers"]


def _program_layers(layers):
    return [Layer(dims=tuple(lay["dims"]), wstride=lay["wstride"],
                  hstride=lay["hstride"], repeat=lay["repeat"],
                  name=lay["name"]) for lay in layers]


def _mamba1_gemms(m: dict) -> dict:
    """Jamba's Mamba-1 projections at the published widths the
    configuration's `model` states: in_proj (x and z), x_proj (dt rank
    plus B and C), dt_proj and out_proj."""
    inner = m["mamba_expand"] * m["d_model"]
    rows = m["batch"]
    return {"ssm_in": (rows, m["d_model"], 2 * inner),
            "ssm_x_proj": (rows, inner,
                           m["mamba_dt_rank"] + 2 * m["mamba_d_state"]),
            "ssm_dt_proj": (rows, m["mamba_dt_rank"], inner),
            "ssm_out": (rows, inner, m["d_model"])}


def test_configs_hold_the_programs_workloads():
    for name, fn in dnn_zoo.TARGET_WORKLOADS.items():
        got = [(tuple(lay["dims"]), lay["wstride"], lay["hstride"],
                lay["repeat"]) for lay in _layers(GEMMINI, name)]
        want = [(lay.dims, lay.wstride, lay.hstride, lay.repeat)
                for lay in fn().layers]
        assert got == want, name
    # Attention, MLP, MoE and the head as the program extracts them;
    # the Mamba blocks at Jamba's published Mamba-1 widths (the
    # program's extractor models them as Mamba-2).
    wl = extract(get_config("jamba_v0_1_52b"), SHAPES["decode_32k"])
    got = {lay["name"]: (tuple(lay["dims"]), lay["repeat"])
           for lay in _layers(JAMBA, "decode32k")}
    for lay in wl.layers:
        if not lay.name.startswith("ssm"):
            assert got.pop(lay.name) == (lay.dims, lay.repeat), lay.name
    m = JAMBA["model"]
    mamba_layers = m["n_layers"] - m["n_layers"] // m["attn_layer_period"]
    for name, (rows, c, k) in _mamba1_gemms(m).items():
        assert got.pop(name) == ((1, 1, rows, 1, c, k, 1), mamba_layers)
    assert got == {}


def test_configs_hold_the_programs_spec_tables():
    for cfg, spec in ((GEMMINI, GEMMINI_SPEC), (JAMBA, TPU_V5E_SPEC)):
        s = cfg["spec"]
        assert s["name"] == spec.name == cfg["spec_name"]
        assert s["epa_mac"] == spec.epa_mac
        for d, lvl in zip(s["levels"], spec.levels):
            assert d["epa"]["base"] == lvl.epa.base
            assert d["epa"]["slope"] == lvl.epa.slope
            assert d["bandwidth"]["coeff"] == lvl.bandwidth.coeff
            assert d["size_words"] == lvl.size_words
            assert tuple(d["tensors"]) == lvl.tensors


def test_served_answers_are_judged_as_sent():
    """A mapping of the padded problem the service searches passes where
    the configuration allows padding, priced on the dims it tiles; one
    that tiles less than was sent fails either way."""
    layers = _layers(JAMBA, "decode32k")
    lay = next(x for x in layers if x["name"] == "ssm_x_proj")
    padded = bucket_workload(
        _program_layers_workload([lay])).layers[0].dims
    assert padded != tuple(lay["dims"])
    assert all(p == bucket_dim(d) for p, d in zip(padded, lay["dims"]))
    rspec = reference.Spec(JAMBA["spec"])
    f = np.ones((2, rspec.n, 7))
    f[1, rspec.backing, :] = padded
    answer = {"mappings": [(f.tolist(), [0] * rspec.n)],
              "protocol": {"steps": 1, "round_every": 1,
                           "n_start_points": 1, "max_reject_tries": 1,
                           "device_seeded": True},
              "history": [[2, 0.0]], "n_evals": 2}
    want = reference.network_edp(rspec, answer["mappings"],
                                 [dict(lay, dims=list(padded))])
    answer["best_edp"] = want
    answer["history"][0][1] = want
    assert reference.judge(rspec, answer, [lay], at_least=True) == {
        "invalid": 0, "edp_gap": 0.0, "accounting": 0}
    assert reference.judge(rspec, answer, [lay])["invalid"] == 1
    short = [dict(lay, dims=[d + 1 if d > 8 else d for d in padded])]
    assert reference.judge(rspec, answer, short, at_least=True)[
        "invalid"] == 1


def _program_layers_workload(layers):
    from repro.core.problem import Workload
    return Workload(layers=tuple(_program_layers(layers)), name="w")


@pytest.mark.parametrize("cfg,spec,name", CASES)
def test_reference_edp_equals_the_oracle(cfg, spec, name):
    """Random valid mappings: the reference EDP equals the program's
    oracle to rounding, and the control (float32) does not."""
    rng = np.random.default_rng(0)
    cspec = compile_spec(spec)
    layers = _layers(cfg, name)
    prog = _program_layers(layers)
    rspec = reference.Spec(cfg["spec"])
    gaps, ctrl = [], []
    for _ in range(6):
        maps = [random_mapping(lay.dims, rng, spec=cspec) for lay in prog]
        want, _ = evaluate_workload(maps, prog, spec=cspec)
        pairs = [(m.f.tolist(), m.order.tolist()) for m in maps]
        got = reference.network_edp(rspec, pairs, layers)
        if not math.isfinite(want):
            assert not math.isfinite(got)
            continue
        gaps.append(abs(got - want) / want)
        low = reference.network_edp(rspec, pairs, layers, np.float32)
        ctrl.append(abs(low - got) / got)
    assert gaps and max(gaps) < 1e-13
    lim = harness.limits()["edp_gap"]
    assert max(gaps) <= lim < max(ctrl)


def test_check_mapping_catches_broken_tilings():
    rspec = reference.Spec(GEMMINI["spec"])
    dims = (3, 3, 56, 56, 64, 64, 1)
    f = np.ones((2, 4, 7))
    f[1, 3, :] = dims
    assert reference.check_mapping(rspec, f, dims) == ""
    g = f.copy()
    g[1, 3, 2] = 28            # P no longer tiles 56
    assert "multiply" in reference.check_mapping(rspec, g, dims)
    g = f.copy()
    g[0, 3, 4], g[1, 3, 4] = 2, 32   # spatial C at DRAM: off the sites
    assert "sites" in reference.check_mapping(rspec, g, dims)
    g = f.copy()
    g[1, 0, 4], g[1, 3, 4] = 2, 32   # temporal C at the registers
    assert "registers" in reference.check_mapping(rspec, g, dims)


def test_accounting_rules():
    proto = {"steps": 6, "round_every": 3, "n_start_points": 2,
             "max_reject_tries": 10, "device_seeded": True}
    hist = [[13, 5.0], [14, 4.0], [15, 4.0], [16, 3.0]]
    good = {"history": hist, "n_evals": 16, "best_edp": 3.0}
    assert reference.accounting_faults(good, proto) == 0
    assert reference.accounting_faults(dict(good, n_evals=15), proto) > 0
    assert reference.accounting_faults(dict(good, history=hist[:3]),
                                       proto) > 0
    bad = [[13, 5.0], [14, 6.0], [15, 4.0], [16, 3.0]]
    assert reference.accounting_faults(dict(good, history=bad), proto) > 0
    host = dict(proto, device_seeded=False)
    hist6 = [[i, 9.0 - i] for i in range(10, 16)]
    assert reference.accounting_faults(
        {"history": hist6, "n_evals": 20, "best_edp": hist6[-1][1]},
        host) == 0
