"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They put `bench/` and the program's `src/` on the path, as
`bench/run.py` does."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def tiny(monkeypatch):
    """Every cell at a tiny protocol and load, so a run takes seconds."""
    import harness
    config_for, traffic_mix = harness.config_for, harness.traffic_mix

    def config(bm, name):
        c = config_for(bm, name)
        c["protocol"].update(steps=4, round_every=2, n_start_points=2)
        return c

    def mix(name):
        m = traffic_mix(name)
        if m["driver"] == "api_sweep":
            m.update(population=4, workload="bert")
        else:
            m.update(clients=2, lead_in_s=0.0, poll_s=0.01,
                     popularity=m["popularity"][:1])
            if m["pick"] == "networks":
                m["popularity"] = ["bert"]
        return m

    monkeypatch.setattr(harness, "config_for", config)
    monkeypatch.setattr(harness, "traffic_mix", mix)
    drv = harness.driver("http_closed")
    monkeypatch.setattr(harness, "driver", lambda name: (
        drv if name == "http_closed" else
        harness._load_module(harness.BENCH / "drivers" / f"{name}.py",
                             f"bench_driver_{name}")))
    monkeypatch.setattr(drv, "STALL_S", 4.0)
    monkeypatch.setattr(drv, "ANSWER_WAIT_S", 2.0)


@pytest.fixture
def searched(monkeypatch):
    """The sweep at the paper's protocol with a quarter of its starts:
    resnet50, 32 device-seeded starts, so that the search reference
    runs in seconds on the CPU."""
    import harness
    traffic_mix = harness.traffic_mix
    monkeypatch.setattr(harness, "traffic_mix", lambda name: dict(
        traffic_mix(name), population=32))


# The paper-protocol served mix (`bench/traffic/serve-paper.json`) is
# kept for a later cell (PERF.md, Open questions); its generator path
# (whole networks per request) is tested through this entry.
PAPER_CELL = {"name": "gemmini-dosa4.serve-paper", "config": "gemmini-dosa4",
              "traffic": "serve-paper", "chips": 1, "why": "tests only"}


@pytest.fixture(autouse=True)
def paper_cell(monkeypatch):
    import harness
    real = harness.benchmark

    def with_paper():
        bm = real()
        if all(w["name"] != PAPER_CELL["name"] for w in bm["workloads"]):
            bm["workloads"].append(dict(PAPER_CELL))
        return bm
    monkeypatch.setattr(harness, "benchmark", with_paper)
