"""Traffic comes from `--seed` alone: the same seed gives the same
requests, another seed the same set of requests in another order."""
import collections
import json

import harness


def _traffic(cell: str, seed: int, tmp_path):
    bm = harness.benchmark()
    entry = harness.cell_entry(bm, cell)
    mix = harness.traffic_mix(entry["traffic"])
    cfg = harness.config_for(bm, entry["config"])
    return harness.driver(mix["driver"]).Traffic(
        cfg, mix, seed, tmp_path, lambda msg: None), mix


GEMM = "tpuv5e-jamba-decode32k.serve-gemm"
PAPER = "gemmini-dosa4.serve-paper"
SWEEP = "gemmini-dosa4.sweep-p128"


def test_http_stream_repeats_per_seed(tmp_path):
    big = 2**31 + 12345   # seeds past 32 signed bits are accepted
    a, _ = _traffic(GEMM, big, tmp_path)
    b, _ = _traffic(GEMM, big, tmp_path)
    c, _ = _traffic(GEMM, big + 1, tmp_path)
    assert json.dumps(a.payloads) == json.dumps(b.payloads)
    assert json.dumps(a.payloads) != json.dumps(c.payloads)


def _block_counts(t, mix, block: int):
    names = [p["workload"]["name"] for p in
             t.payloads[block * mix["block"]:(block + 1) * mix["block"]]]
    return collections.Counter(names)


def test_every_seed_sends_the_same_set_per_block(tmp_path):
    for cell in (GEMM, PAPER):
        t1, mix = _traffic(cell, 1, tmp_path)
        t2, _ = _traffic(cell, 99, tmp_path)
        for blk in (1, 5):
            n_rep = round(mix["repeat_share"] * mix["block"])
            c1, c2 = _block_counts(t1, mix, blk), _block_counts(t2, mix, blk)
            # fresh requests per item are fixed; repeats copy earlier
            # requests, so only those may move between items
            diff = sum(abs(c1[k] - c2[k]) for k in set(c1) | set(c2))
            assert diff <= 2 * n_rep
            assert sum(c1.values()) == sum(c2.values()) == mix["block"]


def test_zipf_popularity_and_repeats(tmp_path):
    t, mix = _traffic(GEMM, 7, tmp_path)
    names = collections.Counter(p["workload"]["name"] for p in t.payloads)
    ranked = [n for n, _ in names.most_common()]
    assert ranked[0] == mix["popularity"][0]
    blobs = [json.dumps(p, sort_keys=True) for p in t.payloads]
    repeats = len(blobs) - len(set(blobs))
    assert abs(repeats / len(blobs) - mix["repeat_share"]) < 0.02
    for p in t.payloads:
        assert len(p["workload"]["layers"]) == 1
        assert p["config"]["spec"] == "tpu_v5e"


def test_paper_mix_is_uniform_over_networks(tmp_path):
    t, mix = _traffic(PAPER, 3, tmp_path)
    names = collections.Counter(p["workload"]["name"] for p in t.payloads)
    assert set(names) == set(mix["popularity"])
    assert len(set(names.values())) == 1
    seeds = [p["config"]["seed"] for p in t.payloads]
    assert len(set(seeds)) == len(seeds)   # no repeats in this mix


def test_sweep_seeds_repeat_per_seed(tmp_path):
    a, _ = _traffic(SWEEP, 2**31 + 5, tmp_path)
    b, _ = _traffic(SWEEP, 2**31 + 5, tmp_path)
    c, _ = _traffic(SWEEP, 2**31 + 6, tmp_path)
    def draw(t):
        return [int(t._seeds.integers(2**31 - 1)) for _ in range(5)]
    assert a._warm_seed == b._warm_seed
    assert draw(a) == draw(b) != draw(c)
