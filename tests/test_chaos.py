"""Crash-consistency and degradation tests for the hardened serving
runtime: torn checkpoints, kill/resume across multiple tasks, poison
quarantine with sibling bit-identity, deadline/segment-budget timeouts,
graceful degradation (surrogate + shard loss), priority scheduling and
checkpoint GC."""
import dataclasses

import numpy as np

from repro import obs
from repro.api import SearchRequest
from repro.core.problem import Layer, Workload
from repro.core.search import SearchConfig, dosa_search
from repro.runtime import faults
from repro.runtime import search_checkpoint as sckpt
from repro.runtime.chaos import ChaosConfig, ChaosMonkey, tear_checkpoint
from repro.serve.cosearch_service import CoSearchService, ServiceConfig

WL_A = Workload(layers=(Layer.matmul(16, 16, 16, name="a"),), name="wa")
WL_B = Workload(layers=(Layer.matmul(32, 16, 8, name="b"),), name="wb")


def _cfg(seed=1, steps=4, round_every=2):
    return SearchConfig(steps=steps, round_every=round_every,
                        n_start_points=2, seed=seed)


def _req(seed=1, wl=WL_A, **kw):
    return SearchRequest(workload=wl, config=_cfg(seed), **kw)


def _key(out):
    r = out.result
    return (r.best_edp, r.n_evals, tuple(map(tuple, r.history)))


def _direct_key(wl, seed):
    r = dosa_search(wl, _cfg(seed), population=2, fused=True)
    return (r.best_edp, r.n_evals, tuple(map(tuple, r.history)))


# ---------------------------------------------------------------------------
# Crash consistency
# ---------------------------------------------------------------------------

def test_torn_checkpoint_falls_back_to_previous_good_step(tmp_path):
    """Truncating the newest checkpoint mid-write must not lose the
    task: restore falls back to the previous intact step and the
    deterministic replay still reaches the bit-identical answer."""
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        checkpoint_dir=str(tmp_path),
                                        gc_completed=False))
    rid = svc.submit(_req(1))
    svc.step()   # seg 1 done; steps 0 and 1 on disk
    task_id = svc._tasks[0].task_id
    assert sckpt.restore_task(tmp_path, task_id,
                              tracer=obs.get_tracer())[0] == 1
    assert tear_checkpoint(tmp_path, task_id, 1)
    # the torn newest step is skipped; the seg-0 baseline restores
    assert sckpt.restore_task(tmp_path, task_id,
                              tracer=obs.get_tracer())[0] == 0

    svc2 = CoSearchService(ServiceConfig(bucket_workloads=False,
                                         checkpoint_dir=str(tmp_path)))
    svc2.submit(_req(1))
    out = svc2.drain()[rid]
    assert out.status == "ok"
    assert _key(out) == _direct_key(WL_A, 1)


def test_all_checkpoints_torn_replays_from_scratch(tmp_path):
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        checkpoint_dir=str(tmp_path),
                                        gc_completed=False))
    rid = svc.submit(_req(2))
    svc.step()
    task_id = svc._tasks[0].task_id
    for step in (0, 1):
        tear_checkpoint(tmp_path, task_id, step)
    assert sckpt.restore_task(tmp_path, task_id,
                              tracer=obs.get_tracer()) is None
    svc2 = CoSearchService(ServiceConfig(bucket_workloads=False,
                                         checkpoint_dir=str(tmp_path)))
    svc2.submit(_req(2))
    assert _key(svc2.drain()[rid]) == _direct_key(WL_A, 2)


def test_kill_resume_multiple_interleaved_tasks(tmp_path):
    """Two tasks advancing in interleaved WRR order, killed mid-stream:
    the successor service resumes BOTH from their own checkpoints and
    every answer stays bit-identical."""
    reqs = [_req(3, wl=WL_A), _req(3, wl=WL_B)]

    def make_service():
        return CoSearchService(ServiceConfig(
            bucket_workloads=False, checkpoint_dir=str(tmp_path),
            gc_completed=False))

    monkey = ChaosMonkey(ChaosConfig(seed=0))
    svc = make_service()
    for r in reqs:
        svc.submit(r)
    for _ in range(3):   # both tasks started, neither finished
        svc.step()
    assert sum(t.seg_done for t in svc._tasks) == 3
    svc = monkey.kill_resume(svc, make_service, reqs)
    outs = svc.drain()
    assert monkey.stats()["kills"] == 1
    assert _key(outs[reqs[0].request_id]) == _direct_key(WL_A, 3)
    assert _key(outs[reqs[1].request_id]) == _direct_key(WL_B, 3)


def test_seeded_chaos_schedule_keeps_healthy_requests_identical(
        tmp_path):
    """The chaos-gate contract at test scale: transient faults + torn
    checkpoint writes from one seeded schedule; every request still
    answers exactly."""
    reqs = [_req(s) for s in (4, 5)]
    svc = CoSearchService(ServiceConfig(
        bucket_workloads=False, checkpoint_dir=str(tmp_path),
        max_restarts=8, backoff_base_s=0.0))
    monkey = ChaosMonkey(ChaosConfig(seed=11, p_transient=0.4,
                                     p_torn_checkpoint=0.5,
                                     max_faults=4))
    monkey.attach(svc)
    for r in reqs:
        svc.submit(r)
    outs = svc.drain()
    injected = monkey.stats()
    assert injected["transient"] + injected["torn_checkpoint"] > 0
    for s, r in zip((4, 5), reqs):
        assert outs[r.request_id].status == "ok"
        assert _key(outs[r.request_id]) == _direct_key(WL_A, s)
    fstats = svc.stats()["faults"]
    assert fstats["retries"] == injected["transient"]


# ---------------------------------------------------------------------------
# Poison quarantine
# ---------------------------------------------------------------------------

def test_poison_quarantine_leaves_siblings_bit_identical():
    """A deterministically-failing request splits its batch, is
    quarantined with a structured poison record, and the sibling
    requests still answer exactly what direct search gives."""
    reqs = [_req(s) for s in (6, 7, 8)]
    target = reqs[-1].request_id

    def poison_hook(task_id, seg, request_ids):
        if target in request_ids:
            raise ValueError("chaos: poison input")

    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        backoff_base_s=0.0))
    svc.fault_hook = poison_hook
    for r in reqs:
        svc.submit(r)
    outs = svc.drain()

    bad = outs[target]
    assert bad.status == "error" and not bad.ok
    assert bad.result is None
    assert bad.error["fault_class"] == "poison"
    assert bad.error["type"] == "ValueError"
    for s, r in zip((6, 7), reqs[:2]):
        assert outs[r.request_id].status == "ok"
        assert _key(outs[r.request_id]) == _direct_key(WL_A, s)
    fstats = svc.stats()["faults"]
    assert fstats["quarantined"] == 1
    assert fstats["batch_splits"] == 1


def test_retry_budget_exhaustion_contained_for_server_loop():
    """contain_fatal (the transport scheduler's mode) converts an
    exhausted retry budget into structured error outcomes instead of
    propagating out of the loop."""
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        max_restarts=1,
                                        backoff_base_s=0.0))
    rid = svc.submit(_req(9))

    def always_fail(task_id, seg, request_ids):
        raise RuntimeError("hard fault")

    svc.fault_hook = always_fail
    while svc.busy():
        svc.step(contain_fatal=True)
    out = svc.outcome(rid)
    assert out.status == "error" and out.result is None
    assert out.error["type"] == "RuntimeError"
    assert out.error["retries"] == 1


# ---------------------------------------------------------------------------
# Deadlines / budgets
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_deadline_timeout_structured_partial_outcome():
    """A request whose wall-clock deadline expires mid-search finalizes
    as status='timeout' carrying the best-so-far partial result; its
    batch sibling is unperturbed."""
    clk = _Clock()
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        clock_fn=clk))
    slow = _req(10, deadline_s=50.0)
    sib = _req(11)
    svc.submit(slow)
    svc.submit(sib)
    svc.step()          # segment 1 of 2 done
    clk.t += 100.0      # blow the deadline between segments
    outs = svc.drain()

    t_out = outs[slow.request_id]
    assert t_out.status == "timeout" and not t_out.ok
    assert t_out.error["fault_class"] == "timeout"
    assert t_out.error["reason"] == "deadline"
    # partial result: one segment of history, finite best
    assert t_out.result is not None
    assert np.isfinite(t_out.best_edp)
    assert outs[sib.request_id].status == "ok"
    assert _key(outs[sib.request_id]) == _direct_key(WL_A, 11)
    assert svc.stats()["faults"]["timeouts"] == 1


def test_segment_budget_timeout():
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    rid = svc.submit(_req(12, segment_budget=1))
    outs = svc.drain()
    out = outs[rid]
    assert out.status == "timeout"
    assert out.error["reason"] == "segment_budget"
    assert out.result is not None


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------

class _DummySurrogate:
    """Stands in for a trained model; the engine never consumes it
    because the fault fires before the traced model is built."""


def test_surrogate_failure_degrades_to_analytical():
    req = SearchRequest(
        workload=WL_A,
        config=dataclasses.replace(_cfg(13),
                                   surrogate=_DummySurrogate()))
    fired = {"n": 0}

    def hook(task_id, seg, request_ids):
        if fired["n"] == 0:
            fired["n"] += 1
            raise faults.SurrogateFault("surrogate blew up")

    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    svc.fault_hook = hook
    rid = svc.submit(req)
    out = svc.drain()[rid]
    assert out.status == "degraded" and out.ok
    assert out.degraded == ("surrogate_fallback",)
    # the fallback answer IS the analytical answer, bit-identically
    assert _key(out) == _direct_key(WL_A, 13)
    assert svc.stats()["faults"]["degraded_requests"] == 1


def test_shard_loss_degrades_to_single_shard():
    fired = {"n": 0}

    def hook(task_id, seg, request_ids):
        if fired["n"] == 0:
            fired["n"] += 1
            raise faults.ShardLossFault("device unreachable")

    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    svc.fault_hook = hook
    rid = svc.submit(_req(14))
    out = svc.drain()[rid]
    assert out.status == "degraded"
    assert out.degraded == ("shard_fallback",)
    assert svc._tasks[0]._force_shards1
    assert _key(out) == _direct_key(WL_A, 14)


# ---------------------------------------------------------------------------
# Priority scheduling
# ---------------------------------------------------------------------------

def test_weighted_round_robin_prefers_high_priority():
    """Two equal-length tasks, one at priority 5: the high-priority
    task must finish all its segments strictly first."""
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    hi = SearchRequest(workload=WL_A, config=_cfg(15, steps=8),
                       priority=5)
    lo = SearchRequest(workload=WL_B, config=_cfg(16, steps=8))
    svc.submit(hi)
    svc.submit(lo)
    done_order = []
    while svc.busy():
        for ev in svc.step():
            if ev.done:
                done_order.append(ev.request_id)
    assert done_order[0] == hi.request_id
    # WRR is work-conserving: the low-priority task still finished
    assert svc.outcome(lo.request_id).status == "ok"


# ---------------------------------------------------------------------------
# Checkpoint GC
# ---------------------------------------------------------------------------

def test_drain_deletes_completed_task_checkpoints(tmp_path):
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        checkpoint_dir=str(tmp_path)))
    svc.submit(_req(17))
    svc.drain()
    assert not list(tmp_path.glob("task_*"))
    gc_stats = svc.stats()["faults"]["checkpoint_gc"]
    assert gc_stats["removed_tasks"] == 1
    assert gc_stats["bytes_freed"] > 0


def test_gc_disabled_keeps_checkpoints(tmp_path):
    svc = CoSearchService(ServiceConfig(bucket_workloads=False,
                                        checkpoint_dir=str(tmp_path),
                                        gc_completed=False))
    svc.submit(_req(18))
    svc.drain()
    assert list(tmp_path.glob("task_*"))


def test_lru_disk_sweep_bounds_total_bytes(tmp_path):
    """Unit-level: the GC sweeps least-recently-used task dirs until
    the disk bound holds, never evicting the most recent task."""
    for i in range(4):
        d = tmp_path / f"task_t{i}"
        d.mkdir()
        (d / "arrays.npz").write_bytes(bytes(1000))
    tracer = obs.Tracer()
    gc = sckpt.CheckpointGC(tmp_path, max_bytes=2000, tracer=tracer)
    for i in range(4):
        gc.touch(f"t{i}")   # recency order t0 (oldest) .. t3
    swept = gc.sweep()
    assert swept == ["t0", "t1"]
    # one checkpoint.gc span per directory the sweep deleted
    assert [(s.attrs["task_id"], s.attrs["bytes"])
            for s in tracer.spans_named("checkpoint.gc")] \
        == [("t0", 1000), ("t1", 1000)]
    assert gc.total_bytes() <= 2000
    assert sorted(p.name for p in tmp_path.glob("task_*")) \
        == ["task_t2", "task_t3"]
    stats = gc.stats()
    assert stats["removed_tasks"] == 2
    assert stats["bytes_freed"] == 2000


def test_checkpoint_fallback_unit(tmp_path):
    """save_task twice, tear the newest: restore_task returns the
    older step's exact payload."""
    theta0 = np.zeros((2, 1, 2, 3, 7), np.float32)
    theta1 = np.ones_like(theta0)
    orders = np.zeros((2, 1, 3), np.int64)
    rec = {"evals": np.int64(5)}
    sckpt.save_task(tmp_path, "tid", 1, theta0, orders, [rec],
                    tracer=obs.get_tracer())
    sckpt.save_task(tmp_path, "tid", 2, theta1, orders, [rec],
                    tracer=obs.get_tracer())
    seg, theta, _, recs = sckpt.restore_task(tmp_path, "tid",
                                             tracer=obs.get_tracer())
    assert seg == 2 and theta[0, 0, 0, 0, 0] == 1.0
    assert tear_checkpoint(tmp_path, "tid", 2)
    seg, theta, _, recs = sckpt.restore_task(tmp_path, "tid",
                                             tracer=obs.get_tracer())
    assert seg == 1 and theta[0, 0, 0, 0, 0] == 0.0
    assert int(recs[0]["evals"]) == 5


# ---------------------------------------------------------------------------
# Cross-request dedup
# ---------------------------------------------------------------------------

def test_dedup_attaches_to_inflight_task():
    """Fingerprint-identical submissions share one task; an aliased
    request_id resolves to the same outcome and events."""
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    rid = svc.submit(_req(19))
    again = svc.submit(_req(19))                       # same fingerprint
    alias = svc.submit(_req(19, request_id="mine"))    # custom id alias
    assert again == rid and alias == "mine"
    outs = svc.drain()
    assert svc.stats()["n_batches"] == 1
    assert svc.stats()["faults"]["dedup_hits"] == 2
    assert outs["mine"] is outs[rid]
    assert svc.outcome("mine") is svc.outcome(rid)
    assert svc.events("mine") == svc.events(rid)
    # scheduling hints are excluded from the fingerprint on purpose
    pri = _req(19, priority=3)
    assert pri.fingerprint() == _req(19).fingerprint()
