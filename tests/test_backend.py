"""Execution-backend tests: the SimBackend refactor must be invisible
(bit-identical to the pre-backend runtime) and the JaxProcessBackend
must reproduce the simulator's numerics over *real* multi-process
``jax.distributed`` collectives — the sim/real parity contract CI's
``multiprocess-smoke`` lane enforces.

The multi-process tests spawn real OS processes (gloo CPU collectives)
via ``repro.cluster.launch_mp.run_mp`` — two for the single-trainer
parity runs, four for the k=2 multi-trainer merge run; everything else
runs in-process (a single-process JaxProcessBackend degenerates every
collective to the identity, which is exactly what makes it comparable
bit-for-bit).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.configs.base import AdLoCoConfig
from repro.core import train_adloco
from repro.cluster import (SimBackend, JaxProcessBackend, Topology,
                           interleave_pods, make_pod_profiles,
                           make_rack_profiles, run_cluster)
from repro.cluster import launch_mp
from repro.cluster.launch_mp import run_mp, run_sim

from tests.test_adloco_integration import QuadStream, _quad_setup, quad_loss

TOY = dict(flops=1e6, hbm_bw=1e9, link_bw=2e5, link_latency=2e-3)

ACFG = AdLoCoConfig(num_outer_steps=8, num_inner_steps=5, lr_inner=0.05,
                    lr_outer=0.7, outer_momentum=0.5, nodes_per_gpu=2,
                    num_init_trainers=3, initial_batch_size=2,
                    merge_frequency=3, eta=0.8, max_batch=16,
                    inner_optimizer="sgd", stats_probe_size=32,
                    enable_merge=False, adaptive=False)

#: parity tolerance for the real backend: the hierarchical pmean chain
#: may re-associate the mean, so "float tolerance", not bitwise — in
#: practice the 2-process runs come out bit-identical
PARITY_ATOL = 1e-6


def _pod_cluster():
    profiles = make_pod_profiles([5, 5], ratio=2.0, **TOY)
    topo = Topology.from_profiles(profiles, inter_bw=1e5,
                                  inter_latency=4e-3)
    return interleave_pods(profiles), topo


# ------------------------------------------------- SimBackend identity

def test_explicit_sim_backend_is_bit_identical_to_network_path():
    """run_cluster(backend=SimBackend(topo)) must reproduce
    run_cluster(network=topo) exactly — same params, same report — on
    an elastic scenario run that exercises joins, leaves, fabric
    windows and in-flight re-pricing."""
    def go(use_backend):
        interleaved, topo = _pod_cluster()
        prob, inits, streams = _quad_setup(k=3, M=2)
        streams = streams + [QuadStream(prob, 100 + i) for i in range(4)]
        kw = ({"backend": SimBackend(topo)} if use_backend
              else {"network": topo})
        return run_cluster(quad_loss, inits, streams, ACFG,
                           policy="elastic", profiles=interleaved,
                           scenario="spot_churn", fixed_batch=4, **kw)

    pool_a, _, rep_a = go(False)
    pool_b, _, rep_b = go(True)
    assert rep_a.summary() == rep_b.summary()
    assert rep_a.applied_events == rep_b.applied_events
    np.testing.assert_allclose(
        np.asarray(pool_a.global_params["x"]),
        np.asarray(pool_b.global_params["x"]), rtol=0, atol=0)
    # the sim backend never claims measured wire time
    assert rep_b.real_comm_time == 0.0


def test_backend_and_network_are_mutually_exclusive():
    _, inits, streams = _quad_setup()
    with pytest.raises(ValueError, match="not both"):
        run_cluster(quad_loss, inits, streams, ACFG,
                    network=Topology(pods=[["a"], ["b"]], inter_bw=1e5),
                    backend=SimBackend())


def test_sim_backend_rejects_partial_worker_sets():
    with pytest.raises(ValueError, match="partial worker set"):
        SimBackend().outer_reduce([{"x": np.ones(2)}, None])


# ------------------------------------------- participant-tree mapping

def test_participant_tree_prunes_and_collapses():
    profiles = make_rack_profiles([[2, 2], [2, 2]], **TOY)
    topo = Topology.from_profiles(profiles, inter_bw=1e5, pod_bw=1.5e5)
    names = [p.name for p in profiles]
    # full cluster: 2 pods x 2 racks x 2 nodes, fully nested
    assert topo.participant_tree(names) == [
        [["p0r0n0", "p0r0n1"], ["p0r1n0", "p0r1n1"]],
        [["p1r0n0", "p1r0n1"], ["p1r1n0", "p1r1n1"]]]
    # one rack: single-child levels collapse to a flat leaf group
    assert topo.participant_tree(["p0r0n0", "p0r0n1"]) == \
        ["p0r0n0", "p0r0n1"]
    # one node per pod: each pod collapses to its single participating
    # rack's leaf group; the cross-pod level survives
    assert topo.participant_tree(["p0r0n0", "p1r0n1"]) == \
        [["p0r0n0"], ["p1r0n1"]]
    # caller order is preserved inside leaf groups (worker <-> process
    # identification depends on it)
    assert topo.participant_tree(["p0r0n1", "p0r0n0"]) == \
        ["p0r0n1", "p0r0n0"]


# -------------------------------------- JaxProcessBackend, in-process

def test_jax_backend_single_process_matches_sim_bitwise():
    """With one process the real backend's collectives degenerate to
    the identity: the run must match the SimBackend bit-for-bit while
    still exercising the full mesh/shard_map execution path."""
    acfg, inits, streams, profiles, network = launch_mp.fixture(
        1, rounds=3)
    pool, hist, rep = run_cluster(
        launch_mp.quad_loss, inits, streams, acfg, policy="sync",
        profiles=profiles, backend=JaxProcessBackend(network),
        fixed_batch=4)
    ref = run_sim(1, rounds=3)
    np.testing.assert_allclose(
        np.asarray(pool.global_params["x"], np.float64),
        np.asarray(ref["x"]), rtol=0, atol=0)
    assert rep.sim_time == ref["sim_time"]
    assert rep.num_syncs == ref["num_syncs"]
    # measured wire time is recorded per event and in aggregate
    assert rep.real_comm_time > 0.0
    outer = [e for e in pool.comms.log if e["kind"] == "outer"]
    assert outer and all("real_s" in e for e in outer)
    assert pool.comms.total_real_time == pytest.approx(rep.real_comm_time)


def test_jax_backend_validates_unsupported_configs():
    from repro.cluster.runtime import ClusterEvent

    from repro.cluster import make_heterogeneous_profiles

    acfg, inits, streams, profiles, network = launch_mp.fixture(
        1, rounds=2)
    many = make_heterogeneous_profiles(4, **TOY)

    def go(acfg=acfg, inits=inits, streams=streams, profiles=profiles,
           **kw):
        return run_cluster(launch_mp.quad_loss, inits, streams, acfg,
                           profiles=profiles,
                           backend=JaxProcessBackend(network),
                           fixed_batch=4, **kw)

    with pytest.raises(ValueError, match="sync/async"):
        go(policy="elastic")
    with pytest.raises(ValueError, match="one worker per process"):
        go(acfg=dataclasses.replace(acfg, nodes_per_gpu=2),
           streams=streams * 2, profiles=many)
    with pytest.raises(ValueError, match="k=2"):
        go(inits=inits * 2, streams=streams * 2, profiles=many,
           acfg=dataclasses.replace(acfg, num_init_trainers=2))
    with pytest.raises(ValueError, match="elastic in-process pool"):
        go(scenario=[ClusterEvent(time=0.0, kind="join")])

    # multi-trainer pools and merges are supported now: k=2 with
    # enable_merge validates whenever the process count matches the
    # k x M group layout...
    backend = JaxProcessBackend(network)
    backend.num_processes = 2
    merged = dataclasses.replace(acfg, enable_merge=True,
                                 num_init_trainers=2)
    backend.validate(merged, policy="sync", k=2, M=1)
    # ...but adaptive batching still reduces stats over the whole mesh,
    # so it stays k=1-only
    with pytest.raises(ValueError, match="trainer group"):
        backend.validate(dataclasses.replace(merged, adaptive=True),
                         policy="sync", k=2, M=1)


def test_jax_backend_adaptive_validation():
    """Adaptive batching is supported now — but only through the
    composable microbatch estimator when the statistics actually span
    processes (a rank-local per-sample probe would desynchronize the
    batch decision)."""
    acfg, _, _, _, network = launch_mp.fixture(1, rounds=2)
    backend = JaxProcessBackend(network)

    # multi-process + per-sample probe: rejected with a pointed message
    backend.num_processes = 2
    bad = dataclasses.replace(acfg, adaptive=True,
                              stats_estimator="per_sample")
    with pytest.raises(ValueError, match="microbatch"):
        backend.validate(bad, policy="sync", k=1, M=2)
    # multi-process + microbatch estimator: accepted
    ok = dataclasses.replace(acfg, adaptive=True,
                             stats_estimator="microbatch")
    backend.validate(ok, policy="sync", k=1, M=2)
    # single process: every worker is local, both estimators fine
    backend.num_processes = 1
    backend.validate(bad, policy="sync", k=1, M=1)


def test_jax_backend_rejects_autoscale():
    """An ElasticPolicy scripts joins/leaves through the in-process
    elastic pool; a fixed process set cannot honor it.  run_cluster
    already refuses autoscale on the sync/async policies, so the
    backend contract is pinned on validate() directly."""
    from repro.cluster.autoscale import BandAutoscale

    acfg, _, _, _, network = launch_mp.fixture(1, rounds=2)
    backend = JaxProcessBackend(network)
    with pytest.raises(ValueError, match="cannot grow or shrink"):
        backend.validate(acfg, policy="sync", k=1, M=1,
                         autoscale=BandAutoscale())
    backend.validate(acfg, policy="sync", k=1, M=1)  # None: accepted


def test_jax_backend_single_process_predicted_matches_sim_bitwise():
    """k_correct > 1 through the JaxProcessBackend on one process must
    reproduce the SimBackend trajectory bit-for-bit: the predictor is
    pure local float arithmetic, so prediction cannot introduce a
    backend-dependent decision."""
    acfg, inits, streams, profiles, network = launch_mp.fixture(
        1, rounds=6, adaptive=True, k_correct=3)
    pool, hist, rep = run_cluster(
        launch_mp.quad_loss, inits, streams, acfg, policy="sync",
        profiles=profiles, backend=JaxProcessBackend(network))
    ref = run_sim(1, rounds=6, adaptive=True, k_correct=3)
    np.testing.assert_allclose(
        np.asarray(pool.global_params["x"], np.float64),
        np.asarray(ref["x"]), rtol=0, atol=0)
    assert hist.requested_batches == ref["batches"]
    assert hist.modes == ref["modes"]
    # corrections at rounds 1 and 4; the other four rounds predicted
    assert rep.num_stats_syncs == ref["num_stats_syncs"] == 2
    assert rep.num_predicted_rounds == 4


def test_jax_backend_single_process_adaptive_matches_sim_bitwise():
    """Adaptive + switch through the JaxProcessBackend on one process
    must reproduce the SimBackend bit-for-bit: the stats reducer is
    None (all workers local), so the in-process estimator path — and
    therefore the whole batch/plan trajectory — is shared."""
    acfg, inits, streams, profiles, network = launch_mp.fixture(
        1, rounds=4, adaptive=True)
    pool, hist, rep = run_cluster(
        launch_mp.quad_loss, inits, streams, acfg, policy="sync",
        profiles=profiles, backend=JaxProcessBackend(network))
    ref = run_sim(1, rounds=4, adaptive=True)
    np.testing.assert_allclose(
        np.asarray(pool.global_params["x"], np.float64),
        np.asarray(ref["x"]), rtol=0, atol=0)
    assert rep.sim_time == ref["sim_time"]
    assert hist.requested_batches == ref["batches"]
    assert hist.modes == ref["modes"]
    # every adaptive round priced a stats reduction
    assert rep.num_stats_syncs == ref["num_stats_syncs"] > 0


# ------------------------------------- real 2-process differential run

@pytest.mark.mp
def test_two_process_sync_run_matches_sim_and_host_loop():
    """The headline differential guarantee: a 2-process
    JaxProcessBackend sync run — real ``jax.distributed`` collectives —
    must land on the same final parameters as the SimBackend event loop
    AND the legacy ``train_adloco`` host loop, to float tolerance."""
    res = run_mp(2, rounds=6, policy="sync")
    assert res["num_syncs"] == 6 and res["real_comm_time"] > 0.0

    ref = run_sim(2, rounds=6, policy="sync")
    np.testing.assert_allclose(np.asarray(res["x"]), np.asarray(ref["x"]),
                               rtol=0, atol=PARITY_ATOL)
    assert res["sim_time"] == ref["sim_time"]

    acfg, inits, streams, _, _ = launch_mp.fixture(2, rounds=6)
    pool, _ = train_adloco(launch_mp.quad_loss, inits, streams, acfg,
                           fixed_batch=4)
    np.testing.assert_allclose(
        np.asarray(res["x"]),
        np.asarray(pool.global_params["x"], np.float64),
        rtol=0, atol=PARITY_ATOL)


@pytest.mark.mp
def test_two_process_async_run_matches_sim():
    """The async policy's delayed-apply/rebase schedule must survive
    real collectives unchanged: same event order, same numerics."""
    res = run_mp(2, rounds=5, policy="async")
    ref = run_sim(2, rounds=5, policy="async")
    np.testing.assert_allclose(np.asarray(res["x"]), np.asarray(ref["x"]),
                               rtol=0, atol=PARITY_ATOL)
    assert res["sim_time"] == ref["sim_time"]
    assert res["num_syncs"] == ref["num_syncs"]


@pytest.mark.mp
def test_two_process_hierarchical_groups_match_sim():
    """2-pod Topology: the FabricDomain tree maps onto nested mesh axes
    (one process per pod) and the grouped-collective reduction must
    still agree with the simulator."""
    res = run_mp(2, rounds=4, policy="sync", pods=True)
    ref = run_sim(2, rounds=4, policy="sync", pods=True)
    np.testing.assert_allclose(np.asarray(res["x"]), np.asarray(ref["x"]),
                               rtol=0, atol=PARITY_ATOL)
    assert res["sim_time"] == ref["sim_time"]


@pytest.mark.mp
def test_two_process_adaptive_switch_run_agrees():
    """The distributed adaptive headline: a 2-process adaptive + switch
    run — batch stats composed by a real ``lax.pmean`` all-reduce each
    round — must (a) keep every rank on the identical ExecutionPlan
    sequence (the worker asserts cross-rank agreement via allgather and
    exits nonzero on divergence), and (b) land on the SimBackend's
    batch/plan trajectory and final params to the pinned tolerance."""
    res = run_mp(2, rounds=6, policy="sync", adaptive=True)
    ref = run_sim(2, rounds=6, policy="sync", adaptive=True)
    # trajectory identity: same requested batches, same modes -> same
    # plan_execution outputs (a pure function of batch and config)
    assert res["batches"] == ref["batches"]
    assert res["modes"] == ref["modes"]
    assert res["num_stats_syncs"] == ref["num_stats_syncs"] > 0
    # the ramp is real: batches grew and switch mode engaged
    firsts = [b[0] for b in res["batches"]]
    assert firsts[-1] > firsts[0]
    assert any(m == "accum" for ms in res["modes"] for m in ms)
    np.testing.assert_allclose(np.asarray(res["x"]), np.asarray(ref["x"]),
                               rtol=0, atol=PARITY_ATOL)
    # identical batch ints feed identical pure-float pricing
    assert res["sim_time"] == ref["sim_time"]
    assert res["real_comm_time"] > 0.0


@pytest.mark.mp
def test_four_process_two_trainer_merge_matches_sim():
    """The multi-trainer tentpole: 4 processes as k=2 disjoint trainer
    groups — each outer sync a grouped mean over its own group's mesh
    axes, and the MIT merge a *global* weighted psum across groups —
    must land on the SimBackend's params, merge trajectory, and sim
    clock.  At least one merge must actually execute, or the
    cross-group collective path wasn't exercised."""
    res = run_mp(4, rounds=6, policy="sync", k=2, merge=True)
    ref = run_sim(4, rounds=6, policy="sync", k=2, merge=True)
    assert res["merge_events"] == ref["merge_events"]
    assert any(e["kind"] == "merge" for e in res["merge_events"])
    np.testing.assert_allclose(np.asarray(res["x"]), np.asarray(ref["x"]),
                               rtol=0, atol=PARITY_ATOL)
    assert res["sim_time"] == ref["sim_time"]
    assert res["num_syncs"] == ref["num_syncs"]
    assert res["real_comm_time"] > 0.0


@pytest.mark.mp
def test_two_process_trace_digest_matches_sim(tmp_path):
    """The trace layer's lockstep contract: the sim-span trace recorded
    inside a real 2-process run must be digest-identical to the
    SimBackend reference (both backends drive the same deterministic
    event loop with analytic span payloads), while the real backend
    additionally lays measured wall-clock spans on the second clock —
    one per executed collective, each with nonzero duration."""
    from repro.cluster import Trace, validate_perfetto
    out = tmp_path / "mp.perfetto.json"
    res = run_mp(2, rounds=4, policy="async", adaptive=True,
                 trace=str(out))
    ref = run_sim(2, rounds=4, policy="async", adaptive=True, trace=True)
    assert res["trace_digest"] == ref["trace_digest"]
    assert res["overlap_frac"] == ref["overlap_frac"] > 0.0
    assert res["utilization"] == ref["utilization"]
    assert res["real_span_time"] > 0.0
    # the nonblocking contract, on the wall clock: dispatched collective
    # windows (dispatch -> ready) must coincide with measured inner
    # compute — async dispatch is real, not a simulated claim
    assert res["real_overlap_frac"] > 0.0
    # the exported Perfetto file carries both clocks and validates
    data = json.loads(out.read_text())
    assert validate_perfetto(data) == []
    tr = Trace.from_perfetto(data)
    assert tr.sim_digest() == ref["trace_digest"]
    reals = tr.real_spans()
    assert len(reals) == res["num_real_spans"]
    # real-span census: one in-flight window per dispatched outer
    # collective ("piggyback" when the phase-1 stats vector rode along,
    # "outer" otherwise), plus the noted inner-compute windows.  The
    # phase-2 moment reduction is chained onto the piggyback window at
    # fold time, so no standalone "stats" span remains.
    kinds = {}
    for s in reals:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    assert (kinds.get("outer", 0) + kinds.get("piggyback", 0)
            == res["num_syncs"])
    assert kinds.get("piggyback", 0) == res["num_stats_syncs"] > 0
    assert kinds.get("stats", 0) == 0
    assert kinds.get("compute", 0) > 0
    assert all(s.duration > 0.0 for s in reals)


def test_tpu_workers_each_own_one_chip_of_one_slice():
    """Off the CPU, run_mp gives rank r chip r and joins the four
    one-chip processes into one 2x2 slice; other process counts do not
    map onto a v5e host one chip each."""
    envs = launch_mp._tpu_process_env(4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    addresses = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert all(e["TPU_PROCESS_ADDRESSES"] == envs[0]["TPU_PROCESS_ADDRESSES"]
               for e in envs)
    assert [f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs] == addresses
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    with pytest.raises(ValueError, match="--procs"):
        launch_mp._tpu_process_env(2)
