"""repro.cluster — event-driven cluster runtime for AdLoCo, with
pluggable execution backends.

Runs real AdLoCo numerics (the same jitted ``TrainerRound`` primitives
as ``repro.core.adloco``) over heterogeneous nodes, so the paper's
dynamic-workload scenarios — stragglers, congested fabrics, flapping
racks, pod partitions, trainers joining and leaving — can be exercised
and timed.  The division of labor:

* a network model (``NetworkModel`` / ``Topology``) describes **where**
  a collective runs — which fabric domains it crosses and what each
  level's paths cost on the simulated clock;
* an execution backend (``repro.cluster.backend``) supplies **how** it
  executes — in-process arithmetic (``SimBackend``, the default) or
  real multi-process ``jax.lax`` collectives over ``jax.distributed``
  (``JaxProcessBackend``, one OS process per worker, launched by
  ``repro.cluster.launch_mp``);
* the scenario decides **what happens** while it runs.

None of the three may change the numerics: the sync policy is
bit-identical to the legacy host loop under every network model and
backend (CI's ``multiprocess-smoke`` lane pins sim/real parity on every
push).

Quick start::

    from repro.cluster import (Topology, make_rack_profiles, run_cluster)

    # 3-level fabric: 2 pods x 2 racks x 2 nodes
    profiles = make_rack_profiles([[2, 2], [2, 2]], ratio=2.0)
    topo = Topology.from_profiles(profiles, inter_bw=1e5, pod_bw=1.5e5)
    pool, hist, report = run_cluster(loss_fn, inits, streams, acfg,
                                     policy="async", profiles=profiles,
                                     network=topo, eval_fn=eval_fn,
                                     scenario="correlated_pod_failure")
    # hist.sim_time x hist.eval_loss -> time-to-target under the sim clock

Execution backends
------------------
``SimBackend``
    Prices every collective analytically (``comms.
    hierarchical_allreduce_time`` under a ``Topology``, the flat ring
    otherwise) and executes the outer reduction in-process: the
    workers' params go straight into the jitted outer step, which
    averages them.  The default when ``run_cluster`` gets
    a ``network=``; bit-identical to the pre-backend runtime (the
    golden-trace digests pin it).
``JaxProcessBackend``
    One process per worker via ``jax.distributed.initialize`` (gloo CPU
    collectives locally; the same code path NCCL/ICI deployments use).
    Every process runs the identical deterministic event loop, computes
    only its own worker's inner steps, and the outer all-reduce executes
    as a real ``jax.lax.pmean`` — with the pricing ``Topology``'s
    participant-pruned ``FabricDomain`` tree mapped onto nested mesh
    axes, so the reduction lowers to grouped collectives per fabric
    level, exactly where the tree says the hierarchical schedule runs.
    The simulated clock still comes from the network model (reports
    stay comparable across backends); wall-clock measured inside each
    real collective lands in ``ClusterReport.real_comm_time``, per
    event in the comms log (``real_s``), and — when tracing — as
    ``real``-clock spans laid alongside the sim spans.  Scope:
    sync/async policies with ``k >= 1`` trainer groups — each group's
    outer sync is a grouped mean over its own ranks and MIT merges
    execute as real cross-group collectives (see *Three-stage method on
    real collectives* below); elastic join/leave scenarios and the
    autoscaler still need in-process pool surgery and stay
    simulator-only.

The dispatch/handle contract (nonblocking collectives)
------------------------------------------------------
Backends expose the outer sync as a split pair, and the runtime drives
it at two different simulated instants:

* ``dispatch_outer(worker_params, stats_vec=None) -> handle`` is called
  at the collective's *launch* point.  It must start the reduction and
  return without waiting for the result: ``JaxProcessBackend`` enqueues
  the jitted ``pmean`` chain via JAX async dispatch (no
  ``block_until_ready``), so the wire works while the caller keeps
  computing; ``SimBackend`` does no work (the handle just carries the
  workers' params, which the outer step averages).
* ``wait_outer(handle) -> (workers, stats_total_or_None)`` is called at
  the collective's *arrival* (the priced completion event).  It blocks
  until the result is ready, records the true in-flight window
  (dispatch -> ready) as a ``real``-clock span, and hands back the
  sequence of param-shaped pytrees the outer step averages (the sim's
  workers, or a 1-tuple of the reduced mean) plus the SUM-reduced
  phase-1 stats vector when one was fused in.

Dispatch order is part of the lockstep contract: every process reaches
every ``dispatch_outer`` in the same order with the same shapes
(first-time shapes warm up with a blocking lockstep execution).  Under
``policy="async"`` the runtime dispatches round ``r``'s outer sync and
immediately starts round ``r+1``'s inner steps — the overlap is now a
measured wall-clock fact (``Trace.overlap_fraction(clock="real")``),
not just the simulated schedule's claim.  Handles may be abandoned
without ``wait_outer`` only where preemption can cancel a trainer
mid-flight, which the simulator-only policies are the only ones to
allow — sim handles are plain data and safe to drop.

Piggybacked stats (payload layout)
---------------------------------
Under ``policy="async"`` with ``acfg.adaptive=True`` the runtime does
not pay a standalone gradient-order stats collective: the phase-1
``[colsum, count]`` vector (``n + 1`` floats for an ``n``-param model)
rides the next outer dispatch as ONE fused collective — traced and
priced as kind ``"piggyback"`` with ``payload_bytes = params_bytes +
stats_payload_bytes``, counted in ``num_stats_syncs``.  On
``JaxProcessBackend`` the fused tree is ``{"params": <(1, ...) pytree>,
"stats": <(1, n+1) float32>}`` reduced by the same ``pmean`` chain; the
phase-2 five scalar moments chain onto the same in-flight window: the
dispatch derives the global mean gradient from the enqueued phase-1
buffers without blocking and enqueues the five-moment reduction as a
second collective on the same handle, which the outer wait collects
alongside the params — no standalone fold-time ``stats`` collective
remains on the wire.  The batch decision folds at the
fused collective's arrival — one round stale, exactly the
``BatchPlanProtocol`` semantics every rank already agrees on.
Sync/elastic policies keep the inline gated stats path, preserving
bit-parity with the legacy host loop.

``python -m repro.cluster.launch_mp --procs 2 --rounds 1 --check`` is
the zero-to-parity smoke: it spawns the processes, runs the canonical
quadratic through the real backend, and asserts the final parameters
match the simulator; add ``--adaptive`` for the batch-ramp variant
(trajectory parity included).

Three-stage method on real collectives (multi-trainer MIT)
----------------------------------------------------------
With ``--k K`` the process set splits into ``K`` disjoint trainer
groups: trainer ``t`` owns the contiguous rank block ``[t*M, (t+1)*M)``
where ``M = P / K`` (``validate`` rejects anything that doesn't divide
evenly).  The device mesh grows a leading ``"t"`` axis over the
groups, with the fabric axes nested inside it whenever every group's
participant-pruned ``FabricDomain`` tree has the same shape (one flat
row per group otherwise).  Grouped reductions never name ``"t"``, so
each trainer's outer sync is a ``lax.pmean`` chain over its *own*
block only — ``K`` independent DiLoCo instances sharing one mesh, one
lockstep event loop, and one wire.

MIT merges (and the final consolidate) are the one place groups talk
to each other, and they execute as real cross-group collectives
(``merge_reducer``): each member rank contributes its trainer's
replica scaled by ``weight / M`` (the M group ranks split the group's
share), non-member ranks contribute zeros of the same shape, and a
single global ``psum`` folds both the weighted parameter sum and the
total-weight row; the division yields Algorithm 2's batch-weighted
average replicated on every rank.  The merge is priced on the sim
clock exactly as the ``SimBackend`` prices it (so ``--check`` parity
covers the merged params, the merge applied-events, and the sim-span
trace digest), while the measured wall time lands in
``real_comm_time`` and as a ``real``-clock ``merge`` span.
``merge_drift_window`` gating, survivor bookkeeping and stream unions
stay host-side pool surgery — identical on both backends because it is
pure rank-indexed group-membership arithmetic over the same
deterministic loop.

``validate`` still rejects elastic join/leave scenario events, the
autoscaler, and ``adaptive`` with ``k > 1``: the first two resize the
pool mid-run (cross-process pool surgery — remapping live ranks
between groups — is not built yet), and the stats protocol reduces
over the whole fabric rather than per trainer group, so adaptive
multi-trainer pools would feed every group the union statistics.
``python -m repro.cluster.launch_mp --procs 4 --k 2 --rounds 6
--merge --check`` is the multi-trainer smoke (CI runs it): two
2-process trainers, at least one executed merge, float parity with the
simulator end to end.

Distributed adaptive batching (the stats-reduction protocol)
------------------------------------------------------------
Adaptive batching + switch mode run end-to-end on both backends.  The
coordination problem — per-rank batch statistics would desynchronize
the compiled shapes — is solved by a shape-agreement protocol
(``repro.core.adloco.BatchPlanProtocol`` over ``repro.core.batching.
distributed_stats``): the five sufficient statistics of the batching
tests are *additive* given the global mean gradient, so each rank's
worker contributes its microbatch-mean gradient rows and two
all-reduces — the gradient-sized ``[colsum, count]`` vector, then the
five scalar moments — hand every rank bit-identical ``GradStats``.  The requested batch and the
``ExecutionPlan`` are pure functions of those values and the shared
config, so every rank compiles the same shapes each round without
further coordination.  Under the ``SimBackend`` the reduction is
in-process (bit-identical to the legacy host loop); under the
``JaxProcessBackend`` both phases execute as real ``lax.pmean``\\ s
over the fabric mesh (``stats_estimator="microbatch"`` required — the
per-sample probe is rank-local and stays rejected).  The runtime
prices every stats reduction as a collective over the trainer's nodes
(``ClusterReport.num_stats_syncs``; duration inside ``comm_time``),
re-priced at fabric window edges like any in-flight collective, and
batch growth feeds the per-node roofline compute — so sync, async and
elastic all experience the ramp on the clock, not just in the
numerics.  Async runs fuse phase 1 onto the outer sync (see
*Piggybacked stats* above), so adaptive rounds there pay one
gradient-order collective, not two.

Reporting & tracing
-------------------
Three tiers, cheapest first:

``ClusterReport``
    Aggregate scalars, always on: ``sim_time``, ``comm_time``,
    ``num_syncs``, per-round logs, ``applied_events``.
    ``report.summary()`` is the golden-digest surface — byte-stable
    across PRs; ``report.summary(extended=True)`` adds the opt-in
    fields (``real_comm_time``, ``num_stats_syncs``, and — when the
    run was traced — ``utilization``, ``blocked_frac``, ``idle_frac``,
    ``overlap_frac``) without perturbing the default dict.
``Trace`` (``repro.cluster.trace``)
    The structured tier: ``run_cluster(..., trace=Trace())`` makes the
    event loop record one typed span per inner-compute block, outer
    collective, stats reduction, join transfer and fabric window, plus
    instant annotations (re-pricings, joins, leaves, merges,
    slowdowns).  Strictly opt-in — with the default ``trace=None``
    nothing is allocated and scheduling is untouched (the golden
    digests pin that).  Derived metrics: ``trace.utilization()`` — a
    per-trainer busy / comm-blocked / idle ledger asserted to
    partition each trainer's alive window exactly — and
    ``trace.overlap_fraction()`` — collective in-flight time
    coincident with the same trainer's compute over total collective
    time, the ROADMAP item-1 gate (sync scores exactly 0.0; async > 0
    wherever an outer all-reduce hides behind compute).  On the real
    backend, wall-clock spans measured inside each executed collective
    land in the same trace on a second clock (``launch_mp --trace``).
``trace.to_perfetto()`` / ``repro.cluster.trace_report``
    The export tier: Chrome-trace/Perfetto JSON (load in
    https://ui.perfetto.dev), with exact-seconds endpoints embedded so
    ``Trace.from_perfetto`` round-trips digest-identically.  ``python
    -m repro.cluster.trace_report trace.json`` prints the ledger,
    overlap breakdown and longest spans; ``--validate`` is the CI
    schema gate.  ``cluster_bench`` rows carry ``utilization`` and
    ``overlap_frac`` columns derived the same way.

Network models
--------------
``NetworkModel``
    The flat baseline: every collective is one ring over the global
    min-bandwidth link.
``Topology``
    An n-level tree of ``FabricDomain``\\ s (rack -> pod -> cluster, to
    any depth).  Leaf domains hold nodes — their links are the nodes'
    own ``link_bw`` — and each internal domain joins its children with
    explicit per-path bandwidth/latency.  Collectives are priced by
    ``core.comms.hierarchical_allreduce_time``: ring reduce-scatter
    inside every leaf group, reduce-scatters of the surviving shards up
    the internal levels, a concurrent shard ring across the top
    bottleneck, and the mirror-image all-gathers back down.  Build one
    from the classic two-level spelling (``pods`` + ``inter_bw``; prices
    bit-identically to the old pod-only model), from profile attributes
    (``from_profiles``; pass ``pod_bw`` to get rack/pod/cluster from
    ``NodeProfile.pod``/``.rack``), or hand ``tree=`` an explicit
    ``FabricDomain``.

Every domain carries its own time-varying ``FabricSchedule``: scenarios
open ``FabricWindow``\\ s — bandwidth scaled by ``bw_scale``, hops
paying ``extra_latency`` — scoped to ``"all"``, the leaf level
(``"intra"``), every internal level (``"inter"``), one level
(``"level:<k>"``, 0 = leaves), one named domain
(``"domain:<name>"``), or one named domain's *uplink edge* into its
parent (``"edge:<name>"`` — per-path fabric asymmetry: only
collectives and transfers whose routes cross that edge pay, the
siblings' paths stay nominal), so a window can hit one rack's links
without touching the rest of the fabric.  The runtime re-prices in-flight
collectives *and* join-time parameter transfers at every window edge
(fraction done credited, remainder re-costed).

Scenario registry
-----------------
``repro.cluster.scenarios`` holds named, deterministic generators that
compile to ``ClusterEvent`` streams; ``run_cluster(scenario="<name>")``
accepts them directly, so benchmarks and the golden-trace tests in
``tests/test_scenarios.py`` exercise identical event streams.
Registered: ``baseline`` (no events), ``bursty_congestion`` (periodic
congestion windows), ``spot_churn`` (seeded Poisson leave events each
followed by a rejoin), ``pod_partition`` (cross-pod links drop to
``residual`` bandwidth), ``flash_crowd_join`` (``joins`` trainers
landing every ``spacing`` seconds), and four co-scripted generators
that couple node dynamics with fabric windows:
``correlated_pod_failure`` (a pod's nodes slow down *and* the fabric
joining pods degrades, together), ``diurnal_congestion`` (piecewise-
constant cosine bandwidth schedule), ``rack_flap`` (one named rack
domain's level-0 fabric oscillates) and ``straggler_cascade``
(staggered node slowdowns inside an open congestion window), plus
``drifted_merge`` (one trainer slowed until it drifts past the merge
window, pinning the skip-the-laggard merge semantics).  The
adaptive arms ``adaptive_ramp`` (clean fabric; the ramp lives in the
config) and ``congested_adaptive`` (a deep congestion window colliding
with the middle of the batch ramp) are meant to run with
``acfg.adaptive=True``; ``autoscale_ramp`` (clean fabric, no scripted
events — the pool dynamics come from the autoscale policy) and
``preemption_storm_growth`` (scripted leaves landing mid-ramp, so the
policy must rebuild the pool it just lost) are meant to run with
``autoscale=`` as well.  ``build_scenario`` compiles a name into a
:class:`~repro.cluster.scenarios.Scenario` record — ``(name, knobs,
events)`` — that ``run_cluster`` accepts anywhere a plain event list
works and threads into ``summary(extended=True)["scenario"]``.  See
the generator docstrings for knob semantics; register new ones with
``scenarios.register_scenario``.

Autoscaling
-----------
AdLoCo's batch tests grow the requested global batch roughly
exponentially, so a fixed pool's gradients-per-worker grows with it.
``run_cluster(..., policy="elastic", autoscale=BandAutoscale(...))``
(or ``ClusterSpec(autoscale=...)``) closes the loop the adadamp way: an
:class:`~repro.cluster.autoscale.ElasticPolicy` observes every round
boundary's decided batch and scripts ``join``/``leave`` events through
the same elastic machinery scenarios use — scale-ups pay real
``point_to_point_time`` parameter transfers (re-priced at fabric-window
edges), joiners inherit the source trainer's requested batch, and each
trainer executes only its ``ceil(requested_batch / pool_size)`` share
while the batch *decision* keeps tracking the full requested batch.
Actions land in ``applied_events`` (kind ``"autoscale"``, with the
observed gradients-per-worker), ``ClusterReport.num_autoscale_events``,
and fabric-lane trace instants; joins that exhaust the spare pool
record a ``"join_skipped"`` event instead of failing silently.  The
reference policy :class:`~repro.cluster.autoscale.BandAutoscale` holds
gradients-per-worker inside a ``[lo, hi]`` hysteresis band with a
cooldown between actions.  Pair it with ``acfg.k_correct > 1``
(PadaDamp-style predicted growth: the exact gradient-order stats
reduction runs every ``k_correct`` rounds and the fitted exponential
trajectory fills the rounds between, cutting stats collectives by
~``k_correct``x) to co-scale the fleet against a mostly-predicted
batch trajectory.  ``History.eval_loss_pool`` tracks the
batch-weighted pool-average parameters (what ``consolidate`` would
return) so time-to-target comparisons see the whole fleet, not one
anchor trainer.

Which sync policy should I use?
-------------------------------
``sync``
    Barrier semantics identical to the legacy ``train_adloco`` loop.
    Use it as the ground-truth baseline: with merging disabled the
    parameter trajectory is bit-identical to the host loop — under any
    topology or fabric schedule — so any simulated-time comparison is
    apples-to-apples.  Pick it when the network is fast relative to a
    round (comm « compute) or when you need exactly reproducible
    numerics.
``async``
    ACCO-style overlap: workers keep accumulating inner steps while the
    outer all-reduce is in flight; the delayed pseudo-gradient applies
    on arrival and workers rebase, keeping in-flight progress.  Pick it
    when outer syncs are expensive — congested or partitioned fabrics,
    slow cross-pod bottlenecks, large models, high heterogeneity.
    Expect a small loss-trajectory perturbation (one round of delay) in
    exchange for hiding comm time entirely.  High outer Nesterov
    momentum (0.9) is underdamped under the one-round staleness; set
    ``acfg.delay_compensation=True`` and the outer step scales the
    momentum by the *measured* staleness of each applied
    pseudo-gradient (``mu / (1 + delay)`` — 0.9 behaves like 0.45 at
    the async steady-state delay of one round, and sync runs, at delay
    0, are untouched), so the previously diverging configs converge
    (``tests/test_cluster.py`` pins the regression).
``elastic``
    ``async`` plus scripted :class:`ClusterEvent`\\ s — trainers leave
    (folded into the pool via ``mit.do_merge``) and join (cloned from
    the most-advanced trainer onto spare nodes/streams).  Pick it to
    study preemptible/spot capacity and pool-size dynamics; pass extra
    streams and profiles beyond k*M to give joiners somewhere to land.
    Merges are round-tagged and fire on time: a trainer whose round
    counter has drifted behind the merge round by
    ``acfg.merge_drift_window`` or more is *skipped* (recorded in the
    applied event's ``skipped`` list) rather than stalling the merge
    and folding rounds-stale params into the pool.

``benchmarks/cluster_bench.py`` compares sync/async under 1x/2x/4x node
heterogeneity, across registered scenarios on a 2-pod topology, and
across the co-scripted scenarios on a 3-level rack/pod/cluster fabric;
``examples/heterogeneous_cluster.py`` is the narrated tour.
"""
from repro.cluster.autoscale import BandAutoscale, ElasticPolicy
from repro.cluster.backend import (CollectiveBackend, JaxProcessBackend,
                                   SimBackend)
from repro.cluster.network import (FABRIC_SCOPES, CommDomain, FabricDomain,
                                   FabricSchedule, FabricWindow,
                                   NetworkModel, Topology)
from repro.cluster.node import (NodeProfile, Slowdown, interleave_pods,
                                make_heterogeneous_profiles,
                                make_pod_profiles, make_rack_profiles)
from repro.cluster.runtime import (POLICIES, ClusterEvent, ClusterReport,
                                   ClusterSpec, run_cluster)
from repro.cluster.scenarios import (SCENARIOS, Scenario, build_scenario,
                                     list_scenarios, register_scenario)
from repro.cluster.trace import (Span, Trace, TraceEvent,
                                 validate_perfetto)

__all__ = [
    "FABRIC_SCOPES", "POLICIES", "SCENARIOS", "BandAutoscale",
    "ClusterEvent", "ClusterReport", "ClusterSpec", "CollectiveBackend",
    "CommDomain", "ElasticPolicy", "FabricDomain", "FabricSchedule",
    "FabricWindow", "JaxProcessBackend", "NetworkModel", "NodeProfile",
    "Scenario", "SimBackend", "Slowdown", "Span", "Topology", "Trace",
    "TraceEvent", "build_scenario", "interleave_pods", "list_scenarios",
    "make_heterogeneous_profiles", "make_pod_profiles",
    "make_rack_profiles", "register_scenario", "run_cluster",
    "validate_perfetto",
]
