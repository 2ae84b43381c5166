"""Pluggable execution backends for the cluster runtime.

``Topology`` describes *where* a hierarchical all-reduce runs — which
fabric domains a collective crosses and what each level's paths cost.
A :class:`CollectiveBackend` supplies *how*: the same runtime event loop
drives either

:class:`SimBackend`
    The default.  Collectives are *priced* analytically (delegating to
    the wrapped :class:`~repro.cluster.network.NetworkModel` /
    :class:`~repro.cluster.network.Topology`) and *executed* locally —
    the workers' params go straight into the jitted outer step, which
    averages them.  Behavior is bit-identical to the pre-backend
    runtime; the golden-trace suite pins that.
:class:`JaxProcessBackend`
    One OS process per worker via ``jax.distributed.initialize`` (see
    ``repro.cluster.launch_mp``): every process runs the *same*
    deterministic event loop, computes only its own worker's inner
    steps, and the outer reduction executes as a real ``jax.lax``
    collective across processes.  The simulated clock still comes from
    the analytic network model (so reports stay comparable), while the
    wall-clock actually spent inside each collective is recorded
    separately (``ClusterReport.real_comm_time`` and per-event
    ``real_s``).  When the pricing network is a
    ``Topology``, the participant-pruned :class:`~repro.cluster.network.
    FabricDomain` tree is mapped onto nested mesh axes, so the reduction
    lowers to grouped all-reduces per fabric level — intra-leaf process
    groups first, then the cross-domain groups, exactly where the tree
    says the hierarchical schedule runs (unbalanced participant trees
    fall back to one flat group).

Lockstep contract (distributed backends): every process must pop the
same events in the same order, so collectives launch identically
everywhere.  That holds because pricing is pure float arithmetic on
state every process replicates (profiles, network, scenario).
Adaptive batching joins the contract through the batch-stats all-reduce
(:meth:`CollectiveBackend.stats_reducer`): each rank contributes its
worker's gradient rows to the exact two-phase composition of
``repro.core.batching.distributed_stats`` — executed here as real
``lax.pmean``\\ s over the fabric mesh — so every rank derives the
identical requested batch and compiled shapes from the identical
reduced statistics (``repro.core.adloco.BatchPlanProtocol``).
Multi-trainer pools (MIT, paper §4.1) map onto *disjoint process
groups*: with ``k > 1`` trainers of ``M`` workers each, the mesh gains
a leading ``"t"`` axis indexing the groups and the fabric axes only
ever appear in grouped reductions, so each trainer's outer sync is a
``lax.pmean`` over its own workers and nothing else.  ``do_merge`` /
``consolidate`` become real *cross-group* collectives through
:meth:`CollectiveBackend.merge_reducer`: members contribute their
trainer's weighted replica, a global ``psum`` over every axis folds
numerator and total weight, and the result lands replicated on every
rank (which is also what repairs non-member replicas after pool
contraction).  :meth:`JaxProcessBackend.validate` still rejects what
would let processes diverge: the rank-local per-sample probe estimator
(its statistics live on one rank's params; use the composable
``stats_estimator="microbatch"``), elastic joins/leaves and
autoscaling (the process set cannot grow or shrink mid-run), and
adaptive batching over ``k > 1`` (the stats reductions are global,
not per-group).
"""
from __future__ import annotations

import copy
import time
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.cluster.network import NetworkModel
from repro.cluster.node import NodeProfile


class CollectiveBackend:
    """Protocol: pricing (simulated clock) + execution (numerics).

    Pricing methods mirror the network-model interface so the runtime
    can stay network-agnostic; execution methods carry the actual
    parameter movement.  ``outer_reduce`` must return a sequence of
    param-shaped pytrees for ``repro.core.diloco.make_outer_step`` to
    average inside its program — either the M workers' params
    themselves (sim) or a 1-tuple holding the already-reduced mean
    (real collectives).  No worker-stacked array is built.
    """

    name = "abstract"

    # ------------------------------------------------------------ setup
    def for_run(self) -> "CollectiveBackend":
        """Per-run copy of the mutable pricing state (the runtime opens
        fabric windows and the sim draws jitter); process-level handles
        (meshes, distributed clients) are shared, not copied."""
        raise NotImplementedError

    def bind(self, profiles: Sequence[NodeProfile]) -> None:
        """Associate the run's node profiles (index i = worker i)."""

    def validate(self, acfg, *, policy: str, k: int, M: int,
                 scenario: Sequence[Any] = (),
                 autoscale: Optional[Any] = None) -> None:
        """Reject configurations this backend cannot execute."""

    def attach_trace(self, trace) -> None:
        """Record *wall-clock* spans for executed collectives into
        ``trace`` (see ``repro.cluster.trace``).  Pricing-only backends
        ignore it — the runtime records the simulated spans itself."""

    # ---------------------------------------------------------- pricing
    def allreduce_time(self, payload_bytes: float,
                       nodes: Sequence[NodeProfile], *,
                       now: float = 0.0) -> float:
        raise NotImplementedError

    def point_to_point_time(self, payload_bytes: float, src: NodeProfile,
                            dst: NodeProfile, *, now: float = 0.0) -> float:
        raise NotImplementedError

    def add_fabric_window(self, start: float,
                          duration: Optional[float] = None, *,
                          bw_scale: float = 1.0, extra_latency: float = 0.0,
                          scope: str = "all") -> None:
        raise NotImplementedError

    def fabric_change_points(self) -> List[float]:
        return []

    # -------------------------------------------------------- execution
    def local_workers(self, M: int, *,
                      tid: Optional[int] = None) -> Optional[List[int]]:
        """Worker indices this process computes for trainer ``tid``;
        None means all (the single-process sim).  Multi-group backends
        return ``[]`` on ranks outside the trainer's group — those
        ranks still participate in its collectives (lockstep), they
        just contribute nothing."""
        return None

    def outer_reduce(self, worker_params: List[Any]) -> Any:
        """List of per-worker pytrees (None for workers that live on
        other processes) -> sequence of param-shaped pytrees whose mean
        is the workers' mean."""
        raise NotImplementedError

    # ------------------------------------------- dispatch/handle split
    #
    # The nonblocking contract: ``dispatch_outer`` *starts* the outer
    # collective (optionally fused with the phase-1 batch-stats vector —
    # Lau-style piggybacking) and returns an opaque handle immediately;
    # ``wait_outer`` blocks until the wire work is done, records the
    # *true in-flight window* (dispatch -> ready) as the measured
    # wall-clock span, and returns the results.  The runtime dispatches
    # at the sim's launch point and waits at the rebase/fold point, so
    # the next round's inner steps run while the collective is in
    # flight.  Every rank reaches both calls in the same (lockstep)
    # event order, so dispatch order is identical everywhere.  Handles
    # are per-trainer: with k > 1 groups (or async stats) several can
    # be in flight together, dispatched in lockstep order.  A handle
    # abandoned by preemption (a merge superseding an in-flight sync)
    # is safe to drop on real backends too: the collective was already
    # enqueued on *every* rank at dispatch, so nobody blocks on a
    # missing partner — the result is simply never read.

    def dispatch_outer(self, worker_params: List[Any], *,
                       stats_vec: Optional[Any] = None,
                       phase2: Optional[dict] = None,
                       tid: Optional[int] = None,
                       template: Optional[Any] = None) -> Any:
        """Start the outer reduction; with ``stats_vec`` (the phase-1
        ``[colsum, b]`` f32 vector) the collective is fused: one wire
        operation reduces both payloads.  ``phase2`` (the deferred
        stats request carrying ``G_local``/``micro``) lets a real
        backend chain the five-moment phase-2 reduction onto the same
        in-flight window — the summed moments surface later through
        :meth:`pop_phase2_total`.  ``tid``/``template`` support
        multi-group backends: ranks outside trainer ``tid``'s group
        contribute zeros shaped like ``template`` (their group's
        result is discarded).  Returns an opaque handle."""
        raise NotImplementedError

    def wait_outer(self, handle) -> tuple:
        """Block on a :meth:`dispatch_outer` handle.  Returns
        ``(workers, stats_total)``: the sequence of param-shaped
        pytrees :meth:`outer_reduce` would return (the workers' params,
        or a 1-tuple of their already-reduced mean), and the SUM-reduced
        phase-1 vector (None when no ``stats_vec`` was fused)."""
        raise NotImplementedError

    def note_real_compute(self, t0: float, dt: float, *,
                          tid: int = 0) -> None:
        """Record a wall-clock inner-compute window (perf_counter
        origin) so real-clock overlap is measurable against the
        in-flight collective spans.  Pricing-only backends ignore it."""

    def mean_scalar(self, value: float, *,
                    tid: Optional[int] = None) -> float:
        """Mean of a per-process scalar over trainer ``tid``'s workers
        (loss logging); identity on single-process backends.  Every
        rank calls it (lockstep) and receives the group's mean."""
        return value

    def merge_reducer(self):
        """Callable executing :func:`repro.core.mit.do_merge` /
        ``consolidate`` averages as a real cross-group collective —
        ``reduce(trainers, weights, *, kind, tid)`` returning the
        weighted parameter average replicated on every rank — or None
        when the pool lives in one process (the in-process
        ``merge_params`` already sees every replica)."""
        return None

    def pop_phase2_total(self) -> Optional[Any]:
        """Summed phase-2 moments vector from a fused
        :meth:`dispatch_outer` ``phase2`` chain (cleared on read), or
        None when the backend finished no fused phase-2."""
        return None

    def stats_reducer(self):
        """SUM all-reduce of a small 1-D f32 vector over every
        process, for the adaptive batch-stats composition — or None
        when all workers live in this process (the in-process
        estimators already see every shard)."""
        return None

    def broadcast_params(self, params: Any) -> Any:
        """Coordinator's params on every process (init sync / joins)."""
        return params

    def pop_measured(self) -> Optional[float]:
        """Wall-clock seconds the last ``outer_reduce`` actually spent
        on the wire, or None for backends that only price."""
        return None

    def pop_stats_measured(self) -> Optional[float]:
        """Wall-clock seconds the last stats reduction spent on the
        wire, or None for backends that only price.  A separate slot
        from :meth:`pop_measured`: under async policies a stats
        reduction and an outer collective can be in flight together."""
        return None

    def pop_merge_measured(self) -> Optional[float]:
        """Wall-clock seconds the last merge/consolidate collective
        spent on the wire, or None for backends that only price."""
        return None


class SimBackend(CollectiveBackend):
    """Analytic pricing + in-process execution — the classic runtime.

    Wraps a :class:`NetworkModel` or :class:`Topology` for the clock and
    stacks worker params locally for the numerics.  ``for_run`` deep-
    copies the network so caller-owned fabric schedules stay reusable
    (the same contract ``run_cluster`` has always had).
    """

    name = "sim"

    def __init__(self, network: Optional[NetworkModel] = None):
        self.network = network if network is not None else NetworkModel()

    def for_run(self) -> "SimBackend":
        return SimBackend(copy.deepcopy(self.network))

    # ---------------------------------------------------------- pricing
    def allreduce_time(self, payload_bytes, nodes, *, now=0.0):
        return self.network.allreduce_time(payload_bytes, nodes, now=now)

    def point_to_point_time(self, payload_bytes, src, dst, *, now=0.0):
        return self.network.point_to_point_time(payload_bytes, src, dst,
                                                now=now)

    def add_fabric_window(self, start, duration=None, *, bw_scale=1.0,
                          extra_latency=0.0, scope="all"):
        if not hasattr(self.network, "add_fabric_window"):
            raise ValueError(
                f"network model {type(self.network).__name__} does not "
                f"support fabric events")
        self.network.add_fabric_window(start, duration, bw_scale=bw_scale,
                                       extra_latency=extra_latency,
                                       scope=scope)

    def fabric_change_points(self):
        if hasattr(self.network, "fabric_change_points"):
            return self.network.fabric_change_points()
        return []

    # -------------------------------------------------------- execution
    def outer_reduce(self, worker_params):
        if any(wp is None for wp in worker_params):
            raise ValueError("SimBackend executes every worker in-process;"
                             " got a partial worker set")
        return tuple(worker_params)

    def dispatch_outer(self, worker_params, *, stats_vec=None,
                       phase2=None, tid=None, template=None):
        # The sim's "wire" is the priced clock, not real time: the handle
        # is just the workers' params, averaged inside the outer step.
        # A fused stats_vec reduces over the one process = identity sum;
        # phase2/tid/template are multi-process concerns (the sim holds
        # every worker and every trainer in-process).
        stats = None if stats_vec is None else jnp.asarray(stats_vec,
                                                           jnp.float32)
        return (self.outer_reduce(worker_params), stats)

    def wait_outer(self, handle):
        return handle


class JaxProcessBackend(CollectiveBackend):
    """Real multi-process execution over ``jax.distributed``.

    Construct *after* ``jax.distributed.initialize`` (see
    ``repro.cluster.launch_mp``, which spawns one CPU process per worker
    and elects process 0 as coordinator).  Worker m lives on process m;
    the outer reduction is a jitted ``shard_map`` whose mesh axes follow
    the pricing ``Topology``'s participant-pruned domain tree, so the
    per-axis ``lax.pmean`` chain lowers to grouped all-reduces per
    fabric level (leaf siblings first, bottleneck level last).  With a
    flat :class:`NetworkModel` — or an unbalanced participant tree — the
    mesh is one flat axis and the reduction a single all-reduce.
    Multi-trainer pools (``k > 1``) prepend a trainer-group axis:
    trainer t's workers are the rank block ``[t*M, (t+1)*M)``, outer
    syncs are grouped means over the fabric axes only, and merges run
    as global weighted psums (see :meth:`merge_reducer`).

    The analytic network still prices the simulated clock (reports stay
    comparable across backends); the wall-clock each collective actually
    took flows to ``ClusterReport.real_comm_time`` via
    :meth:`pop_measured`.  Works single-process too
    (``jax.process_count() == 1``): the mesh is this process's device
    and every collective degenerates to the identity, which is what the
    in-process smoke tests exercise.
    """

    name = "jax"

    def __init__(self, network: Optional[NetworkModel] = None):
        self.network = network if network is not None else NetworkModel()
        self.num_processes = jax.process_count()
        self.rank = jax.process_index()
        self._k = 1                  # trainer groups (validate sets it)
        self._M = 1                  # workers per group
        self._last_measured: Optional[float] = None
        self._last_stats_measured: Optional[float] = None
        self._last_merge_measured: Optional[float] = None
        self._last_phase2: Optional[Any] = None
        self._profiles: Optional[List[NodeProfile]] = None
        self._mesh = None
        self._axes: Optional[tuple] = None
        self._group_axes: Optional[tuple] = None
        self._reduce_jit = None
        self._allsum_jit = None
        self._warm: set = set()      # (shape, dtype) combos already compiled
        self._trace = None           # wall-clock span sink (attach_trace)
        self._trace_origin = 0.0     # perf_counter at attach -> span t=0

    def for_run(self) -> "JaxProcessBackend":
        run = object.__new__(JaxProcessBackend)
        run.__dict__.update(self.__dict__)
        run.network = copy.deepcopy(self.network)
        return run

    def bind(self, profiles):
        self._profiles = list(profiles)
        self._mesh = None            # topology of the run may differ

    def attach_trace(self, trace):
        """Wall-clock spans for every executed collective land in
        ``trace`` on the ``real`` clock, timestamped relative to the
        attach point (run start) — laid alongside the runtime's sim
        spans so simulated and measured wire time are comparable per
        collective."""
        self._trace = trace
        self._trace_origin = time.perf_counter()

    def _record_real(self, kind: str, t0: float, dt: float,
                     tid: int = 0) -> None:
        if self._trace is not None:
            rel = t0 - self._trace_origin
            self._trace.begin(tid, kind, rel, rel + dt, clock="real",
                              rank=self.rank)

    def validate(self, acfg, *, policy, k, M, scenario=(), autoscale=None):
        P = self.num_processes
        if policy not in ("sync", "async"):
            raise ValueError(
                f"JaxProcessBackend supports the sync/async policies, "
                f"not {policy!r} (elastic pools mutate in-process state)")
        if autoscale is not None:
            raise ValueError(
                "autoscaling scripts joins/leaves through the elastic "
                "in-process pool; JaxProcessBackend cannot grow or "
                "shrink its process set mid-run")
        if k * M != P:
            if k == 1:
                raise ValueError(
                    f"one worker per process: nodes_per_gpu={M} but "
                    f"{P} processes are initialized")
            raise ValueError(
                f"one worker per process: k={k} trainers x "
                f"nodes_per_gpu={M} need {k * M} processes, but "
                f"{P} are initialized")
        if acfg.adaptive and k != 1:
            raise ValueError(
                "adaptive batching reduces its statistics over the whole "
                "mesh, not per trainer group; multi-trainer (k > 1) pools "
                "run fixed-batch on JaxProcessBackend")
        if acfg.adaptive and P > 1 and acfg.stats_estimator != "microbatch":
            raise ValueError(
                "distributed adaptive batching composes each rank's "
                "microbatch-mean gradients through the stats all-reduce; "
                "the per-sample probe estimator is rank-local and would "
                "desynchronize the batch decision — run with "
                "stats_estimator='microbatch'")
        bad = {e.kind for e in scenario} & {"join", "leave"}
        if bad:
            raise ValueError(f"scenario events {sorted(bad)} need the "
                             f"elastic in-process pool")
        self._k = int(k)
        self._M = int(M)
        self._mesh = None            # group structure may have changed

    def _member(self, tid: Optional[int]) -> bool:
        """Rank-indexed group membership: trainer ``tid``'s workers are
        the contiguous rank block ``[tid*M, (tid+1)*M)``.  Pool surgery
        (merges) never moves ranks between groups — a merged-away
        trainer's ranks simply stop being members of any live tid."""
        if self._k == 1 or tid is None:
            return True
        return self.rank // self._M == tid

    # ---------------------------------------------------------- pricing
    def allreduce_time(self, payload_bytes, nodes, *, now=0.0):
        return self.network.allreduce_time(payload_bytes, nodes, now=now)

    def point_to_point_time(self, payload_bytes, src, dst, *, now=0.0):
        return self.network.point_to_point_time(payload_bytes, src, dst,
                                                now=now)

    def add_fabric_window(self, start, duration=None, *, bw_scale=1.0,
                          extra_latency=0.0, scope="all"):
        self.network.add_fabric_window(start, duration, bw_scale=bw_scale,
                                       extra_latency=extra_latency,
                                       scope=scope)

    def fabric_change_points(self):
        return self.network.fabric_change_points()

    # ------------------------------------------------------------- mesh
    def _balanced_shape(self, ptree):
        """(level shape, flat name order) of a participant tree if every
        sibling subtree has the same shape, else None -> flat mesh."""
        if ptree and all(isinstance(x, str) for x in ptree):
            return (len(ptree),), list(ptree)
        subs = [self._balanced_shape(c) for c in ptree]
        if any(s is None for s in subs):
            return None
        shapes = {s for s, _ in subs}
        if len(shapes) != 1:
            return None
        shape, _ = subs[0]
        return ((len(ptree),) + shape,
                [nm for _, order in subs for nm in order])

    def _build_mesh(self):
        import numpy as np
        from jax.sharding import Mesh

        if self._profiles is None:
            raise RuntimeError("backend not bound to profiles yet")
        P = self.num_processes
        names = [p.name for p in self._profiles[:P]]
        proc_of = {nm: i for i, nm in enumerate(names)}
        if self._k == 1:
            shape, order = (len(names),), list(names)
            if hasattr(self.network, "participant_tree"):
                spec = self._balanced_shape(
                    self.network.participant_tree(names))
                if spec is not None:
                    shape, order = spec
            axes = tuple(f"l{i}" for i in range(len(shape)))
            group_axes = axes
        else:
            # multi-trainer: a leading "t" axis indexes the disjoint
            # per-trainer process groups (trainer t = rank block
            # [t*M, (t+1)*M)); the fabric axes nest inside it when every
            # group's participant-pruned tree has the same shape, else
            # each group is one flat row.  Grouped reductions never name
            # "t", so a trainer's outer sync only touches its own block.
            k, M = self._k, self._M
            groups = [names[t * M:(t + 1) * M] for t in range(k)]
            sub = None
            if hasattr(self.network, "participant_tree"):
                specs = [self._balanced_shape(
                    self.network.participant_tree(g)) for g in groups]
                if (all(s is not None for s in specs)
                        and len({s[0] for s in specs}) == 1):
                    sub = (specs[0][0],
                           [nm for _, order in specs for nm in order])
            if sub is not None:
                shape, order = (k,) + sub[0], sub[1]
            else:
                shape, order = (k, M), [nm for g in groups for nm in g]
            axes = ("t",) + tuple(f"l{i}" for i in range(len(shape) - 1))
            group_axes = axes[1:]
        # device d belongs to process d.process_index; one device per
        # process under the launch_mp contract
        dev_of_proc = {}
        for d in jax.devices():
            dev_of_proc.setdefault(d.process_index, d)
        devs = np.array([dev_of_proc[proc_of[nm]] for nm in order])
        self._axes = axes
        self._group_axes = group_axes
        self._mesh = Mesh(devs.reshape(shape), axes)
        self._reduce_jit = None
        self._allsum_jit = None

    def _reducer(self):
        """Jitted mean-over-workers: pmean per *group* mesh axis,
        innermost (leaf siblings) to outermost (top bottleneck) — the
        hierarchical all-reduce schedule, for real.  With k > 1 the
        leading trainer axis is never reduced, so each group's row gets
        its own mean (non-member rows reduce their zeros to zeros)."""
        from jax.sharding import PartitionSpec as P

        mesh, axes, group_axes = self._mesh, self._axes, self._group_axes

        def mean_group(x):
            for ax in reversed(group_axes):
                x = jax.lax.pmean(x, ax)
            return x

        return jax.jit(jax.shard_map(mean_group, mesh=mesh,
                                 in_specs=P(axes), out_specs=P(axes)))

    def _allsummer(self):
        """Jitted SUM over *every* mesh axis — the cross-group
        collective merges and the final consolidate ride.  Summing over
        the trainer axis too is what folds the groups' weighted
        replicas into one globally-replicated result."""
        from jax.sharding import PartitionSpec as P

        mesh, axes = self._mesh, self._axes

        def sum_all(x):
            for ax in reversed(axes):
                x = jax.lax.psum(x, ax)
            return x

        return jax.jit(jax.shard_map(sum_all, mesh=mesh,
                                 in_specs=P(axes), out_specs=P(axes)))

    def _ensure_jits(self):
        if self._mesh is None:
            self._build_mesh()
        if self._reduce_jit is None:
            self._reduce_jit = self._reducer()
        if self._allsum_jit is None:
            self._allsum_jit = self._allsummer()

    # -------------------------------------------------------- execution
    def local_workers(self, M, *, tid=None):
        if self.num_processes == 1 and M == 1:
            return [0]
        if self._k == 1:
            return [self.rank]
        return [self.rank % self._M] if self._member(tid) else []

    def _dispatch(self, tree, fn=None):
        """Lift the local worker onto the global mesh (leading worker
        axis sharded across every level axis) and *enqueue* the jitted
        reduction — no ready-wait, so the collective runs while the
        caller keeps computing (jax's async dispatch)."""
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        mesh, spec = self._mesh, P(self._axes)
        glob = multihost_utils.host_local_array_to_global_array(
            tree, mesh, spec)
        return jax.tree.map(self._reduce_jit if fn is None else fn, glob)

    def _collect(self, out):
        """Read a dispatched reduction back to host-local shards,
        blocking until the wire work is done."""
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        mesh, spec = self._mesh, P(self._axes)
        host = multihost_utils.global_array_to_host_local_array(
            out, mesh, spec)
        return jax.tree.map(jax.block_until_ready, host)

    def _execute(self, tree, fn=None):
        """Blocking dispatch+collect (warm-ups and the inline paths)."""
        return self._collect(self._dispatch(tree, fn))

    def outer_reduce(self, worker_params):
        local = [wp for wp in worker_params if wp is not None]
        if len(local) != 1:
            raise ValueError(f"expected exactly the local worker's "
                             f"params, got {len(local)} entries")
        self._ensure_jits()
        tree = jax.tree.map(lambda x: jnp.asarray(x)[None], local[0])
        sig = tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(tree))
        if sig not in self._warm:
            # run once untimed so trace/compile never lands in the
            # measured window (pmean is deterministic, and every rank
            # reaches this point in lockstep, so the extra collective is
            # identical everywhere); re-run below for the wire timing
            self._execute(tree)
            self._warm.add(sig)
        t0 = time.perf_counter()
        host = self._execute(tree)
        self._last_measured = time.perf_counter() - t0
        self._record_real("outer", t0, self._last_measured)
        # every shard now holds the global mean; drop the collective's
        # (1, ...) axis: make_outer_step's mean of one tree is that tree
        return (jax.tree.map(lambda x: x[0], host),)

    def dispatch_outer(self, worker_params, *, stats_vec=None,
                       phase2=None, tid=None, template=None):
        local = [wp for wp in worker_params if wp is not None]
        if self._member(tid):
            if len(local) != 1:
                raise ValueError(f"expected exactly the local worker's "
                                 f"params, got {len(local)} entries")
            tree = jax.tree.map(lambda x: jnp.asarray(x)[None], local[0])
        else:
            # outside trainer tid's group: participate in the (global)
            # wire operation with zeros shaped like the template — this
            # row's grouped mean is zeros and the runtime discards it
            if local:
                raise ValueError("rank outside the trainer's group "
                                 "computed worker params")
            if template is None:
                raise ValueError("non-member dispatch needs a params "
                                 "template")
            tree = jax.tree.map(
                lambda x: jnp.zeros((1,) + jnp.shape(x),
                                    jnp.asarray(x).dtype), template)
        self._ensure_jits()
        fused = stats_vec is not None
        if fused:
            # piggyback: the phase-1 [colsum, b] vector rides the same
            # wire operation as the params — one fused collective
            # instead of two gradient-order reductions per round
            tree = {"params": tree,
                    "stats": jnp.asarray(stats_vec, jnp.float32)[None]}
        sig = tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(tree))
        if sig not in self._warm:
            # compile with a blocking run outside any measured window
            # (lockstep on every rank: dispatch order is deterministic,
            # so the extra collective is identical everywhere)
            self._execute(tree)
            self._warm.add(sig)
        chain = (fused and phase2 is not None
                 and self.num_processes > 1)
        if chain:
            # the phase-2 five-moment reduction will chain onto this
            # window; warm its signature now so no compile lands inside
            ph2_sig = ((1, 5), "float32", "stats")
            if ph2_sig not in self._warm:
                self._execute(jnp.zeros((1, 5), jnp.float32))
                self._warm.add(ph2_sig)
        t0 = time.perf_counter()
        out = self._dispatch(tree)     # enqueued, NOT blocked on
        handle = {"out": out, "t0": t0, "fused": fused}
        if chain:
            # fold-time fusion (ROADMAP: overlap the phase-2 reduction
            # too): derive ḡ from the in-flight phase-1 result without
            # blocking — the ops below build on the enqueued buffers —
            # and chain the five shard moments as a second enqueued
            # collective on the same window.  wait_outer collects both;
            # the standalone fold-time stats sync is gone.
            from jax.experimental import multihost_utils
            from jax.sharding import PartitionSpec as P

            row = multihost_utils.global_array_to_host_local_array(
                out["stats"], self._mesh, P(self._axes))
            tot = row[0] * jnp.float32(self.num_processes)
            gbar = tot[:-1] / jnp.maximum(tot[-1], 1.0)
            from repro.core import batching
            m = batching.shard_moments(phase2["G_local"], gbar)
            handle["ph2"] = self._dispatch(m[None])
        return handle

    def wait_outer(self, handle):
        host = self._collect(handle["out"])
        if "ph2" in handle:
            row = self._collect(handle["ph2"])
            # mesh reduction is a mean over the P shards; the stats
            # composition protocol wants elementwise sums
            self._last_phase2 = row[0] * jnp.float32(self.num_processes)
        t0 = handle["t0"]
        dt = time.perf_counter() - t0
        self._last_measured = dt
        # the recorded span is the true in-flight window: dispatch ->
        # ready, spanning whatever inner compute ran in between (and
        # any chained phase-2 moments collective)
        self._record_real("piggyback" if handle["fused"] else "outer",
                          t0, dt)
        # drop the collective's (1, ...) axis, as outer_reduce does
        mean = (jax.tree.map(lambda x: x[0],
                             host["params"] if handle["fused"] else host),)
        if handle["fused"]:
            # same mean -> sum rescale for the fused phase-1 vector
            stats_total = host["stats"][0] * jnp.float32(self.num_processes)
            return mean, stats_total
        return mean, None

    def pop_phase2_total(self):
        v, self._last_phase2 = self._last_phase2, None
        return v

    def note_real_compute(self, t0, dt, *, tid=0):
        self._record_real("compute", t0, dt, tid=tid)

    def mean_scalar(self, value, *, tid=None):
        if self.num_processes == 1:
            return float(value)
        from jax.experimental import multihost_utils
        if self._k == 1:
            got = multihost_utils.process_allgather(
                jnp.asarray(value, jnp.float32))
            return float(jnp.mean(got))
        # group mean as a masked allgather-sum: members contribute
        # value/M, everyone else zero — every rank still joins the
        # collective (lockstep) and reads the same group mean
        contrib = (float(value) / self._M) if self._member(tid) else 0.0
        got = multihost_utils.process_allgather(
            jnp.asarray(contrib, jnp.float32))
        return float(jnp.sum(got))

    def merge_reducer(self):
        """Merges/consolidates as real cross-group collectives: member
        ranks contribute their trainer's replica scaled by
        ``weight/M`` (each of the M group ranks carries 1/M of the
        group's share), non-members contribute zeros, and one global
        ``psum`` folds both the weighted parameter sum and the total
        weight — the division lands the batch-weighted average
        replicated on every rank, exactly what Algorithm 2 computes
        in-process.  None when the pool lives in one process."""
        if self.num_processes == 1 or self._k == 1:
            return None

        def merge_reduce(trainers, weights, *, kind="merge", tid=0):
            self._ensure_jits()
            template = trainers[0].params
            mine, w = None, 0.0
            for t, wt in zip(trainers, weights):
                if self._member(t.tid):
                    mine, w = t.params, float(wt)
            if mine is None:
                tree = jax.tree.map(
                    lambda x: jnp.zeros((1,) + jnp.shape(x), jnp.float32),
                    template)
                wrow = 0.0
            else:
                wrow = w / float(self._M)
                scale = jnp.float32(wrow)
                tree = jax.tree.map(
                    lambda x: (jnp.asarray(x, jnp.float32) * scale)[None],
                    mine)
            payload = {"x": tree, "w": jnp.full((1,), wrow, jnp.float32)}
            sig = tuple((l.shape, str(l.dtype))
                        for l in jax.tree.leaves(payload)) + ("merge",)
            if sig not in self._warm:
                # compile outside the measured window (lockstep: every
                # rank reaches the merge event in the same order)
                self._execute(payload, self._allsum_jit)
                self._warm.add(sig)
            t0 = time.perf_counter()
            host = self._execute(payload, self._allsum_jit)
            dt = time.perf_counter() - t0
            self._last_merge_measured = (
                (self._last_merge_measured or 0.0) + dt)
            self._record_real(kind, t0, dt, tid=tid)
            wsum = host["w"][0]
            return jax.tree.map(
                lambda s, ref: (s[0] / wsum).astype(jnp.asarray(ref).dtype),
                host["x"], template)

        return merge_reduce

    def pop_merge_measured(self):
        m, self._last_merge_measured = self._last_merge_measured, None
        return m

    def stats_reducer(self):
        """Cross-process SUM of a small f32 vector, executed as the
        same per-fabric-level ``lax.pmean`` chain as the outer
        reduction (scaled back to a sum) — the batch-stats phases ride
        the mesh the pricing ``Topology`` defines.  None on a single
        process: the in-process estimator already sees every worker,
        and must stay bit-identical to the SimBackend."""
        if self.num_processes == 1:
            return None

        def reduce_sum(vec):
            if self._mesh is None:
                self._build_mesh()
            if self._reduce_jit is None:
                self._reduce_jit = self._reducer()
            tree = jnp.asarray(vec, jnp.float32)[None]
            sig = (tree.shape, str(tree.dtype), "stats")
            if sig not in self._warm:
                # compile outside the measured window (lockstep on
                # every rank, same as the outer warm-up)
                self._execute(tree)
                self._warm.add(sig)
            t0 = time.perf_counter()
            host = self._execute(tree)
            dt = time.perf_counter() - t0
            self._last_stats_measured = (
                (self._last_stats_measured or 0.0) + dt)
            self._record_real("stats", t0, dt)
            # the mesh reduction is a mean over the P workers; the
            # composition protocol wants elementwise sums
            return host[0] * jnp.float32(self.num_processes)

        return reduce_sum

    def pop_stats_measured(self):
        m = self._last_stats_measured
        self._last_stats_measured = None
        return m

    def broadcast_params(self, params):
        if self.num_processes == 1:
            return params
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(params)

    def pop_measured(self):
        m, self._last_measured = self._last_measured, None
        return m


__all__ = ["CollectiveBackend", "JaxProcessBackend", "SimBackend"]
