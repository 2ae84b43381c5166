"""AdLoCo — Algorithm 3: Adaptive Batching + Merging + SwitchMode on the
DiLoCo core.  Host-level orchestrator over the jitted primitives in
``diloco.py``.

The per-trainer round body (inner steps -> batch statistics -> requested
batch update -> outer sync) lives in :class:`TrainerRound`, shared by

  * :func:`train_adloco` — the legacy synchronous host loop, and
  * ``repro.cluster.run_cluster`` — the event-driven virtual-cluster
    runtime (heterogeneous nodes, async outer syncs, elastic pools).

Ablations (paper Fig. 2) via AdLoCoConfig flags:
  adaptive=False       -> fixed-batch DiLoCo-style training
  enable_merge=False   -> no trainer consolidation
  enable_switch=False  -> no gradient accumulation (batch hard-capped)
Vanilla DiLoCo baseline = adaptive off, merge off, switch off.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.profiler import (StepTraceAnnotation, TraceAnnotation,
                          annotate_function)

from repro import optim
from repro.configs.base import AdLoCoConfig
from repro.core import batching
from repro.core.comms import CommsMeter, param_bytes
from repro.core.diloco import (StepCache, make_outer_step, reshape_for_plan)
from repro.core.mit import (TrainerPoolState, TrainerState, check_merge,
                            consolidate, do_merge)
from repro.core.switch import ExecutionPlan, plan_execution


@dataclass
class History:
    outer_step: List[int] = field(default_factory=list)
    loss: List[float] = field(default_factory=list)
    eval_loss: List[float] = field(default_factory=list)
    # per-record {tid: eval loss} so elastic / multi-trainer runs stay
    # attributable to the trainer that produced each number
    eval_loss_by_trainer: List[Dict[int, float]] = field(default_factory=list)
    # eval loss of the batch-weighted average of the live pool at each
    # record (what ``consolidate`` would return right now) — the honest
    # convergence curve for autoscaled/elastic pools, where averaging k
    # anchors divides the gradient-noise floor; cluster runtime only
    eval_loss_pool: List[float] = field(default_factory=list)
    pool_size: List[int] = field(default_factory=list)
    requested_batches: List[List[int]] = field(default_factory=list)
    comm_events: List[int] = field(default_factory=list)
    comm_bytes: List[float] = field(default_factory=list)
    samples: List[int] = field(default_factory=list)     # cumulative
    modes: List[List[str]] = field(default_factory=list)
    wall: List[float] = field(default_factory=list)
    # simulated seconds (repro.cluster runtime only; empty for the
    # legacy host loop, which has no cluster clock)
    sim_time: List[float] = field(default_factory=list)

    def as_dict(self) -> Dict[str, Any]:
        return self.__dict__.copy()


@dataclass
class RoundOutput:
    """Result of one trainer round's compute phase (inner steps + batch
    adaptation), before the outer sync is applied."""

    worker_params: List[Any]        # per-worker end-of-round params
    x_start: Any                    # params the pseudo-gradient diffs against
    mean_loss: float
    mode: str                       # execution plan mode this round
    samples: int                    # total samples consumed (all workers)
    samples_per_worker: int
    flops_per_worker: float         # estimated compute cost (6*N*samples)
    bytes_per_worker: float         # estimated HBM traffic per worker
    # wire payload of the round's batch-stats reduction (0.0 when the
    # round ran fixed-batch); the cluster runtime prices it as a
    # collective over the trainer's nodes
    stats_bytes: float = 0.0
    # deferred-stats handle (``inner(..., defer_stats=True)``): the
    # material needed to finish the batch decision later via
    # :meth:`TrainerRound.apply_stats` — either ``{"st": GradStats}``
    # (local estimator paths, no collective needed) or
    # ``{"phase1": vec, "G_local": rows, "micro": m}`` whose phase-1
    # vector the runtime piggybacks onto the outer sync.  None when the
    # decision was applied inline (sync policy / fixed batch).
    stats_request: Optional[Dict[str, Any]] = None
    # True when the round's batch decision came from the fitted growth
    # predictor (``acfg.k_correct`` > 1, non-correction round): no stats
    # were computed and no reduction is owed (stats_bytes stays 0.0)
    predicted: bool = False


class BatchPlanProtocol:
    """Shape-agreement protocol: reduced statistics -> one batch
    decision -> one deterministic :class:`ExecutionPlan`.

    Distributed adaptive batching only works if every rank compiles the
    same shapes each round.  The protocol guarantees that by
    construction: the sufficient statistics are reduced with a
    deterministic collective (``repro.core.batching.distributed_stats``
    — every rank receives bit-identical values), and both
    :meth:`decide` and :meth:`plan_for` are pure functions of those
    values and the shared config, so the requested batch and the
    compiled ``(micro_batch, accum_steps)`` shape agree everywhere
    without any further coordination.
    """

    def __init__(self, acfg: AdLoCoConfig):
        self.acfg = acfg

    # ------------------------------------------------------- reduction
    def reduce(self, G_local, sum_reduce, *,
               micro_size: int) -> batching.GradStats:
        """Compose this process's gradient rows with every other
        process's via the backend's SUM all-reduce (exact two-phase
        composition; see ``batching.distributed_stats``)."""
        return batching.distributed_stats(G_local, sum_reduce,
                                          micro_size=micro_size)

    def payload_bytes(self, n_params: int) -> float:
        """Wire payload the runtime prices the stats collective at."""
        return batching.stats_payload_bytes(n_params)

    # ------------------------------------------- deferred (split) phases
    def begin(self, G_local) -> jnp.ndarray:
        """Phase-1 payload for a deferred reduction: the ``[colsum, b]``
        vector the runtime piggybacks onto the outer sync."""
        return batching.stats_phase1(G_local)

    def finish(self, phase1_total, G_local, sum_reduce, *,
               micro_size: int) -> batching.GradStats:
        """Finish a deferred reduction from the piggybacked phase-1
        total: phase 2 (five scalar moments) + rescale.  Bit-identical
        to the inline :meth:`reduce` composition."""
        return batching.stats_finish(phase1_total, G_local, sum_reduce,
                                     micro_size=micro_size)

    def finish_total(self, phase2_total, *,
                     micro_size: int) -> batching.GradStats:
        """Finish from an already-summed phase-2 moments vector — the
        path for backends that chained the moment reduction onto the
        outer collective's window instead of running it standalone."""
        return batching.stats_finish_total(phase2_total,
                                           micro_size=micro_size)

    # -------------------------------------------------------- decision
    def decide(self, st: batching.GradStats, current_b: int) -> int:
        """The configured batch test + monotone-growth/cap policy."""
        return batching.requested_batch(st, self.acfg, current_b)

    def plan_for(self, b_req: int) -> ExecutionPlan:
        acfg = self.acfg
        mult = (acfg.switch_multiplier if acfg.enable_switch
                else 10 ** 9)  # switch off => never accumulate
        return plan_execution(b_req, acfg.max_batch, mult)


class TrainerRound:
    """Reusable per-trainer round primitive (Alg 3 lines 17–44).

    ``inner`` runs the compute phase: M workers x H inner steps from
    ``worker_starts`` (default: the trainer's synced params), updates the
    inner optimizer states and — when adaptive — the requested batch.
    ``outer`` applies the outer (pseudo-gradient) step to the trainer and
    meters the all-reduce.  Keeping the two phases separate is what lets
    the cluster runtime overlap them (ACCO-style async outer syncs).

    Both phases mark their host work with ``jax.profiler`` spans, which a
    profile places on the device's clock so an idle gap on the device
    names the host work it fell in (and cost one cheap call each when no
    profile runs):

      adloco.inner         the compute phase
        adloco.data        drawing and shaping one step's rows
        adloco.step        dispatching one inner step
        adloco.sync.loss   reading a worker's last loss (blocks the host)
        adloco.stats       the batch decision (also ``apply_stats``)
          adloco.sync.batch  reading the requested batch (blocks the host)
      adloco.outer         the outer step
        adloco.outer.stack stacking or reducing the workers' params
    """

    def __init__(self, loss_fn: Callable, acfg: AdLoCoConfig):
        self.loss_fn = loss_fn
        self.acfg = acfg
        self.protocol = BatchPlanProtocol(acfg)
        self.inner_opt = optim.get_optimizer(
            acfg.inner_optimizer, acfg.lr_inner,
            **({"weight_decay": acfg.weight_decay}
               if acfg.inner_optimizer == "adamw" else {}))
        # staleness-aware delay compensation (async policy): swap the
        # plain Nesterov outer for the delay-parameterized variant and
        # thread the measured delay through the jitted step
        self._delay_aware = (acfg.delay_compensation
                             and acfg.outer_optimizer == "nesterov")
        if self._delay_aware:
            self.outer_opt = optim.delay_compensated_nesterov(
                acfg.lr_outer, momentum=acfg.outer_momentum)
        else:
            self.outer_opt = optim.get_optimizer(
                acfg.outer_optimizer, acfg.lr_outer,
                **({"momentum": acfg.outer_momentum}
                   if acfg.outer_optimizer in ("nesterov", "sgd") else {}))
        self.cache = StepCache(loss_fn, self.inner_opt)
        self.outer_step = make_outer_step(self.outer_opt,
                                          delay_aware=self._delay_aware)
        self._n_params: Optional[int] = None
        # per-trainer batch-growth predictors (k_correct > 1): exact
        # decisions are observed, skipped rounds read the fitted line
        self._predictors: Dict[int, batching.BatchGrowthPredictor] = {}

    # ----------------------------------------------- predicted growth
    def _predictor_for(self, tid: int) -> batching.BatchGrowthPredictor:
        pred = self._predictors.get(tid)
        if pred is None:
            pred = batching.BatchGrowthPredictor(self.acfg.max_global_batch)
            self._predictors[tid] = pred
        return pred

    def _is_correction(self, round_i: Optional[int]) -> bool:
        """Rounds that run the exact stats protocol under predicted
        growth: round 1 and every ``k_correct``'th round after it.
        Everything is exact when ``k_correct <= 1`` or the caller does
        not thread round indices (legacy call sites)."""
        k = self.acfg.k_correct
        return k <= 1 or round_i is None or (round_i - 1) % k == 0

    # ---------------------------------------------------------- pool
    def init_pool(self, init_params_list: List[Any],
                  streams: List[Any]) -> TrainerPoolState:
        acfg = self.acfg
        M = acfg.nodes_per_gpu
        trainers = []
        for i, params in enumerate(init_params_list):
            trainers.append(TrainerState(
                tid=i,
                params=params,
                outer_opt_state=self.outer_opt.init(params),
                inner_opt_states=[self.inner_opt.init(params)
                                  for _ in range(M)],
                requested_batch=acfg.initial_batch_size,
                streams=[streams[i * M + m] for m in range(M)],
            ))
        return TrainerPoolState(trainers=trainers)

    def new_trainer(self, tid: int, params: Any,
                    streams: List[Any]) -> TrainerState:
        """Fresh trainer (elastic join): given params, fresh opt states."""
        M = self.acfg.nodes_per_gpu
        return TrainerState(
            tid=tid, params=params,
            outer_opt_state=self.outer_opt.init(params),
            inner_opt_states=[self.inner_opt.init(params) for _ in range(M)],
            requested_batch=self.acfg.initial_batch_size,
            streams=list(streams))

    # --------------------------------------------------------- plans
    def plan_for(self, tr: TrainerState,
                 fixed_batch: Optional[int] = None) -> ExecutionPlan:
        acfg = self.acfg
        b_req = (fixed_batch if (fixed_batch is not None
                                 and not acfg.adaptive)
                 else tr.requested_batch)
        return self.protocol.plan_for(b_req)

    def _count_params(self, params) -> int:
        if self._n_params is None:
            self._n_params = int(sum(
                jnp.size(l) for l in jax.tree.leaves(params)))
        return self._n_params

    # --------------------------------------------------------- inner
    @partial(annotate_function, name="adloco.inner")
    def inner(self, tr: TrainerState, *,
              fixed_batch: Optional[int] = None,
              worker_starts: Optional[List[Any]] = None,
              workers: Optional[List[int]] = None,
              stats_reduce: Optional[Callable] = None,
              defer_stats: bool = False,
              round_i: Optional[int] = None,
              batch_share: Optional[int] = None) -> RoundOutput:
        """Compute phase of one round.  Mutates ``tr.inner_opt_states``
        and (adaptive) ``tr.requested_batch``; never touches
        ``tr.params``.  ``workers`` restricts which of the M workers this
        process computes (distributed execution backends own one worker
        per process); the returned ``worker_params`` list keeps length M
        with ``None`` at the slots other processes own.  ``stats_reduce``
        is a cross-process SUM all-reduce of a small f32 vector (see
        ``CollectiveBackend.stats_reducer``): when provided, adaptive
        batch statistics run the exact two-phase composition over every
        process's workers — each worker's microbatch-mean grad is one
        shard — so all ranks derive the identical requested batch and
        compiled shapes (the :class:`BatchPlanProtocol` contract).
        ``defer_stats`` (async policy) skips the inline batch decision
        and instead returns a stale stats handle in
        ``RoundOutput.stats_request``; the runtime piggybacks its
        phase-1 vector onto the outer sync and folds the decision via
        :meth:`apply_stats` when that collective lands — one-round-stale
        plan semantics, same on every backend by construction.
        ``round_i`` (1-based outer round) enables predicted batch growth
        when ``acfg.k_correct > 1``: non-correction rounds set the
        requested batch from the fitted exponential trajectory with zero
        stats collectives.  ``batch_share`` (autoscaling runtimes)
        overrides the *executed* plan to this trainer's slice of the
        requested batch without touching the decision trajectory."""
        acfg = self.acfg
        M = len(tr.inner_opt_states)
        H = acfg.num_inner_steps
        idxs = list(range(M)) if workers is None else list(workers)
        plan = self.plan_for(tr, fixed_batch)
        if batch_share is not None and acfg.adaptive:
            plan = self.protocol.plan_for(max(1, int(batch_share)))
        step_fn = self.cache.get(plan)

        x_start = tr.params
        worker_params: List[Any] = [None] * M
        worker_grads, last_losses = [], []
        for m in idxs:
            wp = worker_starts[m] if worker_starts is not None else x_start
            opt_m = tr.inner_opt_states[m]
            stream = tr.streams[m % len(tr.streams)]
            for h in range(H):
                with TraceAnnotation("adloco.data", worker=m, step=h):
                    batch = stream.next_batch(plan.effective_batch)
                    batch = reshape_for_plan(batch, plan)
                with TraceAnnotation("adloco.step", worker=m, step=h):
                    wp, opt_m, loss, grads = step_fn(wp, opt_m, batch)
                # drop the replaced state now: held to the round's end it
                # is one more optimizer state on the device
                tr.inner_opt_states[m] = opt_m
            worker_params[m] = wp
            worker_grads.append(grads)
            with TraceAnnotation("adloco.sync.loss", worker=m):
                last_losses.append(float(loss))

        # ---- requested batch for the next round (Alg 3 line 31) ------
        stats_bytes, stats_request, predicted = 0.0, None, False
        if acfg.adaptive:
            stats_bytes, stats_request, predicted = self._decide_batch(
                tr, plan, worker_grads, worker_params, idxs,
                stats_reduce=stats_reduce, defer_stats=defer_stats,
                round_i=round_i)

        spw = plan.effective_batch * H
        n = self._count_params(x_start)
        return RoundOutput(
            worker_params=worker_params, x_start=x_start,
            # a rank outside this trainer's process group computes no
            # workers; its zero contribution drops out of the backend's
            # group-masked loss mean
            mean_loss=(sum(last_losses) / len(last_losses)
                       if last_losses else 0.0),
            mode=plan.mode, samples=spw * M, samples_per_worker=spw,
            flops_per_worker=6.0 * n * spw,
            bytes_per_worker=3.0 * param_bytes(x_start) * H,
            stats_bytes=stats_bytes, stats_request=stats_request,
            predicted=predicted)

    @partial(annotate_function, name="adloco.stats")
    def _decide_batch(self, tr: TrainerState, plan: ExecutionPlan,
                      worker_grads: List[Any], worker_params: List[Any],
                      idxs: List[int], *, stats_reduce: Optional[Callable],
                      defer_stats: bool, round_i: Optional[int]):
        """The batch decision of an adaptive round (see :meth:`inner`):
        sets ``tr.requested_batch``, or (``defer_stats``) returns the
        stats handle that :meth:`apply_stats` folds later.  Returns
        ``(stats_bytes, stats_request, predicted)``."""
        acfg = self.acfg
        if not self._is_correction(round_i):
            # PadaDamp-style skipped round: read the fitted exponential
            # trajectory instead of running the stats reduction — zero
            # collectives, every rank fits the same observations so the
            # shape-agreement contract holds without communication
            tr.requested_batch = self._predictor_for(tr.tid).predict(
                round_i, tr.requested_batch)
            return 0.0, None, True
        stats_request: Optional[Dict[str, Any]] = None
        if stats_reduce is not None:
            # distributed backends: each process contributes its
            # workers' microbatch-mean grads as shards of the exact
            # two-phase composition; every rank receives identical
            # reduced statistics, so the decision below agrees by
            # construction (shape-agreement protocol)
            G_local = batching.flatten_grads(
                jax.tree.map(lambda *g: jnp.stack(g), *worker_grads))
            if defer_stats:
                st = None
                stats_request = {"phase1": self.protocol.begin(G_local),
                                 "G_local": G_local,
                                 "micro": plan.effective_batch}
            else:
                st = self.protocol.reduce(
                    G_local, stats_reduce,
                    micro_size=plan.effective_batch)
        elif acfg.stats_estimator == "microbatch" and len(idxs) >= 2:
            # free distributed estimator: the M workers' last
            # microbatch-mean grads are already materialized;
            # Var over workers * m estimates sigma^2 with zero
            # extra passes (DESIGN.md §3 — the grads come from
            # slightly diverged worker params, an accepted
            # approximation of the shared-point statistics)
            st = batching.stats_from_microbatch_grads(
                worker_grads, plan.effective_batch)
        else:
            # the paper computes sigma_Bk / grad_Bk on the
            # CURRENT batch; stats_probe_size is only a memory
            # cap (the E||g_B||^2 = ||g||^2 + sigma^2/B bias of
            # a too-small probe stalls batch growth and breaks
            # Theorem 2's ln-N communication profile)
            probe_b = max(4, min(acfg.stats_probe_size,
                                 plan.effective_batch))
            probe = tr.streams[0].next_batch(probe_b)
            st = batching.per_sample_stats(
                self.loss_fn, worker_params[idxs[0]], probe,
                use_kernel=acfg.stats_use_kernel)
        if defer_stats:
            # one-round-stale plan semantics: the decision folds at
            # the outer sync's landing point (apply_stats), not here
            if stats_request is None:
                stats_request = {"st": st}
        else:
            tr.requested_batch = self.protocol.decide(
                st, tr.requested_batch)
            if acfg.k_correct > 1 and round_i is not None:
                self._predictor_for(tr.tid).observe(
                    round_i, tr.requested_batch)
        n = self._count_params(tr.params)
        return self.protocol.payload_bytes(n), stats_request, False

    # ---------------------------------------------------- stale stats
    @partial(annotate_function, name="adloco.stats")
    def apply_stats(self, tr: TrainerState, request: Dict[str, Any], *,
                    phase1_total=None, phase2_total=None,
                    sum_reduce: Optional[Callable] = None,
                    round_i: Optional[int] = None) -> int:
        """Fold a stale stats handle produced by
        ``inner(..., defer_stats=True)`` into the trainer's requested
        batch.  Local-estimator requests carry the finished statistics
        (``{"st"}``); distributed requests carry the phase-1 material —
        the caller supplies either ``phase2_total`` (the five-moment
        SUM a backend chained onto the outer collective's in-flight
        window) or ``phase1_total`` (the piggybacked SUM of every
        rank's phase-1 vector) plus ``sum_reduce`` for the standalone
        phase-2 moment reduction.  Returns the updated requested batch
        (identical on every rank — the shape-agreement contract)."""
        if "st" in request:
            st = request["st"]
        elif phase2_total is not None:
            st = self.protocol.finish_total(
                phase2_total, micro_size=request["micro"])
        else:
            st = self.protocol.finish(
                phase1_total, request["G_local"], sum_reduce,
                micro_size=request["micro"])
        tr.requested_batch = self.protocol.decide(st, tr.requested_batch)
        if self.acfg.k_correct > 1 and round_i is not None:
            self._predictor_for(tr.tid).observe(round_i, tr.requested_batch)
        return tr.requested_batch

    # --------------------------------------------------------- outer
    @partial(annotate_function, name="adloco.outer")
    def outer(self, tr: TrainerState, worker_params: List[Any], *,
              x_prev: Optional[Any] = None,
              comms: Optional[CommsMeter] = None, step: int = 0,
              reduce: Optional[Callable] = None,
              delay: float = 0.0) -> None:
        """Apply the outer (pseudo-gradient) step: Alg 3 lines 40–44.
        ``x_prev`` defaults to the trainer's current synced params; the
        async cluster policy passes the anchor captured at launch time
        (delayed application).  ``reduce`` maps the per-worker params
        list to the sequence of param-shaped pytrees ``make_outer_step``
        averages — by default the workers' params themselves, passed
        straight into the jitted step; execution backends substitute a
        real cross-process collective that returns the already-reduced
        mean as a 1-tuple.  ``delay`` is the measured
        staleness in rounds (how many inner rounds folded between the
        snapshot and this application); with ``delay_compensation`` on
        it damps the momentum contribution accordingly, otherwise it is
        ignored by the jitted step."""
        with TraceAnnotation("adloco.outer.stack"):
            workers = (tuple(worker_params) if reduce is None
                       else reduce(worker_params))
        tr.params, tr.outer_opt_state = self.outer_step(
            x_prev if x_prev is not None else tr.params,
            workers, tr.outer_opt_state, float(delay))
        if comms is not None:
            comms.record("outer", participants=len(worker_params),
                         payload_bytes=param_bytes(tr.params), step=step)


def record_eval(hist: History, pool: TrainerPoolState,
                eval_fn: Optional[Callable]) -> None:
    """Evaluate every trainer, keep the per-tid map, and track the best
    (largest requested batch = most advanced) trainer's loss in the
    legacy ``eval_loss`` series."""
    if eval_fn is None:
        return
    per = {tr.tid: float(eval_fn(tr.params)) for tr in pool.trainers}
    hist.eval_loss_by_trainer.append(per)
    best = max(pool.trainers, key=lambda tr: tr.requested_batch)
    hist.eval_loss.append(per[best.tid])


def train_adloco(loss_fn: Callable, init_params_list: List[Any],
                 streams: List[Any], acfg: AdLoCoConfig, *,
                 num_outer_steps: Optional[int] = None,
                 eval_fn: Optional[Callable] = None,
                 fixed_batch: Optional[int] = None,
                 verbose: bool = False,
                 restore_from: Optional[tuple] = None):
    """Run Algorithm 3 (synchronous host loop).

    loss_fn(params, batch) -> (loss, aux);  streams: k*M data shards with
    ``next_batch(b)``;  init_params_list: k independent inits (the paper's
    multi-instance diversity).  ``restore_from``: optional
    (ckpt_dir, step) to restore the trainer pool from before training.
    Returns (TrainerPoolState, History).
    """
    T = num_outer_steps or acfg.num_outer_steps
    rnd = TrainerRound(loss_fn, acfg)
    pool = rnd.init_pool(init_params_list, streams)
    if restore_from is not None:
        from repro.checkpoint import restore_train_state
        pool, _ = restore_train_state(restore_from[0], restore_from[1], pool)
    if fixed_batch is not None and not acfg.adaptive:
        for tr in pool.trainers:
            tr.requested_batch = fixed_batch
    hist = History()
    samples_total = 0
    t0 = time.time()

    for t in range(1, T + 1):
        # one profiler step per round: the step view of a trace
        with StepTraceAnnotation("adloco.round", step_num=t):
            # ---- CheckMerge / DoMerge (Alg 3 lines 11–16) ----------------
            if (acfg.enable_merge and pool.k > 1
                    and t % acfg.merge_frequency == 0):
                ids = check_merge([tr.requested_batch for tr in pool.trainers],
                                  acfg.merge_w + 1)  # w worst + representative
                if len(ids) > 1:
                    pool = do_merge(pool, ids, step=t)

            round_losses, modes = [], []
            for tr in pool.trainers:
                out = rnd.inner(tr, fixed_batch=fixed_batch, round_i=t)
                round_losses.append(out.mean_loss)
                modes.append(out.mode)
                samples_total += out.samples
                # ---- outer sync (Alg 3 lines 40–44) ----------------------
                rnd.outer(tr, out.worker_params, comms=pool.comms, step=t)

            hist.outer_step.append(t)
            hist.loss.append(sum(round_losses) / len(round_losses))
            hist.pool_size.append(pool.k)
            hist.requested_batches.append(
                [tr.requested_batch for tr in pool.trainers])
            hist.comm_events.append(pool.comms.events)
            hist.comm_bytes.append(pool.comms.total_bytes)
            hist.samples.append(samples_total)
            hist.modes.append(modes)
            hist.wall.append(time.time() - t0)
            record_eval(hist, pool, eval_fn)
            if verbose:
                print(f"[adloco] t={t} loss={hist.loss[-1]:.4f} "
                      f"k={pool.k} b={hist.requested_batches[-1]} "
                      f"comm={pool.comms.events}")

    pool = consolidate(pool, step=T)
    return pool, hist
