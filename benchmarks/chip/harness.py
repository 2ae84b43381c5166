"""One cell of ``BENCHMARK.json``, run once on the chip.

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); the configuration names its architecture
family (``families/<family>.py``), the limits of the cell's check are in
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``.  All are found by name, so a cell, a mix, a
metric or a model of a new kind is added by adding files and entries.

Set-up (``setup_s``): the compile cache, the platform check, the weights
made on the device from the seed, every token row the run can use drawn
on the host, and one whole first round through ``TrainerRound``, which
compiles and runs every program the window uses.  In that round the
harness reads what the check compares: every inner step's loss, each
worker's first gradient (from AdamW's first moment after one step) and
its parameters' change after its first steps, the batch statistics and
the outer step's new parameters.

The window then drives ``TrainerRound.inner`` and ``TrainerRound.outer``
round by round, as ``repro.core.train_adloco``'s loop does for one
trainer with merging off, until ``--seconds`` have passed.  A round ends
with ``block_until_ready`` on the trainer's parameters.

After the window the peak device memory is read, the program's state is
freed, and the float32 reference (``reference.py``) follows the same
round from the same weights and rows.  The comparison decides
``correct``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from functools import lru_cache, partial
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

from benchmarks.chip import flops as F
from benchmarks.chip import trace_reduce
from benchmarks.chip.tokens import MarkovTokenStream, PregeneratedFeed

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: a parameter leaf whose reference gradient is under this share of the
#: median leaf's moves under AdamW by round-off alone: its change is not
#: compared
STILL_LEAF = 1e-3


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    family: ModuleType          # ``families/<config["family"]>.py``
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, all found by name."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    here = root / "benchmarks" / "chip"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    config_file = here / "configs" / f"{w['config']}.json"
    config = _read_json(config_file)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=family(config, config_file),
                traffic=_read_json(here / "traffic" / f"{w['traffic']}.json"),
                limits=_read_json(here / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


@lru_cache(maxsize=None)
def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "benchmarks" / "chip" / "metrics" / f"{name}.py"
    return _module(path, "bench_metric_" + name.replace(".", "_")).read


def family(config: dict, config_file: Path) -> ModuleType:
    """The module ``families/<family>.py`` beside the directory of
    ``config_file``, which the configuration names by its ``family``
    key: all that the benchmark knows of the model's block.  It gives

    - ``arch_of(config)``: the sizes, a NamedTuple with a ``vocab_size``
      field, which the feed draws ids from;
    - ``program_config(config)``: the program's ``ModelConfig``; raises
      where the program's registry is not the file;
    - ``layout(arch)``: ``{path: (shape, std)}`` of every parameter leaf
      (leaf i of the sorted paths draws from the seed's i-th stream); a
      leaf may carry its dtype third (a float32 router), else it takes
      the served dtype;
    - ``loss(params, tokens, arch, quant)``: the float32 reference of the
      program's whole training loss, every term the program adds in it;
    - ``param_count(arch)``, and ``train_flops_per_token(arch, seq_len)``,
      the model FLOPs of the work the program does per token (of sparse
      experts, only those a token is routed to);
    - ``entry_axes(path)``: how many leading axes of a leaf the check
      compares entry by entry (a layer, a layer's expert);
    - ``tiny(config)``: the keys that cut the configuration to the CPU
      tests' sizes, and optionally ``TINY_LIMITS``, the check's limits
      there (else ``tests/tiny.py``'s).

    A per-layer metric's ``read(red, run)`` gets the trace's
    ``Reduction`` and ``run``: the roles' programs (``roles``), the
    step's model FLOPs and least bytes, the steps traced, the chip's
    peaks, this module (``family``), its ``arch``, ``seq_len`` and the
    tokens of one inner step (``step_tokens``).

    Raises, naming ``config_file``, where the key or the module is
    missing."""
    name = config.get("family")
    if name is None:
        raise ValueError(f"{config_file} names no architecture family: "
                         f"add \"family\": \"<name>\" for "
                         f"families/<name>.py")
    path = config_file.parents[1] / "families" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{config_file} names the family {name!r}, but "
                         f"there is no {path}")
    return _module(path, "bench_family_" + name.replace(".", "_"))


# ---------------------------------------------------------------- norms
def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def _norm(x, axes: int):
    """Norms of ``x`` over all but its leading ``axes`` axes; one norm,
    shaped (1,), where ``axes`` is 0."""
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    if axes:
        return jnp.sqrt(jnp.sum(jnp.square(
            x.reshape(x.shape[:axes] + (-1,))), -1))
    return jnp.sqrt(jnp.sum(jnp.square(x)))[None]


def _leaf_norms(tree, entry_axes) -> Dict[str, np.ndarray]:
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path(kp): _norm(x, entry_axes(_path(kp))) for kp, x in leaves}


def _diff_norms(a, b, entry_axes) -> Dict[str, np.ndarray]:
    import jax
    import jax.numpy as jnp
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree.leaves(b)
    return {_path(kp): _norm(x.astype(jnp.float32) - y.astype(jnp.float32),
                             entry_axes(_path(kp)))
            for (kp, x), y in zip(la, lb)}


def _host(tree) -> Dict[str, np.ndarray]:
    import jax
    return {k: np.asarray(v, np.float64)
            for k, v in jax.device_get(tree).items()}


class Norms:
    """Per-entry norms of each leaf, an entry per index of the leaf's
    leading ``family.entry_axes(path)`` axes, jitted once."""

    def __init__(self, family: ModuleType):
        import jax
        self._leaf = jax.jit(partial(_leaf_norms,
                                     entry_axes=family.entry_axes))
        self._diff = jax.jit(partial(_diff_norms,
                                     entry_axes=family.entry_axes))

    def leaf(self, tree, scale: float = 1.0):
        return {k: v * scale for k, v in _host(self._leaf(tree)).items()}

    def diff(self, a, b):
        return _host(self._diff(a, b))


# ---------------------------------------------------------- first round
class Readings(NamedTuple):
    """What one side (program, reference or control) produced in the
    first round, which the check compares.  ``grad`` and ``change`` hold
    one entry per worker."""
    losses: List[float]         # every inner step, worker by worker
    grad: List[Dict[str, np.ndarray]]
    change: List[Dict[str, np.ndarray]]
    stats: Optional[Dict[str, float]]
    outer: Optional[Dict[str, np.ndarray]]
    firsts: tuple               # indices in ``losses`` of workers' first steps


class FirstRound:
    """Reads the check's numbers while the first round runs.  Inside the
    ``with`` block the trainer's step lookup and batch decision are
    shadowed by recorders around the program's own compiled step and
    decision; leaving the block restores them, so the window calls the
    program untouched.  ``fault(step, worker)`` plants a fault under the
    recorder (tests and calibration only)."""

    def __init__(self, rnd, tr, traffic: dict, norms: Norms,
                 fault: Optional[Callable] = None):
        self.rnd, self.norms, self.fault = rnd, norms, fault
        self.x0 = tr.params
        self.H, self.M = traffic["inner_steps"], traffic["workers"]
        self.n_change = min(3, self.H)
        self.b1 = traffic["adamw"]["b1"]
        self.calls = 0
        self.losses: list = []
        self.grad: list = []
        self.change: list = []
        self.stats = self.outer = None
        self.plans: list = []

    def __enter__(self):
        cache, protocol = self.rnd.cache, self.rnd.protocol
        get, decide = cache.get, protocol.decide

        def recorded_get(plan):
            self.plans.append(plan)
            return partial(self._step, get(plan))

        def recorded_decide(st, current_b):
            b = decide(st, current_b)
            self.stats = {"mean_norm2": float(st.mean_norm2),
                          "sigma2": float(st.sigma2)}
            return b

        cache.get = recorded_get
        protocol.decide = recorded_decide
        return self

    def __exit__(self, *exc):
        del self.rnd.cache.get
        del self.rnd.protocol.decide
        return False

    def _step(self, fn, params, opt_state, batch):
        import jax
        worker, h = divmod(self.calls, self.H)
        self.calls += 1
        if self.fault is not None:
            fn = self.fault(fn, worker)
        params, opt_state, loss, grads = fn(params, opt_state, batch)
        self.losses.append(loss)
        # read before the next step, which takes this state as donated
        if h == 0:
            self.grad.append(self.norms.leaf(opt_state["m"],
                                             1.0 / (1 - self.b1)))
        if h + 1 == self.n_change:
            self.change.append(self.norms.diff(params, self.x0))
        jax.block_until_ready(params)
        return params, opt_state, loss, grads

    def finish(self, outer_params) -> Readings:
        """The program's readings, once the first outer step has made
        ``outer_params``; drops the initial parameters it held."""
        losses = [float(x) for x in self.losses]
        out = Readings(losses, self.grad, self.change, self.stats,
                       self.norms.diff(outer_params, self.x0),
                       tuple(range(0, self.H * self.M, self.H)))
        self.x0, self.losses = None, []
        return out


# ------------------------------------------------------------- the run
class CompileCounter:
    """Counts programs lowered (each new jitted program or shape)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if event == self.EVENT:
            self.n += 1


def feeds_for(cell: Cell, seed: int, rows_per_worker: int):
    t = cell.traffic
    arch = cell.family.arch_of(cell.config)
    return [PregeneratedFeed(MarkovTokenStream(
        arch.vocab_size, t["seq_len"], shard=m, seed=seed,
        branch=t["markov"]["branch"], mix=t["markov"]["mix"]
    ).rows(rows_per_worker)) for m in range(t["workers"])]


def adloco_config(traffic: dict, seed: int):
    from repro.configs.base import AdLoCoConfig
    t = traffic
    return AdLoCoConfig(
        num_outer_steps=1, num_inner_steps=t["inner_steps"],
        lr_inner=t["lr_inner"], lr_outer=t["lr_outer"],
        outer_momentum=t["outer_momentum"], weight_decay=t["weight_decay"],
        num_init_trainers=1, nodes_per_gpu=t["workers"],
        initial_batch_size=t["initial_batch_size"], max_batch=t["max_batch"],
        switch_multiplier=t["switch_multiplier"],
        max_global_batch=t["max_global_batch"], eta=t["eta"],
        stats_estimator=t["stats_estimator"], k_correct=t["k_correct"],
        adaptive=t["adaptive"], enable_switch=True, enable_merge=False,
        seed=seed % 2 ** 31)


class Program(NamedTuple):
    rnd: object
    tr: object
    feeds: list
    readings: Readings          # the program's side of the check
    names: Dict[str, tuple]     # role -> (module name, runs per round)


def build_and_first_round(cell: Cell, seed: int, rows_per_worker: int,
                          norms: Norms, fault: Optional[Callable] = None,
                          phases: Optional[Dict[str, float]] = None
                          ) -> Program:
    """Weights, feeds and the trainer, driven through its first round.
    Returns the same trainer for the window.  The seconds each part of
    the set-up took go into ``phases``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import make_weights
    from repro import models
    from repro.core.adloco import TrainerRound
    from repro.core import batching
    from repro.launch.train import build_loss_fn

    phases = {} if phases is None else phases
    t0 = time.perf_counter()

    def lap(name):
        nonlocal t0
        t1 = time.perf_counter()
        phases[name] = t1 - t0
        t0 = t1

    fam = cell.family
    cfg = fam.program_config(cell.config)
    params = make_weights(fam.layout(fam.arch_of(cell.config)), seed,
                          jnp.dtype(cfg.dtype))
    jax.block_until_ready(params)
    lap("weights")
    want = jax.eval_shape(lambda: models.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if got != want:
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter tree")
    feeds = feeds_for(cell, seed, rows_per_worker)
    lap("rows")
    rnd = TrainerRound(build_loss_fn(cfg), adloco_config(cell.traffic, seed))
    tr = rnd.init_pool([params], feeds).trainers[0]
    del params
    jax.block_until_ready(tr.inner_opt_states)
    lap("trainer")
    with FirstRound(rnd, tr, cell.traffic, norms, fault) as first:
        out = rnd.inner(tr, round_i=1)
        rnd.outer(tr, out.worker_params)
        jax.block_until_ready(tr.params)
    readings = first.finish(tr.params)
    lap("first_round")
    plan = cell.traffic["plan"]
    got_plan = first.plans[0]
    if (got_plan.micro_batch, got_plan.accum_steps, got_plan.mode) != (
            plan["micro_batch"], plan["accum_steps"], plan["mode"]):
        raise ValueError(f"the first round ran {got_plan}, the traffic "
                         f"file states {plan}")
    H, M = cell.traffic["inner_steps"], cell.traffic["workers"]
    names = {"inner": (f"jit_{rnd.cache.get(got_plan).__name__}", H * M),
             "stats": (f"jit_{batching.stats_from_microbatch_grads.__name__}",
                       1),
             "outer": (f"jit_{rnd.outer_step.__name__}", 1)}
    return Program(rnd, tr, feeds, readings, names)


class Window(NamedTuple):
    round_s: List[float]        # rounds that ended within the window
    end_s: float                # window start to the end of the last one
    attempted: int
    failed: int
    error: Optional[str]
    gc_s: float                 # Python garbage collection inside the window


class _GcClock:
    """Time spent in Python's garbage collector while installed."""

    def __init__(self):
        self.total, self._t = 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total += time.perf_counter() - self._t
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        return False


def run_window(prog: Program, seconds: float, first_round: int = 2,
               span: str = "bench.round") -> Window:
    """Rounds until ``seconds`` have passed, the last one ending after;
    each inside a host span ``span`` with ``bench.inner`` and
    ``bench.outer`` inside it."""
    import jax
    from jax.profiler import TraceAnnotation

    rnd, tr = prog.rnd, prog.tr
    round_s, ends, failed, error = [], [], 0, None
    r = first_round
    with _GcClock() as gc_clock:
        start = prev = time.perf_counter()
        while prev - start < seconds:
            try:
                with TraceAnnotation(span):
                    with TraceAnnotation("bench.inner"):
                        out = rnd.inner(tr, round_i=r)
                    with TraceAnnotation("bench.outer"):
                        rnd.outer(tr, out.worker_params)
                    jax.block_until_ready(tr.params)
            except Exception:                            # noqa: BLE001
                error = traceback.format_exc()
                failed += 1
                break
            now = time.perf_counter()
            if not math.isfinite(out.mean_loss):
                failed += 1
            round_s.append(now - prev)
            ends.append(now - start)
            prev = now
            r += 1
    inside = [i for i, e in enumerate(ends) if e <= seconds] or \
        list(range(len(ends)))
    return Window(round_s=[round_s[i] for i in inside],
                  end_s=ends[inside[-1]] if inside else 0.0,
                  attempted=len(round_s) + (1 if error else 0),
                  failed=failed, error=error, gc_s=gc_clock.total)


# ------------------------------------------------------------ reference
def reference_readings(cell: Cell, seed: int, feeds: list,
                       quant=None) -> Readings:
    """The reference follows the first round, every worker and every
    step, from the benchmark's weights and the rows the program was
    given."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import reference as R
    from benchmarks.chip.weights import make_weights

    quant = quant or R.identity
    t = cell.traffic
    fam = cell.family
    arch = fam.arch_of(cell.config)
    norms = Norms(fam)
    w0 = jax.jit(lambda w: jax.tree.map(
        lambda x: quant(x.astype(jnp.float32)), w))(
            make_weights(fam.layout(arch), seed, jnp.bfloat16))
    grad_fn = R.make_grad(fam.loss, arch, quant)
    a = t["adamw"]
    opt = R.AdamW(t["lr_inner"], a["b1"], a["b2"], a["eps"],
                  t["weight_decay"])
    # donated: the round's working set must fit beside the gradient's
    step = jax.jit(partial(opt.step, quant=quant), donate_argnums=(0, 1))
    add = jax.jit(lambda x, y: jax.tree.map(jnp.add, x, y),
                  donate_argnums=0)
    scale = jax.jit(lambda x, s: jax.tree.map(lambda l: l * s, x),
                    donate_argnums=0)
    copy = jax.jit(lambda x: jax.tree.map(jnp.copy, x))
    plan = t["plan"]
    A, mb = plan["accum_steps"], plan["micro_batch"]
    H, M = t["inner_steps"], t["workers"]
    n_change = min(3, H)

    def mean_grad(p, rows):
        total, g = 0.0, None
        for i in range(A):
            loss, gi = grad_fn(p, jnp.asarray(rows[i * mb:(i + 1) * mb]))
            total += float(loss)
            g = gi if g is None else add(g, gi)
        return total / A, (g if A == 1 else scale(g, 1.0 / A))

    losses, grad, change, last, ends = [], [], [], [], None
    for m in range(M):
        p, st = copy(w0), opt.init(w0)
        for h in range(H):
            loss, g = mean_grad(p, feeds[m].batch_rows(h))
            losses.append(loss)
            if h == 0:
                grad.append(norms.leaf(g))
            p, st = step(p, st, g)
            if h + 1 == n_change:
                change.append(norms.diff(p, w0))
        last.append(g)
        ends = p if ends is None else add(ends, p)
        del p, st, g
    stats = {k: float(v) for k, v in
             R.microbatch_stats(last, A * mb).items()}
    del last
    x1 = jax.jit(partial(R.nesterov_outer, workers=M, lr=t["lr_outer"],
                         momentum=t["outer_momentum"], quant=quant))(w0, ends)
    return Readings(losses, grad, change, stats, norms.diff(x1, w0),
                    tuple(range(0, H * M, H)))


# ----------------------------------------------------------- comparison
def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             skip: Optional[Dict[str, np.ndarray]] = None) -> float:
    """Worst entry (a leaf, or one layer of a stacked leaf) of
    |norm_prog - norm_ref| / max(norm_ref, median of norm_ref).
    Entries where ``skip`` is True are left out."""
    pv = np.concatenate([prog[k].ravel() for k in sorted(ref)])
    rv = np.concatenate([ref[k].ravel() for k in sorted(ref)])
    keep = (~np.concatenate([skip[k].ravel() for k in sorted(ref)])
            if skip is not None else np.ones(rv.shape, bool))
    floor = np.median(rv)
    gaps = np.abs(pv - rv) / np.maximum(rv, floor)
    return float(np.max(gaps[keep])) if keep.any() else float("nan")


def _still(grad: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Entries whose reference gradient is under ``STILL_LEAF`` of the
    median entry's: they move under AdamW by round-off alone."""
    floor = STILL_LEAF * np.median(
        np.concatenate([v.ravel() for v in grad.values()]))
    return {k: v < floor for k, v in grad.items()}


def _worst(gap: Callable, prog: list, ref: list) -> float:
    """The worst of ``gap(p, r)`` over paired entries; infinite where the
    program gave another number of entries than the reference."""
    if len(prog) != len(ref) or not ref:
        return float("inf")
    return max(gap(p, r) for p, r in zip(prog, ref))


def _noise_ratio(stats: Dict[str, float]) -> float:
    """sigma^2 / ||g||^2, what the norm test's batch decision reads."""
    m2 = stats["mean_norm2"]
    return stats["sigma2"] / m2 if m2 > 0 else math.inf


def compare(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers the check can hold to limits.  ``stats`` is the
    relative gap of each statistic, ``stats_ratio`` that of their ratio.
    Both statistics scale with the norm of the last step's gradients,
    which follows the whole trajectory before it: over 8 steps ``stats``
    swings with it from seed to seed, ``stats_ratio`` far less."""
    still = [_still(g) for g in ref.grad]
    pl, rl = np.asarray(prog.losses), np.asarray(ref.losses)
    gap = (np.abs(pl - rl) / np.abs(rl) if pl.shape == rl.shape
           else np.full(rl.shape, np.inf))
    # the first step of each worker starts from the seed's weights, so
    # its loss shows the forward pass alone; later steps also carry the
    # rounding of the parameters' bfloat16 updates
    return {
        "loss1": float(np.max(gap[list(ref.firsts)])),
        "loss": float(np.max(gap)),
        "grad": _worst(leaf_gap, prog.grad, ref.grad),
        "change": _worst(lambda p, r: leaf_gap(p, r[0], r[1]), prog.change,
                         list(zip(ref.change, still))),
        "stats": max(abs(prog.stats[k] - ref.stats[k]) / abs(ref.stats[k])
                     for k in ("sigma2", "mean_norm2")),
        "stats_ratio": abs(_noise_ratio(prog.stats)
                           / _noise_ratio(ref.stats) - 1),
        "outer": leaf_gap(prog.outer, ref.outer, still[0])}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the numbers that the
    cell's limits name."""
    check = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in check.values())
    return ok, check


# ------------------------------------------------------------------ run
def rows_for(cell: Cell, seconds: float, peak_flops: float) -> int:
    """Rows each worker's feed needs: the first round, and as many
    tokens as the chip could train on at its peak for ``seconds``."""
    t = cell.traffic
    arch = cell.family.arch_of(cell.config)
    per_step = t["plan"]["micro_batch"] * t["plan"]["accum_steps"]
    tokens = seconds * peak_flops / cell.family.train_flops_per_token(
        arch, t["seq_len"])
    return (t["inner_steps"] * per_step
            + math.ceil(tokens / t["seq_len"] / t["workers"]))


def require_chips(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_process: float) -> int:
    phases = {"start": time.perf_counter() - t_process}
    cell = load_cell(workload)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the trace's scopes are read from the programs' name stacks, which
    # the cache's key leaves out by default: a program compiled from other
    # source to the same operations would be found, with that source's
    # names and lines
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    require_chips(jax, cell.chips)
    phases["jax_init"] = time.perf_counter() - t_process - phases["start"]
    dev = jax.devices()[0]
    peak = F.peaks(dev.device_kind)
    print(f"bench: {workload} seed={seed} seconds={seconds} trace={int(trace)}"
          f" device={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir}", flush=True)
    t = cell.traffic
    fam = cell.family
    arch = fam.arch_of(cell.config)
    window_s = min(seconds, t["trace_seconds"]) if trace else seconds
    counter = CompileCounter()
    norms = Norms(fam)
    prog = build_and_first_round(
        cell, seed, rows_for(cell, window_s, peak["bf16_flops_per_s"]),
        norms, phases=phases)
    setup_s = time.perf_counter() - t_process
    compiles_before = counter.n
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    first_round = 2
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the round after the profiler starts pays for starting it; it
        # lies outside the traced window, which spans the bench.round spans
        run_window(prog, 1e-9, first_round, span="bench.warm")
        first_round += 1
    try:
        win = run_window(prog, window_s, first_round)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = counter.n - compiles_before
    slow = sorted(range(len(win.round_s)), key=lambda i: -win.round_s[i])[:5]
    print(f"bench: rounds={len(win.round_s)} attempted={win.attempted} "
          f"failed={win.failed} compiles_in_window={compiles} "
          f"feed_cycles={[f.cycles for f in prog.feeds]} "
          f"setup_s={setup_s!r} setup_phases={phases} gc_s={win.gc_s!r} "
          f"round_s_median="
          f"{statistics.median(win.round_s) if win.round_s else None!r} "
          f"slowest={[(i, win.round_s[i]) for i in slow]}", flush=True)
    if win.error:
        print(win.error, file=sys.stderr, flush=True)
    if compiles:
        print(f"bench: {compiles} programs compiled inside the window",
              file=sys.stderr, flush=True)
        return 3
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    plan = t["plan"]
    step_tokens = plan["micro_batch"] * plan["accum_steps"] * t["seq_len"]
    H, M = t["inner_steps"], t["workers"]
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = red.window_ns / 1e9
        run = {"roles": trace_reduce.assign_roles(red, prog.names),
               "flops_per_step": fam.train_flops_per_token(arch, t["seq_len"])
               * step_tokens,
               "bytes_per_step": F.step_bytes(fam.param_count(arch),
                                              accum=plan["accum_steps"] > 1),
               "steps_traced": H * M * red.rounds,
               "peak_flops": peak["bf16_flops_per_s"],
               "peak_bytes_per_s": peak["hbm_bytes_per_s"],
               "family": fam, "arch": arch, "seq_len": t["seq_len"],
               "step_tokens": step_tokens}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(red, run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": trace_reduce.top_scopes(red),
                     "idle_gaps": trace_reduce.top_gaps(red)}
    else:
        values = {
            "tokens_per_s": len(win.round_s) * H * M * step_tokens / win.end_s
            if win.end_s > 0 else None,
            "round_s_p95": statistics.quantiles(win.round_s, n=20)[18]
            if len(win.round_s) >= 2 else None,
            "setup_s": setup_s}
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # the check: free the program's state, then the reference
    prog_readings, feeds = prog.readings, prog.feeds
    del prog
    gc.collect()
    if win.failed:
        correct, check = False, {}
    else:
        t_ref = time.perf_counter()
        ref = reference_readings(cell, seed, feeds)
        print(f"bench: reference_s={time.perf_counter() - t_ref!r}",
              flush=True)
        correct, check = judge(compare(prog_readings, ref), cell.limits)
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    for k, v in check.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
