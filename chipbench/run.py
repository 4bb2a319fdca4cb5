#!/usr/bin/env python3
"""One run of one benchmark cell of the ACE-Sync trainer on TPU chips.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``chipbench/configs/<config>.json``: the model's sizes and
its ``family``, whose reference model and counts are
``chipbench/families/<family>.py``) and a traffic mix
(``chipbench/traffic/<traffic>.json``: the training job's batch,
sequence, strategy, pods, sync interval and exchange settings).  Each pod
is one edge node's whole replica on one chip.  The run

1. builds the program's ``TrainSession`` from them (on a ``(pods, 1, 1)``
   ``("pod", "data", "model")`` mesh over the cell's chips where there is
   more than one pod), makes the weights on the devices from ``--seed`` in
   one jitted call, seeds the token stream from ``--seed`` and the link
   telemetry from the job's own fixed seed;
2. drives the first steps through the window's own call
   (``TrainLoop.run_steps``) and reads what the comparison needs, then
   warms every executable the window uses (the first device replan's
   included) -- all of this is set-up;
3. measures for ``--seconds``: ``run_steps`` runs until the window's time
   is up, while a thread blocks on each step's outputs to time its
   completion; with ``--trace 1`` the profiler records the window and the
   cell's per-layer metrics are read from the trace and the spans;
4. frees the program's state, runs the plain reference over the first
   steps' rows and weights, and prints one JSON line: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
   traced) and, last, ``checks``: each number compared, with its limit.

Without a TPU, or with fewer devices than the cell's ``chips``, it exits
non-zero and prints no result.  ``CHIPBENCH_REHEARSAL=1`` (set by the
benchmark's own tests only) runs the same path on the CPU at the
configuration's ``rehearsal`` sizes and prints no device metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import queue
import shutil
import sys
import threading
import time
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import loader  # noqa: E402

REHEARSAL_ENV = "CHIPBENCH_REHEARSAL"
#: run-time outputs of the benchmark inside the checkout (gitignored)
WORK = os.path.join(ROOT, ".chipbench")

#: keys of a configuration file that are the harness's own; every other
#: key has to be a field of the program's ModelConfig, which gets it
HARNESS_KEYS = ("name", "source", "batch_per_node", "embedding_rows",
                "published", "reduced", "assumed", "deployment",
                "rehearsal")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class WindowClosed(Exception):
    """Raised by the feed when the measured window's time is up."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool):
    """(cell entry, benchmark, configuration dict, traffic dict), with the
    rehearsal sizes laid over both files when rehearsing."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    loader.family(cfg)          # a family without a module stops here
    if pods_of(traffic) != cell["chips"]:
        raise SystemExit(f"traffic {cell['traffic']!r} runs "
                         f"{pods_of(traffic)} pod(s): cell {name!r} asks "
                         f"for {cell['chips']} chip(s), one pod each")
    if rehearsal:
        cfg = dict(cfg, **cfg["rehearsal"])
        traffic = dict(traffic, **traffic["rehearsal"])
    return cell, bench, cfg, traffic


def pods_of(traffic: dict) -> int:
    """Edge nodes of the job, one whole replica per chip (default 1)."""
    return int(traffic.get("pods", 1))


def model_config(cfg: dict):
    """The program's ModelConfig with every key of the configuration file
    that is one of its fields; a key that is neither a field nor the
    harness's own is refused by name."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(cfg) - fields - set(HARNESS_KEYS))
    if unknown:
        raise ValueError(f"configuration {cfg.get('name')!r}: keys "
                         f"{unknown} are neither ModelConfig fields nor "
                         f"the harness's own {HARNESS_KEYS}")
    return ModelConfig(**{k: v for k, v in cfg.items() if k in fields})


def cell_limits(name: str, rehearsal: bool) -> dict:
    """The cell's limit on each compared number: those set on the chip at
    the cell's sizes, or, rehearsing, those set on the CPU at the
    rehearsal sizes."""
    doc = load_json(HERE, "limits", name + ".json")
    return doc["rehearsal"] if rehearsal else doc["limits"]


def data_seed(seed: int) -> int:
    """The token stream's seed: a 31-bit value."""
    return seed % (2 ** 31 - 1)


def weight_key(seed: int):
    """The weights' PRNG key: the low 32 bits of the seed, the rest
    folded in."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------


def check_devices(jax, chips: int, rehearsal: bool):
    devs = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devs[0].platform != want:
        log(f"[device] platform {devs[0].platform!r}: this run needs "
            f"{want!r}")
        sys.exit(3)
    if len(devs) < chips:
        log(f"[device] {len(devs)} device(s): this cell needs {chips}")
        sys.exit(3)
    return devs[:chips]


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if any(p is None for p in peaks):
        raise RuntimeError("the backend reports no peak_bytes_in_use")
    return max(peaks)


# ---------------------------------------------------------------------------
# spans around the calls into each layer, and step completions
# ---------------------------------------------------------------------------


class Spans:
    """Host spans the benchmark records around calls into the program;
    in a traced run each is also a profiler ``TraceAnnotation``."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.rec = defaultdict(list)     # name -> [(start, end)]

    def wrap(self, name: str, fn):
        import jax

        def spanned(*a, **k):
            t0 = time.perf_counter()
            try:
                if self.annotate:
                    with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
                        return fn(*a, **k)
                return fn(*a, **k)
            finally:
                self.rec[name].append((t0, time.perf_counter()))
        return spanned

    def total(self, name: str, lo: float, hi: float) -> float:
        return sum(e - s for s, e in self.rec[name] if s >= lo and e <= hi)


class Feed:
    """The token stream as ``run_steps`` sees it: counts host steps (one
    batch each) and closes the window when its time is up."""

    def __init__(self, pipeline, spans: Spans):
        self.pipeline = pipeline
        self.next_batch = spans.wrap("data", pipeline.__next__)
        self.count = 0
        self.deadline = None

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is not None \
                and time.perf_counter() >= self.deadline:
            raise WindowClosed
        batch = self.next_batch()
        self.count += 1
        return batch

    def __getattr__(self, name):
        return getattr(self.pipeline, name)


class Completions:
    """A thread that blocks on each step call's outputs and records when
    they are ready, keyed by host step; the loop itself never waits."""

    def __init__(self):
        import jax
        self._jax = jax
        self.q = queue.Queue()
        self.done = {}
        self.thread = threading.Thread(target=self._drain, daemon=True)
        self.thread.start()

    def put(self, host_step: int, outputs):
        self.q.put((host_step, outputs))

    def _drain(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            step, outputs = item
            self._jax.block_until_ready(outputs)
            self.done[step] = time.perf_counter()

    def close(self):
        self.q.put(None)
        self.thread.join()


# ---------------------------------------------------------------------------
# building the program's session
# ---------------------------------------------------------------------------


class Cell:
    """The program under test, set up for one cell and seed."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int,
                 devices, trace: bool):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ACESyncConfig, RunConfig, ShapeConfig
        from repro.data.pipeline import TokenPipeline
        from repro.launch.mesh import make_mesh
        from repro.launch.session import TrainSession
        from repro.models.registry import build_model

        family = loader.family(cfg)
        self.traffic = traffic
        self.pods = pods = pods_of(traffic)
        # one host step: every pod's rows, pod p taking the p-th share
        self.global_batch = pods * cfg["batch_per_node"]
        self.seq_len = traffic["seq_len"]
        opt, ex = traffic["optimizer"], traffic["exchange"]
        H = traffic["sync_interval"]
        if H == 1 and pods != 1:
            raise ValueError("a gradient exchange on every step is "
                             "followed for one pod only")
        model_cfg = model_config(cfg)
        if model_cfg.padded_vocab != cfg["embedding_rows"]:
            raise ValueError("embedding_rows does not match the program's "
                             "padded vocabulary")
        shape = ShapeConfig("chipbench", self.seq_len, self.global_batch,
                            "train")
        acecfg = ACESyncConfig(sync_interval_init=H, sync_interval_max=H,
                               replan_every=traffic["replan_every"],
                               gamma=ex["gamma"], accum_bits=ex["accum_bits"])
        run_cfg = RunConfig(
            model=model_cfg, shape=shape, lr=opt["lr"],
            warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"], beta1=opt["beta1"],
            beta2=opt["beta2"], weight_decay=opt["weight_decay"],
            grad_clip=opt["grad_clip"], acesync=acecfg, ckpt_every=0,
            ckpt_dir=os.path.join(WORK, "ckpt"),
            seed=traffic["estimator_seed"])
        model = build_model(model_cfg, run_cfg)
        # one pod is the program's path without a mesh; more are a pod
        # mesh, each pod's state on its own chip
        mesh = (make_mesh((pods, 1, 1), ("pod", "data", "model"),
                          devices=devices) if pods > 1 else None)
        self.sess = TrainSession(model, run_cfg, mesh=mesh,
                                 strategy=traffic["strategy"],
                                 n_edge_devices=traffic["n_edge_devices"],
                                 seed=traffic["telemetry_seed"],
                                 elastic=False)
        self.sess.pipeline = TokenPipeline(model, shape,
                                           seed=data_seed(seed))
        self.loop = self.sess.loop
        trainer = self.loop.trainer
        specs = model.param_specs()
        shapes = family.param_shapes(cfg)
        if jax.tree.map(lambda s: tuple(s.shape), specs) != jax.tree.map(
                tuple, shapes, is_leaf=lambda s: isinstance(s, tuple)):
            raise ValueError("the configuration's parameter shapes differ "
                             "from the program's")

        # the weights: one jitted call from the seed, on the devices, in
        # the program's state layout (a leading pod axis, each pod's
        # replica on its own chip)
        est_key = jax.random.PRNGKey(traffic["estimator_seed"])
        self.weight_key = weight_key(seed)

        def build(key):
            st = trainer.init_state(est_key)
            params = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (pods,) + x.shape),
                family.init_params(key, cfg))
            st["params"] = params
            if "anchor" in st:
                st["anchor"] = jax.tree.map(jnp.copy, params)
            return st

        shardings = trainer.state_shardings() if mesh is not None else None
        self.state = jax.jit(build, out_shardings=shardings)(
            self.weight_key)
        # the parameters' change from the seeded weights, leaf by leaf
        # (pod 0)
        self.change_norms = jax.jit(lambda p, key: [
            jnp.sqrt(jnp.sum(jnp.square(a[0] - b))) for a, b in zip(
                jax.tree.leaves(p),
                jax.tree.leaves(family.init_params(key, cfg)))])
        # the largest |theta_p - theta_0| over pods and leaves
        self.spread = jax.jit(lambda p: jnp.max(jnp.stack(
            [jnp.max(jnp.abs(x - x[:1])) for x in jax.tree.leaves(p)])))

        # spans around the calls into each layer, step completions
        self.spans = Spans(annotate=trace)
        self.feed = Feed(self.sess.pipeline, self.spans)
        self.completions = Completions()
        self.kinds = []                 # (host step, step kind) per call
        # (kind, plan id) -> the latest such call's (host step, kind, plan,
        # state, batch): the state as passed, donated, keeps its avals
        self.calls = {}
        step = self.spans.wrap("dispatch", trainer.step)

        def timed_step(state, batch, plan, kind="grad_sync"):
            out = step(state, batch, plan, kind)
            self.completions.put(self.feed.count, out[1])
            self.kinds.append((self.feed.count, kind))
            self.calls[(kind, id(plan))] = (self.feed.count, kind, plan,
                                            state, batch)
            return out

        trainer.step = timed_step
        self.loop.refresh_plan = self.spans.wrap("replan",
                                                 self.loop.refresh_plan)
        self.loop.poll_replan = self.spans.wrap("poll_replan",
                                                self.loop.poll_replan)

    # -- readings of the first steps ---------------------------------------
    @staticmethod
    def leaf_norms(tree):
        """Float norms of a state subtree's leaves (replica 0)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        fn = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x[0])))
             for x in jax.tree.leaves(t)]))
        return np.asarray(fn(tree)).tolist()

    def first_steps(self, n: int) -> dict:
        """Drive the first ``n`` host steps through ``run_steps`` and read
        the losses and pre-clip gradient norms (each the mean over pods),
        pod 0's first gradient as the optimizer got it (from its first
        moment), its parameters' change and its error-feedback residual,
        and the pods' spread after the last of them."""
        beta1 = self.traffic["optimizer"]["beta1"]
        state = self.state
        self.state = None
        first = None
        for s in range(n):
            state = self.loop.run_steps(state, self.feed, 1, log_every=0)
            if s == 0:
                first = [x / (1 - beta1) for x in self.leaf_norms(state["m"])]
        hist = self.loop.history[-n:]
        change = self.change_norms(state["params"], self.weight_key)
        out = {"losses": [h["loss"] for h in hist],
               "grad_norms": [h["grad_norm"] for h in hist],
               "first": first, "change": [float(x) for x in change],
               "resid": self.leaf_norms(state["ace"].errors),
               "pod_spread": (float(self.spread(state["params"]))
                              if self.pods > 1 else 0.0)}
        self.state = state
        return out

    def plan_info(self):
        plan = self.loop.plan
        rungs = [plan.levels[i].name for i in plan.level_idx]
        return rungs, [float(w) for w in plan.omega]

    def plan_summary(self) -> str:
        """Groups and parameter elements per rung of the plan in use, and
        its ring chunks per rung."""
        plan, sizes = self.loop.plan, self.loop.trainer.scheduler.sizes
        hist = {}
        for g, li in enumerate(plan.level_idx):
            h = hist.setdefault(plan.levels[li].name,
                                {"groups": 0, "elements": 0})
            h["groups"] += 1
            h["elements"] += int(sizes[g])
        ep = self.loop.trainer.exec_plan(plan)
        return (f"rungs={json.dumps(hist)} ring_chunks={tuple(ep.chunks)} "
                f"bidir={ep.bidir}")

    def warm(self, n_steps: int):
        """Run what the window runs beyond the first steps: the step on
        the compared plan with the loop's lagged metric fetch."""
        state = self.state
        self.state = None
        state = self.loop.run_steps(state, self.feed, n_steps, log_every=0)
        self.state = state


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------


def gap(a: float, b: float, base: float) -> float:
    return abs(a - b) / base if base > 0 else float("inf")


def worst_leaf(prog, ref, keep=None) -> float:
    """Worst gap over leaves between two lists of norms, each leaf against
    the larger of its reference norm and the median leaf's."""
    med = sorted(ref)[len(ref) // 2]
    return max((gap(a, b, max(b, med))
                for i, (a, b) in enumerate(zip(prog, ref))
                if keep is None or keep[i]), default=0.0)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: each step's loss, each step's pre-clip
    gradient norm, and, by the worst leaf, the first gradient as the
    optimizer got it, the parameters' change over the first steps and the
    error-feedback residual after them (what the last exchange left
    uncoded: the exchange moves it at first order where the change sees
    it at second)."""
    first_ref = ref["first"]
    med = sorted(first_ref)[len(first_ref) // 2]
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: left out of the change
    keep = [g >= 1e-3 * med for g in first_ref]
    return {
        "loss_gap": max(gap(a, b, abs(b)) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "gnorm_gap": max(gap(a, b, abs(b)) for a, b in
                         zip(prog["grad_norms"], ref["grad_norms"])),
        "grad_gap": worst_leaf(prog["first"], first_ref),
        "change_gap": worst_leaf(prog["change"], ref["change"], keep),
        "resid_gap": worst_leaf(prog["resid"], ref["resid"], keep),
    }


def reference_readings(cfg, traffic, data_seed, weight_key, rungs, omega,
                       n_steps, devices, rnd=None, fault=None):
    """The plain reference's readings of the first ``n_steps`` host steps
    (``reference.Reference.run``), each pod's replica on its own device of
    ``devices``."""
    import reference
    from tokens import TokenRows
    opt = dict(traffic["optimizer"], **traffic["exchange"])
    pods = pods_of(traffic)
    rows = TokenRows(data_seed, cfg["vocab_size"])
    batches = [rows.batch(s, pods * cfg["batch_per_node"],
                          traffic["seq_len"]) for s in range(n_steps)]
    ref = reference.Reference(cfg, opt, loader.family(cfg), rungs=rungs,
                              omega=omega, pods=pods,
                              sync_interval=traffic["sync_interval"],
                              devices=devices,
                              rnd=rnd or reference.identity, fault=fault)
    import jax
    with jax.default_matmul_precision("highest"):
        return ref.run(weight_key, batches, n_steps)


def judge(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


# ---------------------------------------------------------------------------
# the step's memory as the compiler plans it
# ---------------------------------------------------------------------------


def dispatch_spec(x):
    """A step argument's abstract value as the jitted step saw it at
    dispatch: shape, dtype, weak type, and the sharding of an array
    committed to one (a donated array keeps all four)."""
    import jax
    import numpy as np
    committed = getattr(x, "committed", False)
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                weak_type=getattr(x, "weak_type", False),
                                sharding=x.sharding if committed else None)


def step_memory(trainer, calls) -> dict:
    """The compiler's memory plan, per chip, of the step executable with
    the largest peak among those ``calls`` ran (``(kind, plan, state,
    batch)``, a call of each): ``peak_bytes`` (``peak_memory_in_bytes``:
    the arguments, the outputs not aliased to them and the temporaries
    live at the peak) and ``argument_bytes`` (train state, batch and
    plan), with each kind's two under ``by_kind``, and ``compiled``: the
    executables the backend compiled for the reading.  Each is the
    trainer's jitted step lowered for the arguments as dispatched, which
    the process's own cache or the persistent one already holds, so
    ``compiled`` is 0."""
    import jax
    compiles, hits = [], []

    def on_compile(event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def on_hit(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_hit)
    best, seen, by_kind = None, set(), {}
    try:
        for kind, plan, state, batch in calls:
            ep = trainer.exec_plan(plan)
            key = (ep.static_key(), kind)
            if key in seen:
                continue
            seen.add(key)
            specs = jax.tree.map(dispatch_spec, (state, batch, ep))
            mem = trainer.jit_step(ep, kind).lower(
                *specs).compile().memory_analysis()
            reading = {"peak_bytes": int(mem.peak_memory_in_bytes),
                       "argument_bytes": int(mem.argument_size_in_bytes)}
            by_kind[kind] = reading
            if best is None or reading["peak_bytes"] > best["peak_bytes"]:
                best = reading
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_hit)
    if best is None:
        raise RuntimeError("the window ran no step")
    return dict(best, by_kind=by_kind, compiled=len(compiles) - len(hits))


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def per_layer(bench: dict, cell_name: str, ctx: dict) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        value = loader.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_devices(chips: int, rehearsal: bool):
    """The cell's devices, with the program importable and JAX's
    persistent compilation cache on: ``(devices, cache directory)``.
    Exits non-zero without the program or without the chips.  A
    rehearsal runs on as many host-platform CPU devices as the cell has
    chips."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={chips}").strip()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("[setup] the program (src/repro) is not in this checkout")
        sys.exit(3)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    devices = check_devices(jax, chips, rehearsal)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    if not rehearsal:
        # every program of the run, however quick to compile, is cached
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices, cache_dir


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    rehearsal = os.environ.get(REHEARSAL_ENV) == "1"
    cell, bench, cfg, traffic = load_cell(args.workload, rehearsal)
    limits = cell_limits(args.workload, rehearsal)
    devices, cache_dir = open_devices(cell["chips"], rehearsal)
    import jax
    log(f"[setup] cell={args.workload} seed={args.seed} "
        f"devices={[d.device_kind for d in devices]} cache={cache_dir}")

    bench_cell = Cell(cell, cfg, traffic, args.seed, devices,
                      trace=bool(args.trace))
    loop = bench_cell.loop
    n_check = traffic["check_steps"]
    prog = bench_cell.first_steps(n_check)
    rungs, omega = bench_cell.plan_info()
    log(f"[setup] plan: {bench_cell.plan_summary()} omega={omega}")
    bench_cell.warm(traffic["warm_steps"])
    compiles0 = loop.compile_count()
    warm0 = loop.warm_compile_count()
    replans0 = len(loop.replan_latencies)
    history0 = len(loop.history)

    # ---- the measured window ---------------------------------------------
    trace_dir = os.path.join(WORK, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        trace_on = time.perf_counter()
    feed, comp = bench_cell.feed, bench_cell.completions
    state = bench_cell.state
    bench_cell.state = None
    first_window_step = feed.count + 1
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    feed.deadline = t0 + args.seconds
    try:
        loop.run_steps(state, feed, 10 ** 9, log_every=0)
    except WindowClosed:
        pass
    del state
    comp.close()
    last = feed.count
    done = [comp.done[s] for s in range(first_window_step, last + 1)
            if s in comp.done]
    t_end = max(done)
    if args.trace:
        jax.profiler.stop_trace()
        trace_off = time.perf_counter()
    # a plan still warming in the background finishes before the process
    # may exit (a compile thread cut off at exit aborts the process)
    loop.poll_replan(block=True)
    window_s = t_end - t0
    host_steps = len(done)
    compiles = loop.compile_count() - compiles0
    warm = loop.warm_compile_count() - warm0
    replans = len(loop.replan_latencies) - replans0
    # the window has to run the plan the reference follows
    plan_swaps = replans + int(bench_cell.plan_info() != (rungs, omega))
    failed = sum(1 for h in loop.history[history0:]
                 if not math.isfinite(h.get("loss", 0.0)))
    intervals = [b - a for a, b in zip([t0] + done[:-1], done)]
    tokens = host_steps * bench_cell.global_batch * bench_cell.seq_len
    log(f"[window] host_steps={host_steps} window_s={window_s!r} "
        f"compile_count_before={compiles0} foreground_compiles={compiles} "
        f"warm_compiles={warm} replans_applied={replans}")
    log(f"[window] plan {bench_cell.plan_summary()}")
    peak = 0 if rehearsal else peak_bytes(devices)
    sync_steps = sum(1 for s, k in bench_cell.kinds
                     if s >= first_window_step
                     and k in ("grad_sync", "delta_sync"))
    t_mem = time.perf_counter()
    memory = step_memory(loop.trainer, [
        call[1:] for call in bench_cell.calls.values()
        if call[0] >= first_window_step])
    log(f"[memory] the step's plan per chip: {memory['by_kind']} "
        f"compiled={memory['compiled']} "
        f"read_s={time.perf_counter() - t_mem!r}")
    spans = bench_cell.spans
    sess = bench_cell.sess

    # ---- free the program's state, then the reference ----------------------
    del bench_cell, loop, sess
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, traffic, data_seed(args.seed),
                             weight_key(args.seed), rungs, omega, n_check,
                             devices)
    log(f"[check] reference_s={time.perf_counter() - t_ref!r}")
    numbers = dict(compare(prog, ref), plan_swaps=plan_swaps,
                   pod_spread=prog["pod_spread"])
    correct = judge(numbers, limits)
    log(f"[check] program losses={prog['losses']} reference "
        f"losses={ref['losses']}")
    log(f"[check] program grad norms={prog['grad_norms']} reference "
        f"grad norms={ref['grad_norms']}")

    # ---- the result line ----------------------------------------------------
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": host_steps, "failed": failed}
    if rehearsal:
        result["rehearsal"] = True
    elif not args.trace:
        result["metrics"] = {
            "tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
            "step_ms_p95": {"value": 1e3 * percentile(intervals, 95),
                            "unit": "ms"},
            "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
            "step_hbm_gib": {"value": memory["peak_bytes"] / 2 ** 30,
                             "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = device
    else:
        import arith
        import tracereduce
        red = tracereduce.reduce_dir(trace_dir, t0, t_end, spans,
                                     (trace_on, trace_off))
        ctx = {"cfg": cfg, "traffic": traffic, "chips": cell["chips"],
               "host_steps": host_steps, "window_s": window_s,
               "tokens": tokens, "spans": spans, "t0": t0, "t_end": t_end,
               "trace": red, "peaks": arith.peaks_for(d0.device_kind),
               "sync_steps": sync_steps, "step_memory": memory}
        result["metrics"] = per_layer(bench, args.workload, ctx)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": red["top_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        log(f"check {k}={numbers[k]!r} limit={limits[k]!r}")
    print(json.dumps(result), flush=True)
    return 0


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a list."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


if __name__ == "__main__":
    sys.exit(main())
