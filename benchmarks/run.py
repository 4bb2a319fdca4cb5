"""Benchmark harness. One function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (assignment contract)."""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# --multipod / --hierarchy simulate pod meshes with 8 virtual host devices
# (--faults needs 12: its elastic soak shrinks a (3, 2, 2) fleet); XLA
# locks the device count at first use, so this must precede the jax
# import (same trick as tests/test_multipod.py, in-process).
if ("--multipod" in sys.argv or "--hierarchy" in sys.argv
        or "--faults" in sys.argv or "--audit" in sys.argv) \
        and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    _n_sim = 12 if "--faults" in sys.argv else 8
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n_sim}").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# micro: compression operators (the paper's hot loop)
# ---------------------------------------------------------------------------


def bench_compression():
    from repro.core import compression as C
    n = 1 << 20  # 1M gradient entries
    g = jnp.asarray(np.random.RandomState(0).randn(n).astype(np.float32))
    e = jnp.zeros_like(g)
    om = jnp.ones((1,), jnp.float32)
    for name, keep, bits in [("FULL", 1.0, 16), ("INT8", 1.0, 8),
                             ("TOPK10_INT8", 0.10, 8),
                             ("TOPK1_INT8", 0.01, 8)]:
        level = C.Level(name, keep, bits)
        fn = jax.jit(lambda g, e, c=level.codec: c.ef_sync(
            g, e, om, om[0], gamma=1.0, n_pods=1, block=1024,
            use_pallas=False))
        us = _time(fn, g, e)
        mbps = n * 4 / (us / 1e6) / 1e6
        wire = level.wire_bytes(n, 2)
        row(f"sync_leaf_{name}_1M", us,
            f"{mbps:.0f}MBps;wire={wire/1e3:.0f}KB")


def bench_codecs(out_path=None):
    """Per-codec microbenchmark: analytic wire bytes + wall time per size,
    written to benchmarks/results/BENCH_codecs.json so the perf trajectory
    accumulates in CI.  Sizes include the total gradient volume of the
    paper-350m SMOKE config (the reduced-width variant CI can afford —
    ~1e5 grads, not the full 350M model)."""
    from repro.codecs import build_codec, list_codecs
    from repro.configs import SMOKE_ARCHS
    from repro.core import sync as S
    from repro.kernels import ops as kops
    from repro.models.registry import build_model

    model = build_model(SMOKE_ARCHS["paper-350m"])
    model_total = int(sum(m.size for m in
                          S.group_metas(model.param_specs())))
    sizes = [1 << 18, 1 << 20, model_total]
    om = jnp.ones((1,), jnp.float32)
    records = []
    for name in list_codecs():
        codec = build_codec(name)
        for n in sizes:
            g = jnp.asarray(np.random.RandomState(0)
                            .randn(n).astype(np.float32))
            e = jnp.zeros_like(g)

            def run(g, e, c=codec, up=False):
                return c.ef_sync(g, e, om, om[0], gamma=1.0, n_pods=1,
                                 block=1024, use_pallas=up)

            us = _time(jax.jit(run), g, e, iters=3, warmup=1)
            rec = {"codec": name, "n": n, "wall_us": round(us, 1),
                   "gb_per_s": round(n * 4 / (us / 1e6) / 1e9, 3),
                   "wire_bytes_2pods": codec.wire_bytes(n, 2),
                   "is_model_total": n == model_total}
            if kops.default_use_pallas():
                # compiled Pallas path (accelerators; interpret is not a
                # meaningful perf number on CPU)
                usp = _time(jax.jit(lambda g, e: run(g, e, up=True)),
                            g, e, iters=3, warmup=1)
                rec["wall_us_pallas"] = round(usp, 1)
            records.append(rec)
            row(f"codec_{name}_{n}", us,
                f"wire={rec['wire_bytes_2pods']/1e3:.0f}KB")
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "BENCH_codecs.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "paper_350m_smoke_total_grads": model_total,
                   "records": records}, f, indent=1)
    print(f"wrote {out}", flush=True)


def bench_kernels():
    from repro.kernels import ops
    n = 1 << 18
    g = jnp.asarray(np.random.RandomState(1).randn(n).astype(np.float32))
    e = jnp.zeros_like(g)
    us = _time(lambda: ops.ef_topk(g, e, gamma=1.0, k=104)[0])
    row("kernel_ef_topk_interp_256k", us, "interpret-mode(correctness path)")
    us2 = _time(lambda: ops.quantize_int8(g)[0])
    row("kernel_quantize_int8_interp_256k", us2, "")


# ---------------------------------------------------------------------------
# table 1 + fig 2 (paper's comparison) — smoke scale
# ---------------------------------------------------------------------------


def bench_table1(steps=60):
    from benchmarks import table1
    t0 = time.perf_counter()
    res = table1.main(steps)
    us = (time.perf_counter() - t0) * 1e6
    full = res["fullsync"]["comm_bytes"]
    ace = res["acesync"]["comm_bytes"]
    red = 100 * (1 - ace / max(full, 1))
    row("table1_4strategies", us,
        f"comm_reduction={red:.1f}%;paper=60.3%")


# ---------------------------------------------------------------------------
# train/serve step timings (smoke configs)
# ---------------------------------------------------------------------------


def bench_train_step():
    import tempfile
    from repro.launch.session import TrainSession
    for arch in ("paper-350m", "qwen3-8b", "dbrx-132b", "falcon-mamba-7b",
                 "recurrentgemma-2b"):
        # empty per-run ckpt dir: always a fresh init, never a restore
        sess = TrainSession.from_config(arch, strategy="acesync",
                                        seq_len=128, batch=4, steps=100,
                                        ckpt_dir=tempfile.mkdtemp())
        tr = sess.trainer
        shape = sess.run_config.shape
        batch = sess.model.make_batch(jax.random.PRNGKey(1), shape)
        plan = tr.default_plan()
        kind = tr.strategy.representative_kind
        # the train state is donated through the step — chain it instead
        # of replaying the same (consumed) buffers
        state_box = [sess.init()]

        def step():
            state_box[0], m = tr.step(state_box[0], batch, plan, kind)
            return m["loss"]
        us = _time(step, iters=3, warmup=1)
        tok = shape.global_batch * shape.seq_len
        row(f"train_step_smoke_{arch}", us,
            f"{tok/(us/1e6):.0f}tok_s")


def bench_strategy_loop(steps=12):
    """One short hosted loop per registered strategy via the TrainSession
    facade — proves every registry entry trains end-to-end and prices its
    pod-tier traffic."""
    from repro.strategies import list_strategies
    from repro.launch.session import TrainSession
    for name in list_strategies():
        sess = TrainSession.from_config(
            "paper-350m", strategy=name, seq_len=64, batch=4, steps=steps,
            ckpt_every=0, ckpt_dir="/tmp/repro_bench_ckpt_" + name)
        t0 = time.perf_counter()
        sess.run(steps, log_every=0)
        us = (time.perf_counter() - t0) * 1e6 / steps
        row(f"strategy_loop_{name}", us,
            f"loss={sess.losses[-1]:.3f};comm={sess.comm_bytes/1e6:.2f}MB")


def _phase_breakdown(plan, mesh=None, iters=8):
    """Per-phase wall time of ONE sync round of ``plan``, micro-probed as
    separate jitted calls on the final plan's padded rung buffers:

      * ``encode``  — EF + compress (the producer side the
        backward-interleaved schedule hides behind the remaining grads);
      * ``exchange`` — the packed one-shot pod collective (0 on a 1-pod
        mesh: nothing crosses the DCN);
      * ``decode``  — the receiver-side fold, one dequant+accumulate per
        peer payload.

    Returns {phase: us_per_sync}; the caller amortises by the plan's
    sync interval.  SKIP rungs and empty buckets contribute nothing."""
    from repro.codecs.base import BLOCK, pack_payload
    from repro.kernels import ops as kops
    from jax.sharding import PartitionSpec as P

    use_pallas = kops.default_use_pallas()
    n_pods = int(mesh.shape["pod"]) if mesh is not None else 1
    phases = {"encode": 0.0, "exchange": 0.0, "decode": 0.0}
    r = np.random.RandomState(0)
    for rung, nb in enumerate(plan.bucket_sig or ()):
        lv = plan.levels[rung]
        if not nb or lv.is_skip:
            continue
        codec = lv.codec
        n = nb * BLOCK
        flat = jnp.asarray(r.randn(n).astype(np.float32))
        err = jnp.asarray(r.randn(n).astype(np.float32) * 0.1)

        def enc(f, e, c=codec):
            return c.ef_encode(f, e, gamma=0.9, use_pallas=use_pallas)
        phases["encode"] += _time(jax.jit(enc), flat, err, iters=iters)

        payload, _, _ = jax.jit(enc)(flat, err)

        if codec.supports_ring:  # per-peer payload fold codecs
            def dec(pl, c=codec, nb_=nb, n_=n):
                acc = c.accum_init(nb_)
                for _ in range(n_pods):
                    acc = c.decode_accumulate(acc, pl,
                                              jnp.float32(1.0 / 3),
                                              use_pallas=use_pallas)
                return c.accum_finalize(acc, n_, BLOCK)
            phases["decode"] += _time(jax.jit(dec), payload, iters=iters)

        if mesh is not None and n_pods > 1:
            if codec.supports_ring:
                wire, _ = pack_payload(payload)

                def exch(w):
                    return jax.lax.all_gather(w, "pod")
            else:  # FULL: the exchange IS the bf16 psum, decode-free
                wire = flat.astype(jnp.bfloat16)

                def exch(w):
                    return jax.lax.psum(w, "pod")
            smapped = jax.shard_map(exch, mesh=mesh, in_specs=P(),
                                    out_specs=P(), check_vma=False)
            phases["exchange"] += _time(jax.jit(smapped), wire,
                                        iters=iters)
    return phases


def bench_steptime(out_path=None, steps=24, warmup=6, multipod=False,
                   fail_on_recompile=False):
    """Perf trajectory of the retrace-free replan path and the chunked
    ring exchange: steps/sec for fullsync vs acesync (the new default —
    auto ring + rung-ordered apply — against a PR-3-equivalent
    one-shot/barrier variant and a forced-ring stress variant), the
    replan-to-apply latency of the async device replan, the train-step
    compile count (steady-state replans must add ZERO — CI gates on it
    with ``--fail-on-recompile``; AOT warm-ups are reported separately
    as ``warm_compiles``), the padded-vs-analytic wire-byte overhead of
    the per-rung size classes, the chosen classes / chunk grid, and the
    bidirectional-vs-unidirectional forced-ring pair.  ``--multipod`` runs on the simulated (2, 2, 2)
    pod mesh (8 virtual CPU devices).  Run WITHOUT
    ``REPRO_FORCE_INTERPRET`` — perf is measured on the production
    dispatch path (pure-jnp oracle on CPU, compiled Pallas kernels on
    accelerators); the forced Pallas INTERPRETER is a correctness
    harness whose per-grid-step op expansion taxes exactly the codec
    paths this bench compares (the kernel path's correctness is pinned
    by the test suite, not timed here).  Written to
    benchmarks/results/BENCH_step_time.json and mirrored at the repo root
    (the trajectory CI uploads)."""
    import tempfile
    from repro.configs.base import ACESyncConfig
    from repro.launch.session import TrainSession

    mesh = None
    if multipod:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    variants = [
        ("fullsync", "fullsync", 0, {}),
        ("acesync", "acesync", 6, {}),
        ("acesync", "acesync", 18, {}),
        # the adaptive interval pinned to H=2 — twice the default sync
        # cadence, the (harsher) workload earlier trajectory points used
        ("acesync_h2", "acesync", 6, dict(sync_interval_init=2)),
        # the PR-3 exchange: one-shot all_gather per rung + whole-tree
        # optimizer barrier, no backward interleaving — the baseline the
        # ring/overlap/segment-streaming path replaces
        ("acesync_oneshot_pr3", "acesync", 6,
         dict(ring_chunks=-1, overlap_apply=False,
              overlap_backward=False)),
    ]
    if multipod:
        # forced 2-chunk ring on every ring-capable rung: exercises the
        # ppermute pipeline end-to-end even at smoke bucket sizes (the
        # roofline auto path one-shots buckets this small).  The
        # bidirectional (default) and unidirectional variants are both
        # recorded: on the CPU simulator they time within noise (no real
        # full-duplex links), but the pair pins the perf trajectory for
        # real multi-pod hardware where the half-ring split is ~2x.
        variants.append(("acesync_ring2_bidir", "acesync", 6,
                         dict(ring_chunks=2, ring_bidir=True)))
        variants.append(("acesync_ring2_unidir", "acesync", 6,
                         dict(ring_chunks=2, ring_bidir=False)))

    records = []
    for name, strategy, cadence, ace_kw in variants:
        ace = ACESyncConfig(replan_every=cadence if cadence else 10 ** 9,
                            **ace_kw)
        sess = TrainSession.from_config(
            "paper-350m", strategy=strategy, mesh=mesh, seq_len=64,
            batch=4, steps=200, warmup_steps=10, ckpt_every=0,
            ckpt_dir=tempfile.mkdtemp(), acesync=ace)
        sess.run(warmup, log_every=0)            # compile + first replans
        tr = sess.trainer
        # stabilise the signature cache: keep stepping until a full
        # replan cycle adds no compiled variants (bounded) — the timed
        # window then measures the steady state the zero-retrace
        # contract is about
        stabilise_rounds = 0
        for _ in range(6):
            before = tr.compile_count()
            sess.run(max(cadence, 6), log_every=0)
            if tr.compile_count() == before:
                break
            stabilise_rounds += 1
        compiles_before = tr.compile_count()
        # best-of-3 timed windows: the CPU-sim box is shared and a single
        # short window can eat a scheduler stall; the best window is the
        # least-perturbed estimate of the steady-state step time
        sess.loop.poll_replan(block=True)
        windows = []
        for _ in range(3):
            t0 = time.perf_counter()
            sess.run(steps, log_every=0)
            windows.append(time.perf_counter() - t0)
        dt = min(windows)
        # join any background AOT warm thread before the session is
        # dropped (a daemon thread killed mid-XLA-compile aborts the
        # interpreter at teardown)
        sess.loop.poll_replan(block=True)
        compiles_after = tr.compile_count()
        sched = tr.scheduler
        plan = sess.loop.plan
        padded = sched.plan_wire_bytes(plan)
        analytic = sched.plan_wire_bytes(plan, padded=False)
        lat = sess.loop.replan_latencies
        rec = {
            "name": name,
            "strategy": strategy,
            "replan_every": cadence,
            "multipod": multipod,
            "steps_per_sec": round(steps / dt, 3),
            "us_per_step": round(dt / steps * 1e6, 1),
            "window_secs": [round(w, 3) for w in windows],
            "compile_count_warm": compiles_before,
            "stabilise_rounds": stabilise_rounds,
            "new_compiles_during_timed_steps":
                compiles_after - compiles_before,
            "replans_applied": len(lat),
            "replan_to_apply_latency_steps":
                (sum(lat) / len(lat) if lat else None),
            # ring direction + the AOT compiles the speculative replan
            # warm-up kept off the foreground step
            "ring_bidir": ace.ring_bidir,
            "warm_compiles": tr.warm_compiles,
            "wire_bytes_padded": padded,
            "wire_bytes_analytic": analytic,
            "padding_overhead_frac":
                round(padded / analytic - 1.0, 4) if analytic else 0.0,
            # the chosen per-rung size classes + ring chunk grid of the
            # final plan (the ROADMAP pad-growth knob's telemetry)
            "bucket_sig": list(plan.bucket_sig or ()),
            "ring_chunks": list(plan.ring_chunks or ()),
            "final_loss": round(sess.losses[-1], 4),
        }
        # per-phase sync wall time, amortised to us/step by the sync
        # interval (fullsync syncs every step) — the breakdown behind
        # the "encode hides behind backward" headline
        si = max(1, int(getattr(plan, "sync_interval", 1) or 1))
        ph = _phase_breakdown(plan, mesh=mesh)
        rec["sync_interval"] = si
        rec["phase_us_per_step"] = {k: round(v / si, 1)
                                    for k, v in ph.items()}
        rec["overlap_backward"] = ace.overlap_backward
        records.append(rec)
        row(f"steptime_{name}_replan{cadence}", dt / steps * 1e6,
            f"{rec['steps_per_sec']}steps_s;"
            f"recompiles={rec['new_compiles_during_timed_steps']}")
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "BENCH_step_time.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    payload = {"backend": jax.default_backend(), "multipod": multipod,
               "timed_steps": steps, "records": records}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}", flush=True)
    root_out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "BENCH_step_time.json")
    with open(root_out, "w") as f:
        json.dump(payload, f, indent=1)
    bad = [r["name"] for r in records
           if r["new_compiles_during_timed_steps"] > 0]
    if bad:
        msg = f"steady-state recompiles in: {bad}"
        if fail_on_recompile:
            raise SystemExit(msg)
        print(f"WARNING: {msg}", flush=True)
    return records


def bench_hierarchy(out_path=None, steps=24, warmup=6,
                    fail_on_recompile=False):
    """Heterogeneous-fleet benchmark of the two-tier sync topology.

    Runs a simulated (2, 2, 2) ``("pod", "edge", "data")`` mesh — a fleet
    of 4 members in 2 clusters of 2 — under a 16-device flapping 5-200
    Mbps telemetry trace, three ways: dense ``fullsync``, flat ``acesync``
    (``hier_mode=-1`` pins every rung to the one-tier fleet exchange), and
    ``acesync_hier`` (live :class:`~repro.hierarchy.ClusterState`
    re-clustering on the replan cadence, bottleneck-cluster byte budget,
    roofline-picked intra-cluster aggregation feeding the compressed
    cross-tier ring).  Records cross-tier + intra-tier wire bytes,
    steps/s, cluster-assignment churn, replan-to-apply latencies, and the
    steady-state compile count — which must stay at ZERO new entries while
    telemetry-driven replans re-cluster mid-run (CI gates on it with
    ``--fail-on-recompile``).  Written to
    benchmarks/results/BENCH_hierarchy.json and mirrored at the repo
    root."""
    import tempfile
    from repro.configs.base import ACESyncConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.session import TrainSession

    mesh = make_mesh((2, 2, 2), ("pod", "edge", "data"))
    variants = [
        ("fullsync", "fullsync", {}),
        ("acesync_flat", "acesync", dict(hier_mode=-1)),
        ("acesync_hier", "acesync_hier", {}),
    ]
    records = []
    for name, strategy, ace_kw in variants:
        ace = ACESyncConfig(replan_every=6, sync_interval_init=2, **ace_kw)
        sess = TrainSession.from_config(
            "paper-350m", strategy=strategy, mesh=mesh, seq_len=64,
            batch=4, steps=400, warmup_steps=10, ckpt_every=0,
            n_edge_devices=16, ckpt_dir=tempfile.mkdtemp(), acesync=ace)
        sess.run(warmup, log_every=0)            # compile + first replans
        tr = sess.trainer
        # stabilise the signature cache (same contract as bench_steptime):
        # steady-state replans — which keep re-clustering the fleet — must
        # add zero compiled variants before the timed window opens
        stabilise_rounds = 0
        for _ in range(6):
            before = tr.compile_count()
            sess.run(6, log_every=0)
            if tr.compile_count() == before:
                break
            stabilise_rounds += 1
        # land any in-flight replan + background AOT warm-up before the
        # timed window opens (a compile thread would steal the timed CPU)
        sess.loop.poll_replan(block=True)
        compiles_before = tr.compile_count()
        bytes_before = sess.comm_bytes
        t0 = time.perf_counter()
        sess.run(steps, log_every=0)
        dt = time.perf_counter() - t0
        # join any warm thread the timed window launched: a daemon thread
        # killed mid-XLA-compile aborts the interpreter at teardown
        sess.loop.poll_replan(block=True)
        sched = tr.scheduler
        plan = sess.loop.plan
        cs = sess.loop.clusters
        lat = sess.loop.replan_latencies
        rec = {
            "name": name,
            "strategy": strategy,
            "fleet": {"n_pods": tr.n_pods, "n_edge": tr.n_edge,
                      "n_cross": sched.n_cross,
                      "hier_enabled": sched.hier_enabled},
            "steps_per_sec": round(steps / dt, 3),
            "cross_wire_bytes_timed": sess.comm_bytes - bytes_before,
            "cross_wire_bytes_per_sync": sched.plan_wire_bytes(plan),
            "intra_wire_bytes_per_sync": sched.plan_intra_bytes(plan),
            "bucket_sig": list(plan.bucket_sig or ()),
            "hier_grid": list(plan.hier or ()),
            "cluster_updates": cs.updates,
            "cluster_churn": cs.churn,
            "cluster_reclusters": cs.reclusters,
            "replans_applied": len(lat),
            "replan_to_apply_latency_steps":
                (sum(lat) / len(lat) if lat else None),
            "compile_count_warm": compiles_before,
            "stabilise_rounds": stabilise_rounds,
            "new_compiles_during_timed_steps":
                tr.compile_count() - compiles_before,
            "warm_compiles": tr.warm_compiles,
            "final_loss": round(sess.losses[-1], 4),
        }
        records.append(rec)
        row(f"hierarchy_{name}", dt / steps * 1e6,
            f"{rec['steps_per_sec']}steps_s;"
            f"cross={rec['cross_wire_bytes_per_sync']/1e3:.0f}KB;"
            f"churn={rec['cluster_churn']};"
            f"recompiles={rec['new_compiles_during_timed_steps']}")
    by = {r["name"]: r for r in records}
    reduction = (1.0 - by["acesync_hier"]["cross_wire_bytes_per_sync"]
                 / max(by["acesync_flat"]["cross_wire_bytes_per_sync"], 1))
    payload = {"backend": jax.default_backend(),
               "timed_steps": steps,
               "cross_tier_reduction_vs_flat_acesync": round(reduction, 4),
               "records": records}
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "BENCH_hierarchy.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}", flush=True)
    root_out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "BENCH_hierarchy.json")
    with open(root_out, "w") as f:
        json.dump(payload, f, indent=1)
    row("hierarchy_cross_tier_reduction", 0.0,
        f"hier_vs_flat={100 * reduction:.1f}%")
    bad = [r["name"] for r in records
           if r["new_compiles_during_timed_steps"] > 0]
    if bad:
        msg = f"steady-state recompiles in: {bad}"
        if fail_on_recompile:
            raise SystemExit(msg)
        print(f"WARNING: {msg}", flush=True)
    if reduction <= 0:
        msg = (f"two-tier topology did not cut cross-tier bytes "
               f"(reduction={reduction:.4f})")
        if fail_on_recompile:  # CI strict mode gates the headline claim too
            raise SystemExit(msg)
        print(f"WARNING: {msg}", flush=True)
    return records


def bench_faults(out_path=None, steps=16, fail_on_recompile=False):
    """Fault-injected elastic soak on a simulated (3, 2, 2) pod mesh (12
    virtual CPU devices): pod 2 preempted mid-run, its heartbeats delayed
    on return, a checkpoint bit-rotted on disk — against a fault-free
    baseline of the same config.  Records the foreground compile count
    delta (a membership change must add ZERO — the new-P step is AOT-
    warmed in the background; CI gates on it with ``--fail-on-recompile``),
    the membership events with their warm-cache provenance, checkpoint
    integrity triage (the corrupted step must fail deep verification and
    restore must anchor elsewhere), and wall time overhead.  Written to
    benchmarks/results/BENCH_faults.json and mirrored at the repo root."""
    import tempfile
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch.mesh import make_mesh
    from repro.launch.session import TrainSession
    from repro.runtime.faults import (FaultEvent, FaultSchedule, KILL_POD,
                                      REJOIN_POD, CORRUPT_CKPT,
                                      DELAY_HEARTBEAT)

    def run_once(faults, ckpt_every=0):
        mesh = make_mesh((3, 2, 2), ("pod", "data", "model"))
        sess = TrainSession.from_config(
            "paper-350m", strategy="acesync", mesh=mesh, seq_len=64,
            batch=6, steps=steps, ckpt_every=ckpt_every,
            ckpt_dir=tempfile.mkdtemp(), fault_schedule=faults,
            blocking_replans=True)
        t0 = time.perf_counter()
        sess.run(steps, log_every=0)
        dt = time.perf_counter() - t0
        sess.finish()
        return sess, dt

    base, dt_base = run_once(None)
    schedule = FaultSchedule([
        FaultEvent(4, KILL_POD, 2),
        FaultEvent(6, DELAY_HEARTBEAT, 1, duration=2),
        FaultEvent(8, REJOIN_POD, 2),
        FaultEvent(12, CORRUPT_CKPT, 0),   # bit-rots the newest ckpt (10)
    ])
    sess, dt_fault = run_once(schedule, ckpt_every=5)
    loop = sess.loop
    new_foreground = loop.compile_count() - base.loop.compile_count()
    ck = Checkpointer(loop.ckpt.dir)
    deep_valid = ck.valid_steps(deep=True)
    rec = {
        "steps": steps,
        "baseline_steps_per_sec": round(steps / dt_base, 3),
        "faulted_steps_per_sec": round(steps / dt_fault, 3),
        "fault_overhead_frac": round(dt_fault / dt_base - 1.0, 4),
        "baseline_compile_count": base.loop.compile_count(),
        "faulted_compile_count": loop.compile_count(),
        "new_foreground_compiles_from_faults": new_foreground,
        "warm_compiles": loop.warm_compile_count(),
        "membership_events": loop.membership_events,
        "events_fired": [{"step": e.step, "kind": e.kind,
                          "target": e.target} for e in schedule.fired],
        "ckpt_steps_deep_valid": deep_valid,
        "ckpt_corrupted_step_detected": 10 not in deep_valid,
        "ckpt_restore_anchor": ck.latest_step(),
        "final_loss": round(sess.losses[-1], 4),
        "final_n_pods": loop.trainer.n_pods,
    }
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "BENCH_faults.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    payload = {"backend": jax.default_backend(), "record": rec}
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}", flush=True)
    root_out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "BENCH_faults.json")
    with open(root_out, "w") as f:
        json.dump(payload, f, indent=1)
    row("faults_elastic_soak", dt_fault / steps * 1e6,
        f"overhead={100 * rec['fault_overhead_frac']:.1f}%;"
        f"recompiles={new_foreground};"
        f"warm={rec['warm_compiles']}")
    problems = []
    if new_foreground > 0:
        problems.append(f"membership change caused {new_foreground} "
                        f"foreground recompiles")
    if not all(e.get("served_from_warm_cache")
               for e in loop.membership_events):
        problems.append("a membership swap missed the warm AOT cache")
    if not rec["ckpt_corrupted_step_detected"]:
        problems.append("corrupted checkpoint passed deep verification")
    if rec["ckpt_restore_anchor"] == 10:
        problems.append("restore anchored on the corrupted checkpoint")
    if problems:
        msg = "; ".join(problems)
        if fail_on_recompile:
            raise SystemExit(msg)
        print(f"WARNING: {msg}", flush=True)
    return rec


def bench_decode_step():
    from repro.configs import SMOKE_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models.registry import build_model
    for arch in ("paper-350m", "falcon-mamba-7b"):
        model = build_model(SMOKE_ARCHS[arch])
        params = model.init(jax.random.PRNGKey(0))
        pf = ShapeConfig("p", 64, 2, "prefill")
        batch = model.make_batch(jax.random.PRNGKey(1), pf)
        _, cache = jax.jit(model.prefill)(params, batch)
        tok = jnp.zeros((2, 1), jnp.int32)
        dec = jax.jit(model.decode_step)

        def step(c):
            return dec(params, c, jnp.int32(63), tok)[0]
        us = _time(step, cache, iters=5, warmup=2)
        row(f"decode_step_smoke_{arch}", us,
            f"{2/(us/1e6):.0f}tok_s")


# ---------------------------------------------------------------------------
# roofline summary (from dry-run artifacts, if present)
# ---------------------------------------------------------------------------


def bench_roofline_summary():
    from benchmarks import roofline
    rows = roofline.table("16x16")
    if not rows:
        row("roofline_16x16", 0.0, "no dry-run artifacts")
        return
    t0 = time.perf_counter()
    best = max(rows, key=lambda r: r["roofline_frac"])
    worst = min(rows, key=lambda r: r["roofline_frac"])
    us = (time.perf_counter() - t0) * 1e6
    row("roofline_16x16_cells", us,
        f"n={len(rows)};best={best['arch']}/{best['shape']}"
        f"@{best['roofline_frac']:.2f};"
        f"worst={worst['arch']}/{worst['shape']}"
        f"@{worst['roofline_frac']:.3f}")


def bench_audit(out_path=None, fail_on_violation=False):
    """Graph auditor over the shipped strategies on the simulated (2,2,2)
    meshes (see ``repro.analysis``): collective schema vs the ExecPlan's
    analytic schedule, donation aliasing, host-sync lint, recompile
    hazards, Pallas BlockSpec sweep.  Writes AUDIT.json to
    benchmarks/results/ and mirrors it at the repo root."""
    from repro.analysis import run_audit

    t0 = time.perf_counter()
    report = run_audit()
    us = (time.perf_counter() - t0) * 1e6
    payload = report.to_dict()
    payload["backend"] = jax.default_backend()
    out = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "AUDIT.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out}", flush=True)
    root_out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "AUDIT.json")
    with open(root_out, "w") as f:
        json.dump(payload, f, indent=1)
    row("graph_audit", us, report.summary().replace(",", ";"))
    if not report.ok and fail_on_violation:
        raise SystemExit(report.summary())
    return report


def main() -> None:
    print("name,us_per_call,derived")
    if "--audit" in sys.argv:
        bench_audit(
            fail_on_violation="--fail-on-violation" in sys.argv)
        return
    if "--codecs" in sys.argv:
        bench_codecs()
        return
    if "--steptime" in sys.argv:
        bench_steptime(multipod="--multipod" in sys.argv,
                       fail_on_recompile="--fail-on-recompile" in sys.argv)
        return
    if "--hierarchy" in sys.argv:
        bench_hierarchy(
            fail_on_recompile="--fail-on-recompile" in sys.argv)
        return
    if "--faults" in sys.argv:
        bench_faults(
            fail_on_recompile="--fail-on-recompile" in sys.argv)
        return
    bench_compression()
    bench_kernels()
    bench_codecs()
    bench_train_step()
    bench_strategy_loop()
    bench_steptime()
    bench_decode_step()
    bench_roofline_summary()
    bench_table1(steps=int(os.environ.get("TABLE1_STEPS", "60")))


if __name__ == "__main__":
    main()
