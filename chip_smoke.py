#!/usr/bin/env python
"""Chip smoke test: bring the ACE-Sync training step up on a TPU through
the entry points a user calls, and check what comes out.

    python chip_smoke.py                    # one chip
    python chip_smoke.py --four-chips       # four chips: cross-pod path only
    python chip_smoke.py --cpu-rehearsal [--four-chips]

One chip runs three phases in order:

  (a) device check: platform, device kind and count; fails off a TPU;
  (b) kernel parity: every Pallas kernel (``repro.kernels.cases``) at a
      real bucket size, compiled for the chip, against its ``ref.py``
      oracle, bit for bit;
  (c) training steps: ``fullsync`` and ``acesync`` through
      ``TrainSession.from_config("paper-350m", smoke=False, ...)`` — the
      path of ``python -m repro.launch.train --arch paper-350m`` — at the
      published widths with the depth cut to fit one chip (``DEPTH``).
      Losses must be finite.

``--four-chips`` runs only the cross-pod path: paper-350m acesync against
fullsync on a (4, 1, 1) ("pod", "data", "model") mesh, where P = 4 folds
through the deterministic fixed-point ring; per-pod parameters must come
out bit-identical.  Widths stay published, depth is ``FOUR_CHIP_DEPTH``.

``--cpu-rehearsal`` runs the same phases on the CPU at smoke size (four
virtual devices with ``--four-chips``), with the kernels interpreted; on
any other backend it refuses to run.

Each phase prints its own lines.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed; otherwise the process exits non-zero.
"""
import argparse
import collections
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: paper-350m's depth on one v5e chip: its 24 layers at full width hold
#: ~20 B/param of training state (params, AdamW moments, EF residual,
#: anchor) plus the step's transients, more than 16 GB (see PERF.md)
DEPTH = 12
#: the four-chip check's depth: most of its call goes to compiling
#: acesync's P = 4 delta_sync, which takes ~1.7x as long at 12 layers as
#: at 2 (PERF.md), and at 2 layers the plan still rings a rung over the
#: four pods
FOUR_CHIP_DEPTH = 2
STEPS = 4          # acesync's first window: H = 4 ends in a delta_sync
SEQ_LEN = 512
BATCH = 8          # per chip


def log(msg: str) -> None:
    print(msg, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-pod path on four chips")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="rehearse on the CPU at smoke size (refused on "
                         "an accelerator)")
    return ap.parse_args(argv)


def device_phase(rehearsal: bool, need: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    want = "cpu" if rehearsal else "tpu"
    if d0.platform != want:
        raise RuntimeError(f"platform {d0.platform!r}: this run needs "
                           f"{want!r}")
    if len(devs) < need:
        raise RuntimeError(f"{len(devs)} device(s), this run needs {need}")
    return info


def kernel_phase(rehearsal: bool) -> None:
    from repro.kernels import ops
    from repro.kernels.cases import REAL, kernel_cases, parity
    interpret = ops.interpret_mode()
    assert interpret == rehearsal, f"interpret mode {interpret} on this run"
    sizes = dict(rows=64, nb=200, k=104, gather_rows=150) if rehearsal \
        else REAL
    log(f"[kernels] interpret={interpret} sizes={sizes}")
    bad = []
    for i, case in enumerate(kernel_cases(**sizes)):
        t0 = time.perf_counter()
        inputs = case.inputs(seed=i)
        out = parity(case, inputs, interpret=interpret)
        del inputs
        log(f"[kernels] {case.name}: max_abs_diff={out['max_abs_diff']!r} "
            f"mismatches={out['mismatches']} "
            f"({time.perf_counter() - t0:.1f}s with compile)")
        if out["mismatches"]:
            bad.append(case.name)
    gc.collect()
    if bad:
        raise RuntimeError(f"kernels differ from their oracles: {bad}")


def peak_bytes(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if any(p is None for p in peaks):
        return "not reported by this backend"
    return " ".join(str(p) for p in peaks)


def rung_histogram(loop) -> dict:
    """Groups and parameter elements per ladder rung of the plan the
    loop last stepped."""
    plan, sizes = loop.plan, loop.trainer.scheduler.sizes
    groups, elems = collections.Counter(), collections.Counter()
    for g, li in enumerate(plan.level_idx):
        name = plan.levels[li].name
        groups[name] += 1
        elems[name] += int(sizes[g])
    return {n: {"groups": groups[n], "elements": elems[n]} for n in groups}


def session(strategy: str, rehearsal: bool, mesh, global_batch: int,
            depth: int):
    from repro.launch.session import TrainSession
    ckpt = os.path.join(ROOT, ".smoke_ckpt", strategy)
    shutil.rmtree(ckpt, ignore_errors=True)   # never resume a stale run
    return TrainSession.from_config(
        "paper-350m", strategy=strategy, mesh=mesh, smoke=rehearsal,
        seq_len=64 if rehearsal else SEQ_LEN, batch=global_batch,
        steps=STEPS, n_layers=None if rehearsal else depth,
        warmup_steps=2, ckpt_dir=ckpt, ckpt_every=0)


def train_phase(rehearsal: bool, mesh=None, global_batch: int = BATCH,
                depth: int = DEPTH) -> None:
    """Run each strategy's first window; on a pod mesh its per-pod
    parameters must come out bit-identical (the window ends in a
    sync)."""
    import jax
    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.devices()[:1])
    for strategy in ("fullsync", "acesync"):
        t0 = time.perf_counter()
        sess = session(strategy, rehearsal, mesh, global_batch, depth)
        sess.run(STEPS, log_every=1)
        sess.finish()
        losses = sess.losses
        log(f"[train] {strategy}: losses={losses!r}")
        ep = sess.trainer.exec_plan(sess.loop.plan)
        log(f"[train] {strategy}: rungs={json.dumps(rung_histogram(sess.loop))}"
            f" ring_chunks={ep.chunks} bidir={ep.bidir}")
        log(f"[train] {strategy}: compile_count={sess.loop.compile_count()} "
            f"warm_compiles={sess.loop.warm_compile_count()}")
        log(f"[train] {strategy}: peak_bytes_in_use={peak_bytes(devices)} "
            f"({time.perf_counter() - t0:.1f}s with compile)")
        if len(losses) != STEPS or not all(map(math.isfinite, losses)):
            raise RuntimeError(f"{strategy}: losses {losses}")
        if mesh is not None:
            same = pods_identical(sess.state["params"])
            log(f"[train] {strategy}: per-pod params bit-identical={same}")
            if not same:
                raise RuntimeError(f"{strategy}: pods drifted")
        del sess
        gc.collect()


def pods_identical(params) -> bool:
    import jax
    import numpy as np
    for leaf in jax.tree.leaves(params):
        a = np.asarray(jax.device_get(leaf))
        if not all((a[0] == a[p]).all() for p in range(1, a.shape[0])):
            return False
    return True


def four_chip_phase(rehearsal: bool) -> None:
    """fullsync's grad_sync steps and acesync's first window on a
    (4, 1, 1) pod mesh, one pod per chip.  acesync's window ends in a
    delta_sync whose compressed rungs ring over P = 4 and fold in fixed
    point; both strategies must leave per-pod params bit-identical."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 1, 1), ("pod", "data", "model"))
    log(f"[four-chips] mesh {dict(mesh.shape)}")
    train_phase(rehearsal, mesh, global_batch=4 * BATCH,
                depth=FOUR_CHIP_DEPTH)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # the kernel path, interpreted (a CPU-only switch)
        os.environ.setdefault("REPRO_FORCE_INTERPRET", "1")
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] compilation cache dir: {enable_compile_cache()}")
    need = 4 if args.four_chips else 1
    failed = []

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        try:
            result = fn(*a)
        except Exception:
            traceback.print_exc()
            log(f"[{name}] FAILED")
            failed.append(name)
            return None
        log(f"[{name}] ok ({time.perf_counter() - t0:.1f}s)")
        return result

    info = phase("device", device_phase, args.cpu_rehearsal, need)
    if info is None:
        return 1
    if args.four_chips:
        phase("four-chips", four_chip_phase, args.cpu_rehearsal)
    else:
        phase("kernels", kernel_phase, args.cpu_rehearsal)
        phase("train", train_phase, args.cpu_rehearsal)
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
