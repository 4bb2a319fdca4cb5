"""Graph auditor: each pass trips on its seeded violation and stays
silent on the shipped code.

Fast tests seed violations synthetically (handcrafted HLO, broken
ExecPlans, poisoned sources, off-by-one BlockSpecs); the slow test runs
the real CLI end-to-end on the simulated 8-device mesh, like
tests/test_collectives.py."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.analysis import (  # noqa: E402
    AuditReport, audit_collectives, audit_donation, audit_exec_plan,
    audit_host_sync, audit_kernels, audit_plan_pair, check_record,
    expected_schedule, extract_collectives, parse_input_output_aliases,
    permute_direction)
from repro.analysis import lint_rules  # noqa: E402
from repro.analysis.pallas_audit import (  # noqa: E402
    PallasCallRecord, capture_pallas_calls)
from repro.core.compression import Level  # noqa: E402
from repro.core.planexec import ExecPlan  # noqa: E402

MESH = ((2, 2, 2), ("pod", "data", "model"))


def _plan(levels, sig, block=2048, **kw):
    perms = tuple(jnp.zeros((max(s, 1),), jnp.int32) for s in sig)
    return ExecPlan(levels=tuple(levels), sig=tuple(sig), block=block,
                    total_blocks=sum(sig), perms=perms,
                    omega=jnp.ones((2,), jnp.float32), **kw)


def _hlo(body: str) -> str:
    return ("HloModule seeded\n\n"
            "ENTRY %main.1 (p0.1: f32[2048]) -> f32[2048] {\n"
            "  %p0.1 = f32[2048]{0} parameter(0)\n"
            "  %h = bf16[2048]{0} convert(f32[2048]{0} %p0.1)\n"
            + body +
            "  ROOT %r = f32[2048]{0} copy(f32[2048]{0} %p0.1)\n}\n")


# the pod axis on a (2,2,2) pod-major mesh: devices 4 apart
_POD_GROUPS = "replica_groups={{0,4},{1,5},{2,6},{3,7}}"


class TestCollectivePass:
    """Pass 1: traced schedule vs analytic ExecPlan accounting."""

    def test_matching_schedule_is_clean(self):
        # one FULL rung of 1 block: analytic = 2(P-1)/P * 2n = 2n bytes
        ep = _plan([Level("FULL", 1.0, 16)], [1])
        txt = _hlo("  %ar = bf16[2048]{0} all-reduce(bf16[2048]{0} %h), "
                   + _POD_GROUPS + ", to_apply=%add\n")
        rep = AuditReport()
        out = audit_collectives(txt, ep, *MESH, n_pods=2, n_edge=1,
                                report=rep)
        assert rep.ok, rep.summary()
        assert out["traced"]["slow_bytes"] == pytest.approx(
            out["expected"]["slow_bytes"])

    def test_byte_mismatch_trips(self):
        # traced moves an f32[2048] all-reduce (8192B wire) against an
        # analytic schedule of 4096B + 4096B promotion slack -> 8192 is
        # within slack, so double the traced payload to break it
        ep = _plan([Level("FULL", 1.0, 16)], [1])
        txt = _hlo("  %big = f32[4096]{0} concatenate(f32[2048]{0} %p0.1, "
                   "f32[2048]{0} %p0.1), dimensions={0}\n"
                   "  %ar = f32[4096]{0} all-reduce(f32[4096]{0} %big), "
                   + _POD_GROUPS + ", to_apply=%add\n")
        rep = AuditReport()
        audit_collectives(txt, ep, *MESH, n_pods=2, n_edge=1, report=rep)
        assert not rep.ok
        assert any("slow-tier" in v.message for v in rep.errors())

    def test_missing_ring_permutes_trip(self):
        # chunks=(2,) promises K*(P-1)=2 ppermutes; the traced module
        # all-reduces instead
        ep = _plan([Level("INT8", 1.0, 8)], [1], chunks=(2,))
        txt = _hlo("  %ar = bf16[2048]{0} all-reduce(bf16[2048]{0} %h), "
                   + _POD_GROUPS + ", to_apply=%add\n")
        rep = AuditReport()
        audit_collectives(txt, ep, *MESH, n_pods=2, n_edge=1, report=rep)
        assert any("ppermute count" in v.message for v in rep.errors())

    def test_metric_pmeans_excluded(self):
        # a scalar loss pmean must not count as sync traffic
        ep = _plan([Level("FULL", 1.0, 16)], [1])
        txt = _hlo("  %loss = f32[2]{0} slice(f32[2048]{0} %p0.1), "
                   "slice={[0:2]}\n"
                   "  %m = f32[2]{0} all-reduce(f32[2]{0} %loss), "
                   + _POD_GROUPS + ", to_apply=%add\n"
                   "  %ar = bf16[2048]{0} all-reduce(bf16[2048]{0} %h), "
                   + _POD_GROUPS + ", to_apply=%add\n")
        rep = AuditReport()
        out = audit_collectives(txt, ep, *MESH, n_pods=2, n_edge=1,
                                report=rep)
        assert rep.ok, rep.summary()
        assert out["traced"]["n_metric_collectives"] == 1

    def test_permute_direction_classification(self):
        fwd = [(0, 1), (1, 2), (2, 3), (3, 0)]
        bwd = [(1, 0), (2, 1), (3, 2), (0, 3)]
        stride2 = [(0, 2), (1, 3), (2, 0), (3, 1)]
        assert permute_direction(fwd, (4,)) == "fwd"
        assert permute_direction(bwd, (4,)) == "bwd"
        assert permute_direction(stride2, (4,)) == "other"

    def test_expected_schedule_hier_tiers(self):
        from repro.core import planexec
        ep = _plan([Level("INT8", 1.0, 8)], [1],
                   hier=(planexec.INTRA_INT8,))
        want = expected_schedule(ep, n_pods=4, n_edge=2)
        assert want["n_cross"] == 2
        assert want["intra_bytes"] > 0
        assert want["slow_bytes"] < expected_schedule(ep, 4, 1)["slow_bytes"]


class TestDonationPass:
    """Pass 2: donate_argnums buffers must alias in the executable."""

    def _compiled_text(self, donate):
        kw = {"donate_argnums": (0,)} if donate else {}

        def f(x, y):
            return x * 2.0 + y, (x[:1] * 0.0)

        spec = jax.ShapeDtypeStruct((4096,), jnp.float32)
        return jax.jit(f, **kw).lower(spec, spec).compile().as_text()

    def test_donated_buffer_aliases_clean(self):
        txt = self._compiled_text(donate=True)
        assert parse_input_output_aliases(txt) == {0}
        rep = AuditReport()
        out = audit_donation(txt, [("['x']", 4096 * 4)], rep)
        assert rep.ok, rep.summary()
        assert out["n_missing"] == 0

    def test_undonated_buffer_trips(self):
        txt = self._compiled_text(donate=False)
        rep = AuditReport()
        out = audit_donation(txt, [("['x']", 4096 * 4)], rep)
        assert not rep.ok
        assert out["n_missing"] == 1
        assert any("NOT aliased" in v.message for v in rep.errors())

    def test_scalar_leaves_exempt(self):
        txt = self._compiled_text(donate=False)
        rep = AuditReport()
        audit_donation(txt, [("['step']", 4)], rep)
        # below the floor: only the "no alias map" violation may fire
        assert all("NOT aliased" not in v.message for v in rep.errors())


_HOT_ITEM_SRC = '''
class Loop:
    def run_steps(self, state, n):
        for _ in range(n):
            state = self.step(state)
            self.report(state)
        return state

    def step(self, state):
        return state

    def report(self, state):
        loss = state["loss"].item()
        x = np.asarray(jax.device_get(state["x"]))
        return loss, x
'''

_GUARDED_SRC = '''
class Loop:
    def run_steps(self, state, n):
        for _ in range(n):
            self.poll(state)
        return state

    def poll(self, state):
        if not _device_ready(state["sig"]):
            return None
        return np.asarray(jax.device_get(state["sig"]))
'''


class TestHostSyncPass:
    """Pass 3: no implicit device->host blocking on the hot path."""

    def test_injected_item_trips(self):
        rep = AuditReport()
        audit_host_sync(_HOT_ITEM_SRC, rep)
        msgs = [v.message for v in rep.errors()]
        assert any(".item()" in m for m in msgs)
        assert any("jax.device_get" in m for m in msgs)

    def test_readiness_guard_exempts(self):
        rep = AuditReport()
        audit_host_sync(_GUARDED_SRC, rep)
        assert rep.ok, rep.summary()

    def test_shipped_train_loop_is_clean(self):
        from repro.launch.train import TrainLoop
        rep = AuditReport()
        info = audit_host_sync(TrainLoop, rep)
        assert rep.ok, rep.summary()
        # the allowlist is load-bearing: the documented blockers were seen
        assert "_flush_metrics" in info["allowlisted"]


class TestRecompilePass:
    """Pass 4: plan fields must not widen the compiled-step cache."""

    def _ep(self):
        return _plan([Level("FULL", 1.0, 16), Level("INT8", 1.0, 8)],
                     [1, 1])

    def test_shipped_plan_shape_is_clean(self):
        rep = AuditReport()
        info = audit_exec_plan(self._ep(), rep)
        assert rep.ok, rep.summary()
        assert info["static_key_hashable"] and info["aux_fields_in_key"]

    def test_unhashable_field_trips(self):
        ep = dataclasses.replace(self._ep(), sig=[1, 1])
        rep = AuditReport()
        audit_exec_plan(ep, rep)
        assert any("unhashable" in v.message for v in rep.errors())

    def test_python_scalar_child_trips(self):
        ep = dataclasses.replace(self._ep(), omega=(1.0, 1.0))
        rep = AuditReport()
        audit_exec_plan(ep, rep)
        assert any("trace constant" in v.message for v in rep.errors())

    def test_replan_keeps_static_key(self):
        ep = self._ep()
        rep = AuditReport()
        assert audit_plan_pair(ep, ep.with_omega(ep.omega * 0.5),
                               expect_same=True, report=rep)
        assert rep.ok
        ep2 = dataclasses.replace(ep, sig=(2, 0))
        assert not audit_plan_pair(ep, ep2, expect_same=True, report=rep)
        assert not rep.ok


class TestPallasPass:
    """Pass 5: BlockSpec tiling + index-map bounds per kernel."""

    def test_off_by_one_block_trips(self):
        from jax.experimental import pallas as pl
        rec = PallasCallRecord(
            kernel_name="bad_tile", grid=(4,),
            in_specs=[pl.BlockSpec((8, 1000), lambda i: (i, 0))],
            out_specs=[], in_shapes=[(32, 1024)], out_shapes=[])
        rep = AuditReport()
        check_record(rec, rep)
        assert any("does not divide" in v.message for v in rep.errors())

    def test_untiled_gather_row_block_trips(self):
        """The (1, 1024) one-row gather block on a 2-D (NB+1, 1024)
        buffer: it divides the operand, yet Mosaic refuses it."""
        from jax.experimental import pallas as pl
        rec = PallasCallRecord(
            kernel_name="row_gather", grid=(8,),
            in_specs=[pl.BlockSpec((1, 1024), lambda i, p: (p[i], 0))],
            out_specs=[pl.BlockSpec((1, 1024), lambda i, p: (i, 0))],
            in_shapes=[(12, 1024)], out_shapes=[(8, 1024)],
            num_scalar_prefetch=1)
        rep = AuditReport()
        check_record(rec, rep)
        msgs = [v.message for v in rep.errors()]
        assert len(msgs) == 2 and all("tiling rule" in m for m in msgs)
        # the 3-D view the kernels ship: the row block is the whole of
        # the last two dims
        rec3 = PallasCallRecord(
            kernel_name="row_gather_3d", grid=(8,),
            in_specs=[pl.BlockSpec((None, 1, 1024),
                                   lambda i, p: (p[i], 0, 0))],
            out_specs=[pl.BlockSpec((None, 1, 1024),
                                    lambda i, p: (i, 0, 0))],
            in_shapes=[(12, 1, 1024)], out_shapes=[(8, 1, 1024)],
            num_scalar_prefetch=1)
        rep3 = AuditReport()
        check_record(rec3, rep3)
        assert rep3.ok, rep3.summary()

    def test_out_of_bounds_index_map_trips(self):
        from jax.experimental import pallas as pl
        rec = PallasCallRecord(
            kernel_name="oob_map", grid=(4,),
            in_specs=[pl.BlockSpec((8, 1024), lambda i: (i + 1, 0))],
            out_specs=[], in_shapes=[(32, 1024)], out_shapes=[])
        rep = AuditReport()
        check_record(rec, rep)
        assert any("out of bounds" in v.message for v in rep.errors())

    def test_capture_intercepts_without_running(self):
        from repro.kernels import quantize
        g = jnp.ones((32, 1024), jnp.float32)
        with capture_pallas_calls() as records:
            out = getattr(quantize.quantize_int8_fused, "__wrapped__")(
                g, interpret=True)
        assert records and records[0].grid == (4,)
        # the fake returns zeros: proof no kernel body executed
        assert all(float(jnp.sum(jnp.abs(o))) == 0.0
                   for o in jax.tree.leaves(out))

    def test_shipped_kernels_are_clean(self):
        rep = AuditReport()
        info = audit_kernels(rep)
        assert rep.ok, rep.summary()
        assert len(info["kernels_checked"]) >= 15
        assert not info["kernels_failed"]


class TestLintRules:
    """The AST convention pack."""

    def test_python_rng_in_device_code_trips(self):
        import ast
        tree = ast.parse("import numpy as np\n"
                         "def draw():\n"
                         "    return np.random.randn(4)\n")
        rep = AuditReport()
        lint_rules.check_python_rng("core/fake.py", tree, rep)
        assert any("Python RNG" in v.message for v in rep.errors())
        rep2 = AuditReport()  # host-side module: exempt
        lint_rules.check_python_rng("data/fake.py", tree, rep2)
        assert rep2.ok

    def test_unregistered_codec_trips(self):
        import ast
        tree = ast.parse("class MyCodec(Codec):\n"
                         "    name = 'mine'\n"
                         "class _Base(Codec):\n"
                         "    pass\n"
                         "class Sub(_Base):\n"
                         "    name = 'sub'\n")
        rep = AuditReport()
        lint_rules.check_registration("codecs/fake.py", tree, rep)
        bad = {v.details["class"] for v in rep.errors()}
        assert bad == {"MyCodec", "Sub"}  # transitive base tracked

    def test_device_plan_host_sync_trips(self):
        import ast
        tree = ast.parse(
            "def device_replan_fn(s, cfg):\n"
            "    def inner(x):\n"
            "        return helper(x)\n"
            "    return inner\n"
            "def helper(x):\n"
            "    return jax.device_get(x)\n")
        rep = AuditReport()
        lint_rules.check_device_plan_sync("core/fake.py", tree, rep)
        assert any("device control-plane" in v.message
                   for v in rep.errors())

    def test_shipped_tree_is_clean(self):
        import repro
        root = os.path.abspath(next(iter(repro.__path__)))
        rep = AuditReport()
        info = lint_rules.audit_conventions(root, rep)
        assert rep.ok, rep.summary()
        assert info["n_files"] > 40


class TestReportShape:
    def test_serialization_roundtrip(self):
        import json
        rep = AuditReport()
        rep.ran("collective_schema")
        rep.add("collective_schema", "step", "boom", details={"x": 1})
        rep.add("donation_alias", "step", "meh", severity="warning")
        d = json.loads(rep.to_json())
        assert d["ok"] is False
        assert d["n_errors"] == 1 and d["n_warnings"] == 1
        assert d["violations"][0]["pass_name"] == "collective_schema"


def test_extract_collectives_hier_axes():
    """Flat rungs on a hier mesh gather over pod+edge; the auditor must
    classify that as slow tier (regression guard for the tier split)."""
    txt = ("HloModule t\n\nENTRY %e (p: f32[1024]) -> f32[1024] {\n"
           "  %p = f32[1024]{0} parameter(0)\n"
           "  %ag = f32[4096]{0} all-gather(f32[1024]{0} %p), "
           "replica_groups={{0,2,4,6},{1,3,5,7}}, dimensions={0}\n"
           "  ROOT %r = f32[1024]{0} copy(f32[1024]{0} %p)\n}\n")
    recs = extract_collectives(txt, (2, 2, 2), ("pod", "edge", "data"))
    assert len(recs) == 1
    assert set(recs[0].axis.split("+")) == {"pod", "edge"}


@pytest.mark.slow
def test_audit_cli_end_to_end(tmp_path):
    """scripts/audit.py gates clean on the shipped fullsync strategy."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + root
    env.pop("XLA_FLAGS", None)
    out = tmp_path / "AUDIT.json"
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "audit.py"),
         "--strategy", "fullsync", "--fail-on-violation",
         "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    import json
    payload = json.loads(out.read_text())
    assert payload["ok"] is True
    assert payload["info"]["fullsync"]["donation"]["n_missing"] == 0
    assert set(payload["passes"]) >= {"collective_schema",
                                      "donation_alias", "host_sync",
                                      "recompile_hazard",
                                      "pallas_blockspec", "lint_rules"}
