"""The step's memory as the compiler plans it: ``run.step_memory`` picks
the executable with the largest peak among those the window ran, and
``state_gib`` and ``transient_gib`` split that peak in two."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import loader
import run

GIB = 2 ** 30


# (peak, argument) bytes of the step executables whose peaks the cells
# read on a TPU v5 lite: p350m.ace-grad, p350m.full, p350m.ace-h4
RECORDED = [(15_799_167_488, 6_740_584_448), (12_558_249_984, 5_392_996_352),
            (13_495_221_760, 6_737_922_048)]


@pytest.mark.parametrize("peak,argument", RECORDED)
def test_state_and_transient_add_up_to_step_hbm(peak, argument):
    ctx = {"step_memory": {"peak_bytes": peak, "argument_bytes": argument}}
    state = loader.reader("state_gib")(ctx)
    transient = loader.reader("transient_gib")(ctx)
    assert state == argument / GIB
    assert abs(state + transient - peak / GIB) < 1e-6


@pytest.mark.parametrize("name", ["state_gib", "transient_gib"])
def test_memory_readers_read_nothing_without_a_reading(name):
    assert loader.reader(name)({}) is None


class FakeCompiled:
    def __init__(self, peak, argument):
        self.mem = types.SimpleNamespace(
            peak_memory_in_bytes=peak, argument_size_in_bytes=argument)

    def memory_analysis(self):
        return self.mem


@jax.tree_util.register_pytree_node_class
class FakeExecPlan:
    """An executable plan: its vectors are data, its key is static."""

    def __init__(self, key, perm):
        self.key, self.perm = key, perm

    def static_key(self):
        return self.key

    def tree_flatten(self):
        return (self.perm,), self.key

    @classmethod
    def tree_unflatten(cls, key, children):
        return cls(key, *children)


class FakeTrainer:
    """A trainer whose plans are their own static keys: ``lowered`` counts
    the executables built by lowering."""

    def __init__(self, sizes):
        self.sizes = sizes              # kind -> (peak, argument)
        self.lowered = []

    def exec_plan(self, plan):
        return FakeExecPlan(plan, np.arange(4, dtype=np.int32))

    def jit_step(self, ep, kind):
        trainer = self

        class Lowering:
            def lower(self, state, batch, plan):
                # the arguments as dispatched: a committed array keeps its
                # sharding, an uncommitted one and a host array take none,
                # a weak type stays weak
                assert state["w"].sharding is not None
                assert state["s"].sharding is None and state["s"].weak_type
                assert batch["tokens"].sharding is None
                assert batch["tokens"].shape == (2, 4)
                assert plan.static_key() == ep.static_key()
                assert plan.perm.shape == (4,)
                trainer.lowered.append((ep.static_key(), kind))
                return types.SimpleNamespace(
                    compile=lambda: FakeCompiled(*trainer.sizes[kind]))
        return Lowering()


def call(kind, plan):
    """One step call as the harness records it: kind, plan, the state as
    passed and the batch."""
    state = {"w": jax.device_put(jnp.ones((2, 3)), jax.devices()[0]),
             "s": jnp.asarray(2.0)}
    return kind, plan, state, {"tokens": np.zeros((2, 4), np.int32)}


def test_step_memory_takes_the_largest_peak_executable():
    trainer = FakeTrainer({"local": (13 * GIB, 6 * GIB),
                           "delta_sync": (11 * GIB, 7 * GIB)})
    calls = [call("local", "A"), call("local", "A"), call("delta_sync", "A")]
    mem = run.step_memory(trainer, calls)
    assert (mem["peak_bytes"], mem["argument_bytes"]) == (13 * GIB, 6 * GIB)
    assert set(mem["by_kind"]) == {"local", "delta_sync"}
    assert mem["by_kind"]["delta_sync"]["argument_bytes"] == 7 * GIB
    # each executable read once
    assert sorted(trainer.lowered) == [("A", "delta_sync"), ("A", "local")]
    assert mem["compiled"] == 0


def test_step_memory_counts_the_executables_it_compiles():
    fn = jax.jit(lambda state, batch, plan: state["w"].sum() * state["s"]
                 + batch["tokens"].sum() + plan.perm.sum())
    trainer = types.SimpleNamespace(
        exec_plan=lambda plan: FakeExecPlan(plan, np.arange(4, dtype=np.int32)),
        jit_step=lambda ep, kind: fn)
    first = run.step_memory(trainer, [call("local", "A")])
    assert first["compiled"] == 1
    assert first["peak_bytes"] >= first["argument_bytes"] > 0
    # the same program again: the process's own cache holds it
    assert run.step_memory(trainer, [call("local", "A")])["compiled"] == 0


def test_step_memory_of_no_step_is_an_error():
    with pytest.raises(RuntimeError):
        run.step_memory(FakeTrainer({}), [])


def test_dispatch_specs_lower_to_the_dispatched_program():
    # the same program as the live arguments lower to, so the memory read
    # finds the step's executable in the compilation cache; the state is
    # read after the call that donated it
    fn = jax.jit(lambda st, b: ({"w": st["w"] * st["s"], "s": st["s"]},
                                b.sum()), donate_argnums=(0,))
    state = {"w": jax.device_put(jnp.ones((2, 3)), jax.devices()[0]),
             "s": jnp.asarray(2.0)}
    batch = np.ones((2, 4), np.float32)
    live = fn.lower(state, batch).as_text()
    fn(state, batch)
    assert state["w"].is_deleted()
    specs = jax.tree.map(run.dispatch_spec, (state, batch))
    assert fn.lower(*specs).as_text() == live
