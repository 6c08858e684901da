"""The RMI's fused Pallas path as the lookup service serves it: its f32
state prepared once per generation from the host keys, the window it
searches in the build's meta, its stages named for the profiler, and
its programs shared by states whose windows round to one power of
two."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core import spec as spec_mod
from repro.data import sosd
from repro.kernels.rmi_lookup import ops as rops
from repro.obs.trace import SpanRecorder
from repro.serve.lookup.registry import IndexRegistry

N = 1 << 15
BRANCHING = 256


@pytest.fixture(scope="module")
def keys():
    return sosd.generate("wiki", N, seed=4)


def _rmi(keys, branching=BRANCHING):
    return spec_mod.build(spec_mod.IndexSpec(
        "rmi", {"branching": branching}, backend="pallas").validated(), keys)


def _same_state(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(np.array_equal(np.asarray(x), np.asarray(y))
                            for x, y in zip(la, lb))


def test_fused_state_from_host_keys_is_the_state_from_the_device_copy(keys):
    want = rops.prepare_f32_state(keys, branching=BRANCHING)
    host = plan_mod.lower(_rmi(keys), jnp.asarray(keys))
    read_back = plan_mod.lower(_rmi(keys), jnp.asarray(keys))
    assert _same_state(host.fused_state(keys), want)
    assert _same_state(read_back.fused_state(), want)
    assert _same_state(rops.prepare_f32_state(
        keys, branching=BRANCHING, data=jnp.asarray(keys)), want)


def test_published_generation_states_its_served_window(keys):
    reg = IndexRegistry()
    gen = reg.build_and_publish(spec_mod.IndexSpec(
        "rmi", {"branching": BRANCHING}, backend="pallas"), keys)
    meta = gen.build.meta
    fused = gen.plan.operands("pallas")["fused"]
    assert meta["served_window"] == fused.max_err
    assert meta["served_window"] == rops.prepare_f32_state(
        keys, branching=BRANCHING).max_err
    assert meta["kernel_serves"] == 1
    assert gen.plan.kernel_serves("pallas")


@pytest.mark.parametrize("index,hyper,backend,serves", [
    ("pgm", {"eps": 32}, "pallas", 1), ("pgm", {"eps": 32}, "jnp", 0),
    ("rmi", {"branching": BRANCHING}, "jnp", 0)])
def test_unfused_generations_state_the_bounds_window(keys, index, hyper,
                                                     backend, serves):
    reg = IndexRegistry()
    reg.recorder = SpanRecorder()
    gen = reg.build_and_publish(
        spec_mod.IndexSpec(index, hyper, backend=backend), keys)
    assert gen.build.meta["served_window"] == gen.plan.bounds.max_err
    assert gen.build.meta["kernel_serves"] == serves
    assert not [s for s in reg.recorder.spans() if s.name == "fused_prepare"]


def test_fused_state_is_fitted_once_per_generation_in_its_span(keys,
                                                               monkeypatch):
    from repro.serve.lookup import LookupService, LookupServiceConfig

    calls = []
    prepare = rops.prepare_f32_state

    def counted(k, *args, **kw):
        calls.append(k)
        return prepare(k, *args, **kw)

    monkeypatch.setattr(rops, "prepare_f32_state", counted)
    svc = LookupService(keys, LookupServiceConfig(
        spec=spec_mod.IndexSpec("rmi", {"branching": BRANCHING},
                                backend="pallas"),
        max_batch=256, deadline_ms=0.5, warm_buckets=(256,), trace=True))
    with svc:
        q = keys[::97]
        got = np.concatenate([svc.submit(q[i:i + 256]).result(60)
                              for i in range(0, q.size, 256)])
    np.testing.assert_array_equal(got, np.searchsorted(keys, q))
    assert len(calls) == 1 and calls[0] is not None
    assert isinstance(calls[0], np.ndarray)      # the host keys, as given
    (span,) = [s for s in svc.recorder.spans() if s.name == "fused_prepare"]
    assert span.cat == "lifecycle" and span.dur > 0
    assert span.args == {"n": N, "branching": BRANCHING,
                         "served_window": svc.generation.build.meta[
                             "served_window"]}


def _lb_text(plan, state):
    ops = {**plan.operands("pallas"), "fused": state}
    q = jnp.asarray(np.zeros(512, np.uint64))
    prog = jax.jit(plan._program_expr("lb", "pallas", True, True, None, None))
    return prog.lower(q, ops).as_text(debug_info=True)


def test_fused_program_names_its_stages(keys):
    plan = plan_mod.lower(_rmi(keys), jnp.asarray(keys))
    text = _lb_text(plan, plan.fused_state(keys))
    assert "/predict/" in text and "/last_mile/" in text
    assert "rmi_stage2_fetch" in text


def test_windows_in_one_power_of_two_share_a_program(keys):
    """The static window is ``max_err`` rounded up to the power of two
    the kernel pads it to, so two states whose windows round alike
    lower to one program; past the kernel's tile, where the exact jnp
    search's trip count follows the window, two that round apart do
    not.  Answers are exact at any window."""
    import dataclasses

    plan = plan_mod.lower(_rmi(keys), jnp.asarray(keys))
    state = plan.fused_state(keys)
    assert state.max_err < 128
    ops = plan.operands("pallas")
    q = jnp.asarray(keys[::7])

    def widened(max_err):
        return dataclasses.replace(state, max_err=max_err)

    def text(max_err):
        return plan.program("lb", backend="pallas", interpret=True).lower(
            q, {**ops, "fused": widened(max_err)}).as_text()

    assert rops.window_width(state) == rops.window_width(widened(128)) == 128
    assert text(state.max_err) == text(128)
    assert text(3000) == text(4096) != text(4097)
    for max_err in (state.max_err, 3000):
        np.testing.assert_array_equal(
            np.asarray(rops.rmi_lookup(widened(max_err), jnp.asarray(keys),
                                       q, interpret=True)),
            np.searchsorted(keys, np.asarray(q)))
