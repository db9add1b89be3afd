"""The reduction from a profiler trace to busy time, program time and
idle gaps."""
import json
import os

import pytest

import trace_reduce as TR

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


def test_summarize_fixture():
    with open(FIXTURE) as f:
        ev = json.load(f)
    ev = {k: ev[k] for k in ("ops", "modules", "spans")}
    out = TR.summarize(ev)
    assert out["window_s"] == pytest.approx(1000e-9)
    # union of [150, 400), [500, 800) and [950, 1000) clipped to the window
    assert out["busy_s"] == pytest.approx(600e-9)
    # self time: the loop less the op inside it
    assert out["module_s"] == pytest.approx({"jit__sweep": 650e-9,
                                             "jit_convert": 50e-9})
    assert out["device_ops"] == [["fusion.1", pytest.approx(300e-9)],
                                 ["while.9", pytest.approx(150e-9)],
                                 ["fusion.2", pytest.approx(100e-9)],
                                 ["copy.3", pytest.approx(50e-9)]]
    assert out["idle_gaps"] == [["bench.spec_build", pytest.approx(150e-9)],
                                ["bench.run", pytest.approx(150e-9)],
                                ["bench.run", pytest.approx(100e-9)]]


@pytest.mark.parametrize("hlo,short", [
    ("%copy.1 = f32[4]{0:T(128)} copy(f32[4]{0:T(128)} %a)", "copy %copy.1"),
    ("%while.6 = (f32[2]{0}, s32[]{:T(128)}) while((f32[2]{0}) %t), "
     "condition=%c", "while %while.6"),
    ("%fusion.8 = f32[8]{0:T(1024)S(1)} fusion(f32[8]{0} %x), kind=kLoop",
     "fusion %fusion.8"),
    ("fusion.1", "fusion.1")])
def test_short_name(hlo, short):
    assert TR.short_name(hlo) == short


def test_summarize_needs_a_window_and_device_ops():
    assert TR.summarize({"ops": [[]], "modules": [[]],
                         "spans": [["bench.window", 0, 10]]}) is None
    assert TR.summarize({"ops": [[["op", 0, 5]]], "modules": [[]],
                         "spans": []}) is None


def test_extract_reads_host_spans(tmp_path):
    """A CPU profile has no chip plane: the spans come out, no device
    operation does, so there is nothing to summarize."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
            f(jnp.ones(4)).block_until_ready()
    ev = TR.extract(str(tmp_path), chips=1)
    assert [n for n, _, _ in ev["spans"]] == [TR.WINDOW_SPAN]
    assert ev["ops"] == [[]]
    assert TR.summarize(ev) is None


def test_summarize_two_chips():
    """Busy time and programs per chip; the breakdown and the programs'
    time from chip 1, the busier, which sets the pace; the gaps named by
    the harness's spans alone; the program's steps of both groups."""
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "trace_two_chips.json")) as f:
        ev = json.load(f)
    out = TR.summarize({k: ev[k] for k in ("ops", "modules", "spans")})
    assert out["busy_s_per_chip"] == pytest.approx([200e-9, 850e-9])
    assert out["busy_s"] == pytest.approx(525e-9)
    assert out["pace_chip"] == 1
    assert out["module_s"] == pytest.approx(
        {"jit__sweep": 800e-9, "jit_convert": 50e-9})
    assert out["device_ops"] == [["while.9", pytest.approx(500e-9)],
                                 ["fusion.2", pytest.approx(300e-9)],
                                 ["copy.3", pytest.approx(50e-9)]]
    assert out["idle_gaps"] == [["bench.spec_build", pytest.approx(100e-9)],
                                ["bench.run", pytest.approx(50e-9)]]
    # each step summed over the two groups, the last fetch cut at 1000
    assert out["steps_s"] == pytest.approx({
        "experiment.build": 40e-9, "experiment.dispatch": 20e-9,
        "experiment.execute": 710e-9, "experiment.fetch": 130e-9})
