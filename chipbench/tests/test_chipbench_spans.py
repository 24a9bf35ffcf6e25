"""The span readers that split the oracle's call, on hand-made traces and
on a decision traced on a TPU v5e."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import spans, tracefile  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "recorded_spans_trace.json.gz"
READERS = ("micro_calibration_s", "tape_compile_s", "verdict_tapes_s", "frames_s",
           "jit_trace_lower_s", "xla_compile_s", "untraced_call_share")
REQUEST = tracefile.REQUEST_SPAN


def _run(host, decisions=1, window=(0, 1000)):
    trace = {"window": list(window), "devices": {}, "host": [list(h) for h in host]}
    return {"trace": trace, "call_s": [1.0] * decisions}


@pytest.mark.parametrize(
    "reader, run, want",
    [
        # a span nested in a span of its own name counts once
        ("tape_compile_s", _run([("repro.tapes", 100, 200), ("repro.tapes", 150, 50)]), 200e-9),
        # self time: JAX's tracing, lowering and compile inside the span left out, once
        ("micro_calibration_s",
         _run([("repro.micro", 0, 500), ("trace_to_jaxpr_dynamic", 100, 100),
               ("backend_compile_and_load", 150, 200), ("PjitFunction(iota)", 400, 50)]), 250e-9),
        # a JAX span outside the program span takes nothing from it
        ("verdict_tapes_s",
         _run([("repro.verdicts", 0, 100), ("lower_sharding_computation", 100, 300)]), 100e-9),
        # the mean over the traced decisions, each cut to the window
        ("frames_s", _run([("repro.frames", 0, 300), ("repro.frames", 1000, 300),
                           ("repro.frames", 1900, 300)], decisions=2, window=(0, 2000)),
         350e-9),
        # every program's tracing and lowering, nested events once
        ("jit_trace_lower_s",
         _run([("trace_to_jaxpr_dynamic", 0, 100), ("trace_to_jaxpr_dynamic", 20, 30),
               ("lower_sharding_computation", 100, 50), ("repro.tapes", 0, 1000)]), 150e-9),
        ("xla_compile_s",
         _run([("backend_compile_and_load", 0, 400), ("backend_compile", 100, 100),
               ("backend_compile", 600, 100)], decisions=2), 250e-9),
        # no compile in the trace: none to count
        ("xla_compile_s", _run([("repro.tapes", 0, 10)]), 0.0),
        # half the request under a program span, a jitted call and a fetch
        ("untraced_call_share",
         _run([(REQUEST, 0, 1000), ("repro.tapes", 0, 200), ("PjitFunction(one_seed)", 400, 200),
               ("trace_to_jaxpr_dynamic", 700, 100), ("np.asarray(jax.Array)", 900, 100)]), 0.5),
        # covered time between two requests counts for nothing: 200 of 900 ns
        ("untraced_call_share",
         _run([(REQUEST, 0, 500), (REQUEST, 600, 400), ("repro.frames", 400, 300)]), 7 / 9),
        # no request span: nothing to share out
        ("untraced_call_share", _run([("repro.tapes", 0, 100)]), None),
        # a program without the span (an older tree) reads nothing
        ("micro_calibration_s", _run([("repro.tapes", 0, 100)]), None),
        ("frames_s", _run([(REQUEST, 0, 1000)]), None),
    ]
    + [(r, {"trace": None, "call_s": [1.0]}, None) for r in READERS]
    + [(r, _run([(REQUEST, 0, 1000), ("repro.micro", 0, 10), ("repro.tapes", 0, 10),
                 ("repro.verdicts", 0, 10), ("repro.frames", 0, 10)], decisions=0), None)
       for r in READERS],
)
def test_span_reader(reader, run, want):
    got = load_reader(reader)(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12, abs=1e-18)


@pytest.mark.parametrize(
    "a, b, want",
    [
        ([(0, 10)], [(5, 15)], [(5, 10)]),
        ([(0, 10), (20, 30)], [(5, 25)], [(5, 10), (20, 25)]),
        ([(0, 10)], [(10, 20)], []),
        ([], [(0, 1)], []),
    ],
)
def test_intersect(a, b, want):
    assert spans.intersect(a, b) == want
    assert spans.intersect(b, a) == want


@pytest.fixture(scope="module")
def recorded():
    """One traced ``table2_random.child200`` decision (200 campaigns x 4
    strategies, a fresh planning child) on one TPU v5e, in
    :func:`tracefile.load`'s form laid from 0 by :func:`tracefile.concat`:
    the device's ops (instruction names only) and program runs, and the
    host events the span readers read (the request span, the program's
    spans, JAX's jitted calls, fetches, tracing, lowering and compiles)."""
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_decision_splits_the_call(recorded):
    run = {"trace": recorded, "call_s": [recorded["window"][1] / 1e9]}
    got = {r: load_reader(r)(run) for r in READERS + ("device_idle_share", "replay_device_ms")}
    assert all(v is not None for v in got.values()), got
    assert got["untraced_call_share"] <= 0.05
    names = [h[0] for h in recorded["host"]]
    assert names.count("repro.micro") == names.count("repro.tapes") == 1
    assert names.count("repro.verdicts") == names.count("repro.frames") == 4
