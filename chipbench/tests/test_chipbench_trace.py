"""The trace reduction: device idle share, replay program time and idle
gaps, on hand-made traces and on a trace recorded on a TPU v5e."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import tracefile  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "recorded_trace.json.gz"
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _trace(ops0, ops1=None, modules0=(), modules1=(), host=(), window=(0, 100)):
    devices = {DEV0: {"ops": [list(o) for o in ops0], "modules": [list(m) for m in modules0]}}
    if ops1 is not None:
        devices[DEV1] = {"ops": [list(o) for o in ops1], "modules": [list(m) for m in modules1]}
    return {"window": list(window), "devices": devices, "host": [list(h) for h in host]}


@pytest.mark.parametrize(
    "ops, busy, gaps",
    [
        ([], 0, [(0, 100)]),
        ([("a", 10, 20)], 20, [(0, 10), (30, 100)]),
        # overlapping and nested ops count once
        ([("a", 10, 20), ("b", 15, 30), ("c", 16, 2)], 35, [(0, 10), (45, 100)]),
        # ops cut at the window's edges
        ([("a", -50, 60), ("b", 90, 40)], 20, [(10, 90)]),
        ([("a", 0, 100)], 100, []),
    ],
)
def test_busy_and_gaps(ops, busy, gaps):
    t = _trace(ops)
    assert tracefile.busy_ns(t, DEV0) == busy
    assert tracefile.idle_gaps(t, DEV0) == gaps
    share = load_reader("device_idle_share")({"trace": t})
    assert share == pytest.approx(1 - busy / 100)


def test_idle_share_is_the_mean_over_chips():
    t = _trace([("a", 0, 50)], [("a", 0, 10)])
    assert load_reader("device_idle_share")({"trace": t}) == pytest.approx(1 - 30 / 100)


def test_replay_ms_takes_the_slowest_chip_per_run():
    name = "jit_one_seed(7)"
    t = _trace(
        [], [],
        modules0=[(name, 0, 2_000_000), ("jit_other", 0, 9_000_000), (name, 50, 1_000_000)],
        modules1=[(name, 0, 3_000_000), (name, 50, 1_000_000)],
        window=(0, 10_000_000),
    )
    assert load_reader("replay_device_ms")({"trace": t}) == pytest.approx((3 + 1) / 2)
    assert load_reader("replay_device_ms")({"trace": _trace([])}) is None
    assert load_reader("device_idle_share")({"trace": None}) is None


def test_gap_named_by_the_finest_host_event():
    t = _trace(
        [("op", 45, 15)],
        host=[(tracefile.REQUEST_SPAN, 0, 100), ("TransferToDevice", 30, 12), ("tapes", 5, 30)],
    )
    b = tracefile.breakdown(t)
    assert b["device_ops"] == [["op", 15 / 1e9]]
    # gap (0, 45): "tapes" covers 30 ns of it, the transfer 12; gap
    # (60, 100): only the request span covers it
    assert b["idle_gaps"] == [["tapes", 45 / 1e9], [tracefile.REQUEST_SPAN, 40 / 1e9]]


@pytest.fixture(scope="module")
def recorded():
    """One ``choose_strategy`` call on the fleet_stress family (512
    campaigns x 4 strategies), cut from a traced run on one TPU v5e: the
    window is the call's span, times start at 0, op names keep their
    instruction name only."""
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_replay_runs(recorded):
    runs = recorded["devices"][DEV0]["modules"]
    assert [m[0].split("(")[0] for m in runs] == ["jit_one_seed"] * 4
    want = sum(m[2] for m in runs) / 4 / 1e6
    assert load_reader("replay_device_ms")({"trace": recorded}) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(195.0244585, rel=1e-12)


def test_recorded_idle_share(recorded):
    busy = tracefile.busy_ns(recorded, DEV0)
    in_programs = sum(m[2] for m in recorded["devices"][DEV0]["modules"])
    # ops run only inside the four program runs, and fill all but ~10 us
    assert in_programs - 1e5 < busy <= in_programs
    share = load_reader("device_idle_share")({"trace": recorded})
    assert share == pytest.approx(1 - busy / recorded["window"][1], rel=1e-12)
    assert share == pytest.approx(0.3572543007632818, rel=1e-12)


def test_recorded_gaps_and_breakdown(recorded):
    gaps = sorted(tracefile.idle_gaps(recorded, DEV0), key=lambda g: g[0] - g[1])
    first_run = min(m[1] for m in recorded["devices"][DEV0]["modules"])
    # the longest gap is the host's work before the first program: the tapes
    assert gaps[0][0] == 0 and gaps[0][1] >= first_run
    assert gaps[0][1] - gaps[0][0] == 330658532
    b = tracefile.breakdown(recorded)
    assert b["idle_gaps"][0] == [tracefile.REQUEST_SPAN, 0.330658532]
    assert len(b["device_ops"]) == 10
    top = b["device_ops"][:4]
    assert {n.split("/")[1] for n, _ in top} <= {"fusion.166", "fusion.168", "fusion.172"}
    assert len({n.split("/")[0] for n, _ in top}) == 4
    assert sum(s for _, s in tracefile.device_op_seconds(recorded)) == pytest.approx(
        tracefile.busy_ns(recorded, DEV0) / 1e9, rel=1e-9)


def test_concat_lays_windows_end_to_end():
    """Each planning child traces its own call: the windows are laid end to
    end, every event cut to its own window."""
    a = _trace([("x", 5, 10), ("y", 95, 20)], modules0=[("jit_one_seed", 5, 10)],
               host=[("h", -10, 30)], window=(0, 100))
    b = _trace([("z", 1010, 40), ("late", 1060, 5)], window=(1000, 1050))
    c = tracefile.concat([a, b])
    assert c["window"] == [0, 150]
    assert c["devices"][DEV0]["ops"] == [["x", 5, 10], ["y", 95, 5], ["z", 110, 40]]
    assert c["devices"][DEV0]["modules"] == [["jit_one_seed", 5, 10]]
    assert c["host"] == [["h", 0, 20]]
    assert tracefile.busy_ns(c, DEV0) == 55
    assert load_reader("device_idle_share")({"trace": c}) == pytest.approx(1 - 55 / 150)
