"""The command refuses to measure anywhere but on the TPU the cell asks
for: it exits non-zero and prints no result line. It compares with the
reference that the configuration names, and refuses a configuration that
names none."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run  # noqa: E402
from chipbench.child import device_refusal  # noqa: E402

KINDS = json.loads((ROOT / "chipbench" / "devices.json").read_text())["kinds"]
V5E = "TPU v5 lite"


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "table2_random.child200",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


@pytest.mark.parametrize(
    "device, chips, refused",
    [
        ({"platform": "cpu", "kind": "cpu", "count": 1}, 1, "no TPU"),
        ({"platform": "tpu", "kind": "TPU v4", "count": 1}, 1, "not in chipbench/devices.json"),
        # more chips than the cell asks for: the oracle would shard over all
        ({"platform": "tpu", "kind": V5E, "count": 4}, 1, "JAX found 4"),
        ({"platform": "tpu", "kind": V5E, "count": 1}, 4, "JAX found 1"),
        ({"platform": "tpu", "kind": V5E, "count": 1}, 1, None),
        ({"platform": "tpu", "kind": V5E, "count": 4}, 4, None),
    ],
)
def test_device_refusal(device, chips, refused):
    why = device_refusal(device, chips, KINDS)
    if refused is None:
        assert why is None
    else:
        assert refused in why


def _cell(**configuration):
    cell = run.load_cell("table2_random.child200")
    cell["configuration"] = {k: v for k, v in dict(cell["configuration"], **configuration).items()
                             if v is not None}
    return cell


@pytest.mark.parametrize("name, correct", [("reference", True), ("tests.planted_reference", False)])
def test_run_compares_with_the_reference_the_configuration_names(name, correct):
    """The sound program against the default reference, then against one
    that names a wrong winner: only the configuration's key differs."""
    result, checks = run.run_cell(_cell(reference=name), 3_000_000_011, 0.3, False,
                                  look_for_chip=False)
    assert result["correct"] is correct, checks
    assert result["failed"] == 0
    assert (checks["winner_mismatch"]["value"] == 0) is correct


def test_configuration_without_a_reference_is_refused():
    with pytest.raises(SystemExit, match="names no reference"):
        run.run_cell(_cell(reference=None), 1, 0.3, False, look_for_chip=False)
