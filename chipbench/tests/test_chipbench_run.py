"""The command refuses to measure anywhere but on the TPU the cell asks
for: it exits non-zero and prints no result line."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

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
