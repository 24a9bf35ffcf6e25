#!/usr/bin/env python3
"""The chip benchmark of the planning oracle: one cell, one seed, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the deployment as it is run) and a traffic mix (``traffic/<name>.json``: the
requests, read by :mod:`chipbench.loadgen`). Every request is answered as
the daemon has its plans answered: in a fresh child spawned by
``repro.orchestrator.plan.plan_in_child``, which runs
:mod:`chipbench.child`. This process never starts a JAX backend, so the
chip is the child's. A run

1. points the program's compile cache (``$JAX_COMPILATION_CACHE_DIR``) at
   ``.jax_cache/`` in the checkout, a fixed path, and leaves JAX's other
   cache settings at the program's defaults;
2. sets up: one request in its own child on campaign seeds reserved for it,
   which first checks the device (a TPU of a kind in ``devices.json``,
   exactly as many chips as the cell asks for) and exits the run non-zero
   with no result otherwise, then fills the cache with the programs the
   requests run;
3. measures: one caller in a closed loop, issuing requests while the clock
   is inside ``--seconds`` and waiting for each answer; the window closes
   with the last answer. With ``--trace 1`` each child traces its call;
4. compares every answer of the window with the plain reference that the
   configuration names (``"reference"`` in its file: a module under
   ``chipbench/``, :mod:`chipbench.reference` or one composed from its
   pieces, which brings the configuration's own failure process kinds,
   repair distribution or workload state; a configuration that names none
   is refused), and prints each compared number beside its limit as the
   last lines of standard error;
5. prints one JSON object as the last line of standard output: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
   with ``--trace 1`` its per-layer ones, each read by ``metrics/<name>.py``),
   ``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"

for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(name: str) -> Dict:
    """The cell with its configuration and traffic files resolved."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = dict(cells[name])
    cell["bench"] = bench
    cell["configuration"] = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    cell["mix"] = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_reference(configuration: Dict):
    """The plain reference the configuration names: the module
    ``chipbench.<reference>``, whose ``decide`` answers as the oracle must."""
    name = configuration.get("reference")
    if name is None:
        raise SystemExit(f"configuration {configuration['name']!r} names no reference; give it "
                         '"reference": "<module under chipbench/>" ("reference": the default)')
    return importlib.import_module(f"chipbench.{name}")


def read_metrics(cell: Dict, kind: str, run: Dict) -> Dict:
    out = {}
    for m in cell["bench"][kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, look_for_chip: bool = True):
    """Set up, measure and check one run; returns ``(result, checks)``.
    ``look_for_chip=False`` skips the device check, for tests on the CPU."""
    from chipbench import check, child, loadgen, tracefile
    from repro.orchestrator.plan import plan_in_child
    from repro.scenarios.spec import ScenarioSpec

    reference = load_reference(cell["configuration"])
    mix, spec_dict = cell["mix"], cell["configuration"]["spec"]
    loadgen.validate(mix)
    spec = ScenarioSpec.from_dict(spec_dict)
    warm, _ = plan_in_child(child.setup, mix["entry"], spec,
                            loadgen.request(mix, spec, loadgen.warm_base(mix, seed)),
                            cell["chips"] if look_for_chip else None)
    if "refused" in warm:
        raise SystemExit(warm["refused"])
    log(f"set-up request: {warm['call_s']!r} s in its child, {warm['compiles']} XLA compiles "
        f"({warm['compile_s']!r} s)")

    answered, latencies, failed = [], [], 0
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as trace_root:
        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() < t0 + seconds:
            base = loadgen.request_base(mix, seed, k)
            trace_dir = os.path.join(trace_root, str(k)) if trace else None
            k += 1
            ts = time.perf_counter()
            try:
                out, _ = plan_in_child(child.decide, mix["entry"], spec,
                                       loadgen.request(mix, spec, base), trace_dir)
            except Exception:
                failed += 1
                traceback.print_exc()
                out = None
            latencies.append(time.perf_counter() - ts)
            log(f"request {k - 1}: campaign seeds from {base}, {latencies[-1]!r} s issue to answer"
                + ("" if out is None else
                   f", {out['call_s']!r} s in the call, {out['compiles']} XLA compiles"))
            if out is not None:
                answered.append((base, out, trace_dir))
        t1 = time.perf_counter()
        trace_data = (tracefile.concat([tracefile.load(d) for _, _, d in answered])
                      if trace and answered else None)

    pairs = [
        (out["answer"], reference.decide(spec_dict, mix["candidates"], mix["n_seeds"], base,
                                         out["reinstate_s"], detector=mix["detector"]))
        for base, out, _ in answered
    ]
    correct, checks = check.verdict(check.compare(pairs, failed))

    run = {
        "setup_s": t0 - T_START,
        "window_s": t1 - t0,
        "trials": [loadgen.trials(mix)] * len(answered),
        "call_s": [out["call_s"] for _, out, _ in answered],
        "compiles_in_window": sum(out["compiles"] for _, out, _ in answered),
        "trace": trace_data,
    }
    log(f"requests in the window: {len(latencies)} ({len(answered)} answered and compared "
        f"with the reference) over {run['window_s']!r} s; set-up {run['setup_s']!r} s")
    dev = dict(warm["device"])
    result = {"correct": correct, "attempted": len(latencies), "failed": failed}
    if trace_data is not None:
        window = tracefile.window_ns(trace_data)
        busy = {d: tracefile.busy_ns(trace_data, d) for d in sorted(trace_data["devices"])}
        for d, b in busy.items():
            log(f"{d}: busy {b / 1e9!r} s of {window / 1e9!r} s traced, "
                f"idle share {1 - b / window!r}")
        dev["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        dev["window_s"] = window / 1e9
    result["metrics"] = read_metrics(cell, "per_layer" if trace else "end_to_end", run)
    result["device"] = dev
    if trace_data is not None:
        result["breakdown"] = tracefile.breakdown(trace_data)
    result["checks"] = {
        k: {key: (v if math.isfinite(v) else str(v)) for key, v in c.items()}
        for k, c in checks.items()
    }
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    # the program keeps its compile cache where this variable says; here
    # that is a fixed directory of this checkout, and JAX writes no entry
    # into a directory that does not exist
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    result, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
