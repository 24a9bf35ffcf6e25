"""``dtype-x64``: explicit dtypes in the replay-kernel and Pallas
modules; no 32-bit float literals in the x64 modules.

The replay kernel's parity contract is *float64 arithmetic, identical to
the engine's* — it is built and invoked under
:func:`repro.utils.backend.x64`. A dtype-less ``jnp.zeros(H)`` there is
an accident waiting for the fleet-scale rewrite: the moment the kernel
is constructed outside the x64 context (or a tile is built under
``shard_map`` with default promotion), the silent f32 default shears the
accumulators off the engine's f64 and every differential test starts
chasing phantom drift. Pallas kernel modules get the same explicit-dtype
check (block specs and scratch shapes are dtype-contracts with the
compiler); the f32-literal check applies only to the x64 modules, where
``np.float32`` is either a bug or a deliberate engine-fidelity constant
(mark those with ``# repro: ignore[dtype-x64]``).

Scope: a module is an **x64 module** if it imports the x64 helper
(``X64_HELPER``, the kernel's own discipline marker) and a **kernel
module** if it imports Pallas or sits under ``KERNEL_PATTERNS``.
"""
from __future__ import annotations

import ast
from typing import List, Optional

from repro.analysis.base import Rule
from repro.analysis.findings import Finding
from repro.analysis.project import ModuleSource, Project, dotted, expand
from repro.analysis.registry import register

#: constructors checked, with the positional index where dtype may sit
CONSTRUCTORS = {
    "zeros": 1,
    "ones": 1,
    "empty": 1,
    "asarray": 1,
    "array": 1,
    "full": 2,
    "arange": 3,
    "linspace": 5,
}
#: 32/16-bit float dtypes that must not appear in x64 modules
NARROW_FLOATS = {"float32", "float16", "bfloat16"}

#: importing this marks a module as an x64 module
X64_HELPER = "repro.utils.backend.x64"
KERNEL_PATTERNS = ("/kernels/", "kernels/")


def _imports_pallas(mod: ModuleSource) -> bool:
    return any(
        "pallas" in v for v in mod.import_aliases().values()
    )


def _mode(mod: ModuleSource) -> Optional[str]:
    """"x64" | "kernel" | None — which check set applies."""
    if X64_HELPER in mod.import_aliases().values():
        return "x64"
    if any(p in "/" + mod.rel for p in KERNEL_PATTERNS) or _imports_pallas(mod):
        return "kernel"
    return None


def _has_dtype(call: ast.Call, positional_index: int) -> bool:
    if any(kw.arg == "dtype" for kw in call.keywords):
        return True
    return len(call.args) > positional_index


@register("dtype-x64")
class DtypeX64Rule(Rule):
    description = (
        "replay-kernel (x64) and Pallas modules construct arrays with "
        "explicit dtypes; x64 modules carry no f32/f16 literals"
    )

    def check(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for mod in project.by_role("src"):
            mode = _mode(mod)
            if mode is None:
                continue
            aliases = mod.import_aliases()
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call):
                    name = expand(dotted(node.func), aliases)
                    if not name:
                        continue
                    head, _, leaf = name.rpartition(".")
                    if (
                        leaf in CONSTRUCTORS
                        and head in ("jax.numpy", "jnp")
                        and not _has_dtype(node, CONSTRUCTORS[leaf])
                    ):
                        out.append(
                            mod.finding(
                                self.name, node, leaf,
                                f"dtype-less `{dotted(node.func)}(...)` in an "
                                f"{mode} module — pass an explicit dtype so the "
                                f"array's precision survives outside the "
                                f"x64 context",
                            )
                        )
                elif mode == "x64" and isinstance(node, ast.Attribute):
                    name = expand(dotted(node), aliases)
                    if name and name.split(".")[-1] in NARROW_FLOATS and (
                        name.startswith("numpy.") or name.startswith("jax.numpy.")
                        or name.startswith("jnp.")
                    ):
                        out.append(
                            mod.finding(
                                self.name, node, name.split(".")[-1],
                                f"narrow float literal `{dotted(node)}` in an "
                                f"x64 replay-kernel module — the kernel's parity "
                                f"contract is float64; if this constant "
                                f"deliberately mirrors engine state, mark the "
                                f"line `# repro: ignore[dtype-x64]`",
                            )
                        )
        return out
