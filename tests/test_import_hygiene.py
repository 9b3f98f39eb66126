"""Import hygiene: importing the package must NOT initialize a JAX backend.

Module-level jnp/jax array ops (e.g. the old ``_HALF_LOG_2PI = 0.5 *
jnp.log(2 * jnp.pi)`` in nn/layers/variational.py) initialize the default
PJRT backend at import time, which breaks any caller — most importantly the
driver's ``dryrun_multichip`` — that needs to configure the platform (cpu,
virtual device count) before first backend use.
"""

import re
import subprocess
import sys
from pathlib import Path

PKG = Path(__file__).resolve().parent.parent / "deeplearning4j_tpu"


def test_import_does_not_initialize_backend():
    # Fresh interpreter: import every module in the package, then assert no
    # backend has been created. Run on cpu so a violation fails fast rather
    # than claiming an accelerator.
    code = f"""
import sys
sys.path.insert(0, {str(PKG.parent)!r})
from __graft_entry__ import _provision_cpu_mesh
_provision_cpu_mesh(1)
import pkgutil, importlib
from jax._src import xla_bridge as xb
import deeplearning4j_tpu
for m in pkgutil.walk_packages(deeplearning4j_tpu.__path__, "deeplearning4j_tpu."):
    importlib.import_module(m.name)
assert not xb._backends, f"backend initialized at import time: {{list(xb._backends)}}"
print("CLEAN")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=180
    )
    assert out.returncode == 0, out.stderr
    assert "CLEAN" in out.stdout


def test_no_module_level_jnp_ops():
    # Static guard: no top-level (column-0) assignment may CALL into
    # jnp/jax. Type aliases like Callable[[jax.Array], ...] are fine.
    offender_re = re.compile(r"^[A-Za-z_0-9]+(\s*:\s*[^=]+)?\s*=\s*.*\bj(np|ax)\.[\w.]+\(")
    offenders = []
    for path in PKG.rglob("*.py"):
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if offender_re.match(line) and "Callable" not in line:
                offenders.append(f"{path}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)
