"""Import hygiene: importing the package must NOT initialize a JAX backend.

Module-level jnp/jax array ops (e.g. the old ``_HALF_LOG_2PI = 0.5 *
jnp.log(2 * jnp.pi)`` in nn/layers/variational.py) initialize the default
PJRT backend at import time, which breaks any caller — most importantly the
driver's ``dryrun_multichip`` — that needs to configure the platform (cpu,
virtual device count) before first backend use.

And the drawing, held: which package may import which, and the operator's
documents and scripts naming only what exists. Two parametrised tests, no
jax:

- ``test_package_imports_point_down``: every ``import`` under
  ``deeplearning4j_tpu/<package>/`` (function-level ones included) goes to a
  package of a strictly lower tier, or is one of the standing debts listed
  here with its ROADMAP name. A new upward arrow fails; so does an excuse
  whose arrow is gone.
- ``test_document_names_what_exists``: every repo path a document or a
  ``tools/*.sh`` script names exists, and every ``DL4J_TPU_*`` variable it
  names is read by the program (or, in a script, by the script itself).
"""

import ast
import glob
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

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


# ---------------------------------------------------------------------------
# layering
# ---------------------------------------------------------------------------

ROOT = str(PKG.parent)

# lowest first; a package imports only from the tiers above its own line
TIERS = (
    ("native", "obs"),
    ("utils",),
    ("ops", "eval", "datasets", "analysis"),
    ("nn",),
    ("train", "parallel"),
    ("models", "modelimport", "nlp", "clustering"),
    ("graph", "search"),
    ("serve",),
    ("ui",),
)
RANK = {p: i for i, tier in enumerate(TIERS) for p in tier}

# (importer, imported) -> the ROADMAP debt that owns the arrow and why it
# stands. An arrow a PR removes leaves this table in the same PR.
DEBTS = {
    ("nn", "train"): "D15: updaters, listeners and the resilience hooks "
                     "live under train/ and the step is built from them",
    ("nn", "parallel"): "D15: fit()/output() reach for the mesh helpers "
                        "and the inference wrapper",
    ("train", "parallel"): "D15: the elastic trainer is built on "
                           "parallel/netstore and parallel/grads",
    ("parallel", "train"): "D15: the sharded steps take their updaters "
                           "and resilience hooks from train/",
    ("parallel", "serve"): "D15: ParallelInference raises serve's ShedError",
    ("utils", "nn"): "D15: utils/serialization and utils/guesser rebuild "
                     "networks",
    ("utils", "modelimport"): "D15: utils/guesser dispatches to importers",
    ("obs", "utils"): "D15: obs.snapshot() embeds bucketing's telemetry",
    ("obs", "serve"): "D15: the fleet collector serves over "
                      "serve/httpcommon",
    ("obs", "parallel"): "D15: the fleet collector reads parallel/netstore",
    ("clustering", "search"): "D15: the nearest-neighbours server is a "
                              "VectorIndex",
    ("clustering", "serve"): "D15: the nearest-neighbours server is an "
                             "InferenceServer",
}


def _imports(path, package_parts):
    """(dotted target, line) of every import in the module at ``path``;
    relative ones resolved against ``package_parts``."""
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package_parts[:len(package_parts) - (node.level - 1)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module
            if node.module and mod != "deeplearning4j_tpu":
                yield mod, node.lineno
            else:       # ``from . import x`` / ``from deeplearning4j_tpu import x``
                for a in node.names:
                    yield f"{mod}.{a.name}", node.lineno


def _edges(package):
    """imported package -> ["file:line", ...] for one importing package."""
    out = {}
    for path in (PKG / package).rglob("*.py"):
        rel = path.relative_to(PKG.parent)
        for target, line in _imports(path, list(rel.parent.parts)):
            t = target.split(".")
            if t[0] == "deeplearning4j_tpu" and len(t) > 1 and t[1] != package:
                out.setdefault(t[1], []).append(f"{rel}:{line}")
    return out


@pytest.mark.parametrize("package", sorted(
    d.name for d in PKG.iterdir() if d.is_dir() and any(d.glob("*.py"))))
def test_package_imports_point_down(package):
    assert package in RANK, f"give {package} a tier in TIERS"
    edges = _edges(package)
    unknown = {d: s for d, s in edges.items() if d not in RANK}
    assert not unknown, f"{package} imports a package that is not there: {unknown}"
    upward = {d for d in edges if RANK[d] >= RANK[package]}
    excused = {d for (p, d) in DEBTS if p == package}
    new = {d: edges[d] for d in upward - excused}
    assert not new, (
        f"{package} (tier {RANK[package]}) imports upward, and ROADMAP D15 "
        f"does not list it: {new}")
    stale = excused - upward
    assert not stale, (
        f"{package} no longer imports {sorted(stale)}: take the arrow out "
        "of DEBTS and ROADMAP D15")


# ---------------------------------------------------------------------------
# documents and scripts
# ---------------------------------------------------------------------------

DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT) for p in
                      glob.glob(os.path.join(ROOT, "docs", "*.md"))
                      + glob.glob(os.path.join(ROOT, "tools", "*.sh"))))

_TOP_DIRS = ("deeplearning4j_tpu", "tools", "docs", "tests", "benchmark",
             ".claude")
# a path under a directory of the repo, wherever it stands in the text
_ROOTED = re.compile(
    r"(?<![\w/.$}-])((?:" + "|".join(map(re.escape, _TOP_DIRS))
    + r")/[\w./*-]*[\w*/])")
# what a shell line runs
_RUN = re.compile(r"\b(?:python3?|bash|source|sh)\s+(?:-u\s+)?"
                  r"([\w./-]+\.(?:py|sh))\b")
_RUN_MODULE = re.compile(r"\bpython3?\s+(?:-u\s+)?-m\s+"
                         r"(deeplearning4j_tpu(?:\.\w+)+)")
_QUOTED = re.compile(r"`([^`\n]+)`")
_LINKED = re.compile(r"\]\(([^)#\s:]+)[)#]")          # [text](relative/path)
_ENV = re.compile(r"DL4J_TPU_[A-Z0-9_]*[A-Z0-9]")


def _exists(*candidates):
    """Whether one of the root-relative candidates (globs allowed) is there."""
    return any(glob.glob(os.path.join(ROOT, c), recursive=True)
               for c in candidates)


def _missing_paths(text, script, where=""):
    """What ``text`` names and the tree does not hold. Anywhere: a path
    under one of the repo's directories and the module of a ``python -m``.
    In a script: the file an interpreter is given. In a document: a
    link's target (relative to ``where``) and, between back-quotes, a bare
    ``chip_smoke.py`` (at the root or anywhere below), a package-relative
    ``nn/model.py`` or ``analysis/retrace_guard.check``, and a dotted
    ``deeplearning4j_tpu.<package>``."""
    for m in _ROOTED.finditer(text):
        if not _exists(m.group(1)):
            yield m.group(1)
    for m in _RUN_MODULE.finditer(text):
        mod = m.group(1).replace(".", "/")
        if not _exists(mod + ".py", mod + "/__main__.py"):
            yield m.group(1)
    if script:
        for m in _RUN.finditer(text):
            if not _exists(m.group(1)):
                yield m.group(1)
        return
    for m in _LINKED.finditer(text):
        if not _exists(os.path.join(where, m.group(1))):
            yield m.group(1)
    for m in _QUOTED.finditer(text):
        for tok in m.group(1).split():
            tok = tok.strip("(),;").split(":")[0]
            if re.fullmatch(r"[\w*-]+\.(?:py|sh|md)", tok):
                if not _exists(tok, *(f"{d}/**/{tok}" for d in _TOP_DIRS)):
                    yield tok
            elif re.fullmatch(r"[a-z_]+/[\w./*-]+", tok) \
                    and tok.split("/")[0] not in _TOP_DIRS \
                    and (tok.split("/")[0] in RANK or tok.endswith(".py")):
                rel = "deeplearning4j_tpu/" + tok.rstrip("/")
                head, _, last = rel.rpartition("/")
                if not _exists(rel, rel + ".py",
                               f"{head}/{last.split('.')[0]}.py"):
                    yield tok
            elif re.fullmatch(r"deeplearning4j_tpu(?:\.\w+)+", tok):
                first = "deeplearning4j_tpu/" + tok.split(".")[1]
                if not _exists(first, first + ".py"):
                    yield tok


@pytest.fixture(scope="module")
def program_env_names():
    """``DL4J_TPU_*`` names the program reads: the string constants under
    ``deeplearning4j_tpu/`` and in ``chip_smoke.py`` that are exactly a name
    (so a name in a docstring or a message does not count)."""
    names = set()
    for path in [*PKG.rglob("*.py"), PKG.parent / "chip_smoke.py"]:
        for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and _ENV.fullmatch(node.value):
                names.add(node.value)
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document, program_env_names):
    text = (PKG.parent / document).read_text(encoding="utf-8")
    script = document.endswith(".sh")
    missing = sorted(set(_missing_paths(text, script,
                                        os.path.dirname(document))))
    # a script may also name a variable of its own, which it expands
    unread = sorted(
        name for name in set(_ENV.findall(text))
        if name not in program_env_names
        and not (script and re.search(r"\$\{?" + name + r"\b", text)))
    assert not missing and not unread, (
        f"{document} names paths that do not exist: {missing}; "
        f"variables that nothing reads: {unread}")
