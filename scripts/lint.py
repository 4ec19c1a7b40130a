#!/usr/bin/env python
"""AST-based convention lint (``ci.sh lint``).

Upgrades the old ``compileall`` gate: every file is ``ast.parse``d (so
syntax errors still fail) and then checked against the repo's actual
conventions — the ones that have bitten before and that no generic
linter knows about:

1. **env-read** — no ``os.environ`` / ``os.getenv`` READS outside
   ``common/config.py``: runtime knobs flow through the typed Config +
   ``basics.live_config()`` ladder (the PR 7 consolidation), so a
   knob read from env at point-of-use silently ignores a live config.
   Writes (launcher child-env assembly) are allowed. Files that read
   PROTOCOL env (HOROVOD_RANK worker identity, XLA_FLAGS passthrough)
   are grandfathered in ``ENV_READ_ALLOWED`` — adding a new file to
   that list is a reviewed decision, not an accident.
2. **bare-except** — ``except:`` catches ``SystemExit``/
   ``KeyboardInterrupt`` and has eaten shutdown paths before; name the
   exception (``except Exception:`` at minimum).
3. **unused-import** — module-level imports nobody references
   (``__init__.py`` re-export surfaces are exempt; names appearing in
   string annotations / docstring examples count as uses, so typing
   imports under ``from __future__ import annotations`` don't
   false-positive).
4. **debug-callback** — ``jax.debug.callback`` escapes the compiled
   program to host Python; unvetted uses have produced per-step host
   syncs. Only the approved guard/telemetry sites may call it
   (``DEBUG_CALLBACK_ALLOWED``).

Exit 0 clean, 1 on findings, 2 on usage errors. ``--list-rules`` for
the catalog.
"""

import argparse
import ast
import os
import re
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Directories/globs linted. tests and root scripts are in scope for
# bare-except + unused-import; the env-read and debug-callback rules
# apply to the package only (tests legitimately monkeypatch env and
# exercise callbacks).
PACKAGE_DIRS = ("horovod_tpu",)
EXTRA_DIRS = ("tests", "scripts", "examples")
ROOT_GLOBS = ("_hermetic", "__graft_entry__", "chip_smoke")

# --- rule 1 allowlist: files whose os.environ READS are the contract,
# not a config bypass (worker-protocol identity, child-env assembly,
# logging bootstrap that cannot import config yet, signal-path code
# that must not allocate). Relative to repo root.
ENV_READ_ALLOWED = {
    "horovod_tpu/common/config.py",  # THE env surface
    # worker bootstrap protocol (HOROVOD_RANK/HOSTNAME/EPOCH identity
    # stamped by the launcher — these are addresses, not knobs)
    "horovod_tpu/_executor_worker.py",
    "horovod_tpu/elastic/worker.py",
    "horovod_tpu/elastic/driver.py",
    "horovod_tpu/runner/tpu_discovery.py",
    "horovod_tpu/runner/launch.py",
    # JAX_COMPILATION_CACHE_DIR is JAX's own variable: read to decide
    # whether to leave the cache path alone, never as a knob of ours
    "horovod_tpu/common/compile_cache.py",
    # HOROVOD_STANDBY_HOSTNAME / _FINGERPRINT / CHECKPOINT_DIR are
    # identity stamped by the driver's warmer launch, same contract
    "horovod_tpu/elastic/standby.py",
    "horovod_tpu/runner/rendezvous.py",
    "horovod_tpu/executor.py",
    # bootstrap surfaces that run before/While config exists
    "horovod_tpu/common/logging.py",
    "horovod_tpu/common/metrics.py",
    "horovod_tpu/common/telemetry.py",
    "horovod_tpu/common/autotune.py",
    # HOROVOD_EXE_CACHE resolves live like HOROVOD_TUNER_CACHE above:
    # drills/benches flip the cache root mid-process, after any init
    # snapshot (typed knob exists in config.py for the standby warmer)
    "horovod_tpu/common/exe_cache.py",
    "horovod_tpu/testing/chaos.py",
    "horovod_tpu/testing/fake_ray.py",
    "horovod_tpu/_native/loader.py",
    "horovod_tpu/_native/build.py",
    # kernel-level flags read at trace time (documented in env_vars.md;
    # they gate lowering choices, not runtime behavior)
    "horovod_tpu/ops/flash_attention.py",
    "horovod_tpu/sharded_optimizer.py",
}

# --- rule 4 allowlist: the approved jax.debug.callback sites — the
# PR 4 telemetry tick and the PR 7 guard skip-branch callback.
DEBUG_CALLBACK_ALLOWED = {
    "horovod_tpu/optimizer.py",
    "horovod_tpu/sharded_optimizer.py",
}


def _iter_files() -> List[str]:
    out = []
    for d in PACKAGE_DIRS + EXTRA_DIRS:
        for root, dirs, files in os.walk(os.path.join(REPO, d)):
            dirs[:] = [
                x for x in dirs if x != "__pycache__" and not x.startswith(".")
            ]
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(root, f))
    for f in sorted(os.listdir(REPO)):
        if f.endswith(".py") and any(f.startswith(g) for g in ROOT_GLOBS):
            out.append(os.path.join(REPO, f))
    return out


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO).replace(os.sep, "/")


def _is_environ_read(node: ast.AST) -> bool:
    """``os.environ[...]`` / ``os.environ.get(...)`` / ``os.getenv(..)``
    in Load context. ``os.environ`` passed wholesale (child-env
    assembly like ``dict(os.environ)``) or assigned/updated is a
    write-shaped use and allowed everywhere."""
    # os.getenv(...)
    if isinstance(node, ast.Call):
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "getenv"
            and isinstance(f.value, ast.Name)
            and f.value.id == "os"
        ):
            return True
        # os.environ.get(...)
        if (
            isinstance(f, ast.Attribute)
            and f.attr in ("get", "__getitem__")
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "environ"
            and isinstance(f.value.value, ast.Name)
            and f.value.value.id == "os"
        ):
            return True
    # os.environ[...] read (Load ctx only; Store/Del are writes)
    if (
        isinstance(node, ast.Subscript)
        and isinstance(node.ctx, ast.Load)
        and isinstance(node.value, ast.Attribute)
        and node.value.attr == "environ"
        and isinstance(node.value.value, ast.Name)
        and node.value.value.id == "os"
    ):
        return True
    return False


def _is_debug_callback(node: ast.AST) -> bool:
    """A call whose func ends in ``.debug.callback`` (jax.debug....)."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "callback"
        and isinstance(f.value, ast.Attribute)
        and f.value.attr == "debug"
    )


_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _unused_imports(tree: ast.Module, src: str) -> List[Tuple[int, str]]:
    """Module-scope imports never referenced. A name counts as used if
    it appears as any identifier anywhere else in the AST — including
    inside string constants (quoted annotations, doctest snippets), the
    permissive direction for a lint that must never cry wolf."""
    lines = src.splitlines()

    def _noqa(lineno: int) -> bool:
        # honor `# noqa` on the import line (the existing re-export
        # convention, e.g. fusion.py's hierarchical_stage_groups)
        return 0 < lineno <= len(lines) and "noqa" in lines[lineno - 1]

    imported = {}  # name -> (lineno, display)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = a.asname or a.name.split(".")[0]
                imported[name] = (node.lineno, a.asname or a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                name = a.asname or a.name
                imported[name] = (node.lineno, a.asname or a.name)
    if not imported:
        return []
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass  # the root Name node is walked separately
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            used.update(_WORD.findall(node.value))
    # __all__ re-exports count
    out = []
    for name, (lineno, display) in sorted(imported.items()):
        if name in used or _noqa(lineno):
            continue
        out.append((lineno, display))
    return out


def lint_file(path: str) -> List[str]:
    rel = _rel(path)
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno}: syntax-error: {e.msg}"]

    findings: List[str] = []
    in_package = rel.startswith("horovod_tpu/")

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            findings.append(
                f"{rel}:{node.lineno}: bare-except: name the exception "
                "(except Exception: at minimum — bare except eats "
                "SystemExit/KeyboardInterrupt)"
            )
        if in_package and rel not in ENV_READ_ALLOWED and _is_environ_read(
            node
        ):
            findings.append(
                f"{rel}:{node.lineno}: env-read: os.environ read outside "
                "common/config.py — add a typed Config knob and read it "
                "via basics.live_config() (or, for protocol env, add "
                "this file to ENV_READ_ALLOWED in scripts/lint.py with "
                "a justification)"
            )
        if (
            in_package
            and rel not in DEBUG_CALLBACK_ALLOWED
            and _is_debug_callback(node)
        ):
            findings.append(
                f"{rel}:{node.lineno}: debug-callback: jax.debug.callback "
                "outside the approved guard/telemetry sites escapes the "
                "compiled program to host Python (per-step host-sync "
                "hazard) — route through common/guard.py or "
                "common/telemetry.py, or extend DEBUG_CALLBACK_ALLOWED"
            )

    if os.path.basename(path) != "__init__.py":
        for lineno, display in _unused_imports(tree, src):
            findings.append(
                f"{rel}:{lineno}: unused-import: {display!r} is never "
                "referenced"
            )
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*", help="lint only these files")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        print("env-read bare-except unused-import debug-callback")
        return 0

    files = (
        [os.path.abspath(p) for p in args.paths]
        if args.paths
        else _iter_files()
    )
    findings: List[str] = []
    for path in files:
        findings.extend(lint_file(path))
    for f in findings:
        print(f)
    if findings:
        print(
            f"lint: {len(findings)} finding(s) in {len(files)} file(s)",
            file=sys.stderr,
        )
        return 1
    print(f"lint: {len(files)} files clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
