"""Repo-rule lint: the source-level plane of the contract checker.

The jaxpr contracts (:mod:`.contracts`) verify compiled programs; this AST
pass verifies the SOURCE conventions that keep those programs checkable —
factorizations funneled through ``core/linalg_safe.py``, one jitter constant,
XLA_FLAGS mutation only in ``compat.py``, no host pulls in hot modules,
registries populated at import time, trace counters touched only through the
contract API.  Run it as::

    python -m repro.analysis.lint src/            # exit 1 on any violation

CI runs exactly that; ``tests/test_analysis.py`` pins each rule firing on a
known-bad fixture and the real tree lint-clean.

Active rules
------------
raw-cholesky
    No on-device ``*.linalg.cholesky`` call outside ``core/linalg_safe.py``
    — every factorization goes through ``chol_jittered``/``chol_safe`` so
    jitter policy and escalation live in ONE place (numpy/scipy host-oracle
    calls are exempt).
raw-eigh
    Same for ``*.linalg.eigh``/``eig`` (``linalg_safe.eigh_sym`` is the
    on-device home).
local-jitter
    No module grows its own ``_JITTER`` constant (or rebinds
    ``DEFAULT_JITTER``): the one pinned value is
    ``linalg_safe.DEFAULT_JITTER``.
xla-env-mutation
    ``os.environ["XLA_FLAGS"]`` is process-global, order-sensitive state;
    only ``compat.force_host_device_count`` may touch it (a stray mutation
    after backend init silently does nothing).
device-get-hot-path
    No ``device_get`` in ``kernels/`` at all, and in ``core/protocols/``
    only inside the named host-sync boundary functions (the ledger
    properties, the fit-time mesh unshard, the bucket-crossing growth) —
    anywhere else it is a per-call host round-trip in a hot path.
registry-top-level
    ``register_*`` calls (kernels, schemes, fusions, protocols, kernel ops,
    contracts) run at module top level only, so one import populates the
    registry deterministically and duplicate-registration errors surface at
    import time, not mid-serve.
trace-counter-encapsulation
    ``_SERVE_TRACES``/``_UPDATE_TRACES`` are implementation details of
    ``core/protocols`` (plus ``repro/analysis``, which implements the
    trace-neutral snapshot/restore); everything else budgets retraces
    through ``repro.analysis.retrace_budget`` / the ``*_trace_count``
    wrappers.
span-funnel
    Profiler annotations (``TraceAnnotation``, ``StepTraceAnnotation``,
    ``annotate_function``) only in ``repro/spans.py``: every span is named
    ``repro.<name>`` and carries its fit's id, which the benchmark's
    per-layer metrics read.
"""
from __future__ import annotations

import ast
import dataclasses
import sys
from pathlib import Path

__all__ = ["Violation", "RULES", "lint_source", "lint_file", "lint_paths", "main"]

# host numerics roots exempt from the factorization-funnel rules (scipy/numpy
# run on host, carry no jitter policy, and appear in the paper oracles only)
_HOST_ROOTS = {"np", "numpy", "scipy", "sp", "onp"}

# the sanctioned host-sync boundaries inside core/protocols/ — each is a
# documented ONE-host-round-trip point, not a hot loop (see the module
# docstrings at the definitions).  Keyed by module basename; any device_get
# lexically inside one of these functions is allowed, everything else fires.
_PROTOCOL_HOST_SYNC = {
    "base.py": {
        # FittedProtocol's legacy integer views: explicit host sync of the
        # device-resident StreamState ledgers
        "lengths", "wire_bits", "payload_bits", "integrity_bits",
        "rows_demoted",
    },
    "mesh.py": {
        # the PR-8 fix: ONE fit-time pull that erases the committed
        # replicated sharding before it can leak into serve jits
        "_run_wire_protocol_mesh",
        # the same boundary on the streaming side: the update wrapper
        # host-syncs only the leaked bookkeeping leaves (params/y/wire/
        # stream), never the mesh-sharded factor buffers
        "_update_mesh_jit",
    },
    "streaming.py": {
        # bucket-crossing growth: the ONE host synchronization of the
        # streaming path (ensure_capacity docstring)
        "ensure_capacity", "_pad_last", "_pad_rows", "_pad_chol",
    },
}

_REGISTER_CALLS = (
    "register_kernel", "register_scheme", "register_fusion",
    "register_protocol", "register_kernel_op", "register_contract",
    "register_tune_candidates",
)

RULES = {
    "raw-cholesky":
        "on-device cholesky outside core/linalg_safe.py (use chol_jittered/"
        "chol_safe)",
    "raw-eigh":
        "on-device eigh/eig outside core/linalg_safe.py (use eigh_sym)",
    "local-jitter":
        "local _JITTER constant / DEFAULT_JITTER rebinding (the one home is "
        "linalg_safe.DEFAULT_JITTER)",
    "xla-env-mutation":
        "XLA_FLAGS environment mutation outside repro/compat.py",
    "device-get-hot-path":
        "device_get in kernels/ or outside the named host-sync boundaries of "
        "core/protocols/",
    "registry-top-level":
        "register_* call below module top level (registries populate at "
        "import time)",
    "trace-counter-encapsulation":
        "_SERVE_TRACES/_UPDATE_TRACES touched outside core/protocols/ (use "
        "repro.analysis.retrace_budget)",
    "span-funnel":
        "profiler annotation outside repro/spans.py (use repro.spans.span)",
}

_ANNOTATIONS = ("TraceAnnotation", "StepTraceAnnotation", "annotate_function")


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _dotted(node) -> str:
    """``a.b.c`` for a Name/Attribute chain; '' for anything dynamic."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


@dataclasses.dataclass(frozen=True)
class _FileKind:
    """Which rule scopes apply to one file, derived from its repo path."""

    is_linalg_safe: bool
    is_compat: bool
    is_spans: bool
    in_kernels: bool
    in_protocols: bool
    in_analysis: bool
    basename: str

    @classmethod
    def of(cls, path: str) -> "_FileKind":
        p = Path(path).as_posix()
        return cls(
            is_linalg_safe=p.endswith("core/linalg_safe.py"),
            is_compat=p.endswith("repro/compat.py"),
            is_spans=p.endswith("repro/spans.py"),
            in_kernels="repro/kernels/" in p or p.startswith("kernels/"),
            in_protocols="core/protocols/" in p,
            in_analysis="repro/analysis/" in p,
            basename=Path(path).name,
        )


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, kind: _FileKind):
        self.path = path
        self.kind = kind
        self.out: list[Violation] = []
        self._func_stack: list[str] = []

    def _flag(self, node, rule: str, message: str) -> None:
        self.out.append(Violation(
            self.path, node.lineno, node.col_offset, rule, message
        ))

    # -- scope tracking ----------------------------------------------------

    def visit_FunctionDef(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._func_stack.append("<lambda>")
        self.generic_visit(node)
        self._func_stack.pop()

    # -- rules -------------------------------------------------------------

    def visit_Call(self, node):
        dotted = _dotted(node.func)
        root = dotted.split(".", 1)[0]
        tail = dotted.rsplit(".", 1)[-1]

        if not self.kind.is_linalg_safe and root not in _HOST_ROOTS:
            if dotted.endswith(".linalg.cholesky"):
                self._flag(node, "raw-cholesky",
                           f"{dotted}: factorizations go through "
                           "linalg_safe.chol_jittered/chol_safe")
            elif dotted.endswith((".linalg.eigh", ".linalg.eig")):
                self._flag(node, "raw-eigh",
                           f"{dotted}: eigendecompositions go through "
                           "linalg_safe.eigh_sym")

        if tail == "device_get":
            if self.kind.in_kernels:
                self._flag(node, "device-get-hot-path",
                           "device_get in a kernels/ module (host round-trip "
                           "in the dispatch path)")
            elif self.kind.in_protocols:
                allowed = _PROTOCOL_HOST_SYNC.get(self.kind.basename, set())
                if not any(f in allowed for f in self._func_stack):
                    self._flag(node, "device-get-hot-path",
                               "device_get outside the named host-sync "
                               "boundaries of core/protocols/")

        if tail in _REGISTER_CALLS and self._func_stack:
            self._flag(node, "registry-top-level",
                       f"{tail}() inside {self._func_stack[-1]!r}: registry "
                       "registration happens at module top level")

        if not self.kind.is_compat and dotted in (
            "os.environ.setdefault", "os.environ.update", "os.environ.pop",
            "os.putenv",
        ):
            for arg in node.args:
                if isinstance(arg, ast.Constant) and "XLA_FLAGS" in str(arg.value):
                    self._flag(node, "xla-env-mutation",
                               "XLA_FLAGS mutated outside repro/compat.py")
        self.generic_visit(node)

    def visit_Assign(self, node):
        for target in node.targets:
            self._check_store(target, node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._check_store(node.target, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_store(node.target, node)
        self.generic_visit(node)

    def _check_store(self, target, node):
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._check_store(elt, node)
            return
        if isinstance(target, ast.Name) and not self.kind.is_linalg_safe:
            if target.id == "_JITTER" or target.id == "DEFAULT_JITTER":
                self._flag(node, "local-jitter",
                           f"{target.id} bound outside linalg_safe (import "
                           "linalg_safe.DEFAULT_JITTER instead)")
        if isinstance(target, ast.Subscript) and not self.kind.is_compat:
            base = _dotted(target.value)
            key = target.slice
            if base.endswith("environ") and isinstance(key, ast.Constant) \
                    and "XLA_FLAGS" in str(key.value):
                self._flag(node, "xla-env-mutation",
                           "XLA_FLAGS mutated outside repro/compat.py "
                           "(use compat.force_host_device_count)")

    def visit_ImportFrom(self, node):
        if not self.kind.is_linalg_safe:
            for alias in node.names:
                if alias.name == "_JITTER":
                    self._flag(node, "local-jitter",
                               "importing _JITTER (import "
                               "linalg_safe.DEFAULT_JITTER instead)")
        module = node.module or ""
        if not self.kind.is_spans:
            for alias in node.names:
                if alias.name in _ANNOTATIONS:
                    self._flag(node, "span-funnel",
                               f"importing {alias.name} (use repro.spans.span)")
        if module.startswith("jax") and module.endswith("linalg") \
                and not self.kind.is_linalg_safe:
            for alias in node.names:
                if alias.name == "cholesky":
                    self._flag(node, "raw-cholesky",
                               "importing cholesky from jax linalg (use "
                               "linalg_safe)")
                elif alias.name in ("eigh", "eig"):
                    self._flag(node, "raw-eigh",
                               "importing eigh from jax linalg (use "
                               "linalg_safe.eigh_sym)")
        self.generic_visit(node)

    def visit_Name(self, node):
        if node.id in ("_SERVE_TRACES", "_UPDATE_TRACES") \
                and not (self.kind.in_protocols or self.kind.in_analysis):
            self._flag(node, "trace-counter-encapsulation",
                       f"{node.id} accessed outside core/protocols/ (use "
                       "repro.analysis.retrace_budget / *_trace_count)")
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if node.attr in _ANNOTATIONS and not self.kind.is_spans:
            self._flag(node, "span-funnel",
                       f"{_dotted(node) or node.attr}: spans go through "
                       "repro.spans.span")
        if node.attr in ("_SERVE_TRACES", "_UPDATE_TRACES") \
                and not (self.kind.in_protocols or self.kind.in_analysis):
            self._flag(node, "trace-counter-encapsulation",
                       f"{node.attr} accessed outside core/protocols/ (use "
                       "repro.analysis.retrace_budget / *_trace_count)")
        self.generic_visit(node)


def lint_source(source: str, path: str = "<string>") -> list[Violation]:
    """Lint one source text as if it lived at ``path`` (the path decides
    which scoped rules apply — tests feed synthetic paths)."""
    tree = ast.parse(source, filename=path)
    linter = _Linter(path, _FileKind.of(path))
    linter.visit(tree)
    return sorted(linter.out, key=lambda v: (v.line, v.col, v.rule))


def lint_file(path) -> list[Violation]:
    return lint_source(Path(path).read_text(), str(path))


def lint_paths(paths) -> list[Violation]:
    """Lint files and/or directory trees (directories recurse over *.py)."""
    out: list[Violation] = []
    for p in paths:
        p = Path(p)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            out.extend(lint_file(f))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="repo-rule lint (serve/wire source contracts)",
    )
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the active rule table and exit")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rule, desc in sorted(RULES.items()):
            print(f"{rule:28s} {desc}")
        return 0
    violations = lint_paths(args.paths or ["src"])
    for v in violations:
        print(v)
    n = len(violations)
    print(f"{n} violation(s), {len(RULES)} active rule(s)"
          if n else f"clean ({len(RULES)} active rule(s))")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
