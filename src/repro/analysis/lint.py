"""JAX trace-hazard linter: AST checks for the bug classes generic
linters don't know about.

Rules (ids are what the waiver syntax names):

* ``traced-cond`` — a Python ``if``/``while`` whose test involves a
  parameter of a jitted or scanned function.  Python control flow on a
  traced value raises ``TracerBoolConversionError`` at trace time — or
  worse, silently bakes one branch into the compiled program when the
  value happens to be concrete on the first trace.  A function counts as
  jitted/scanned when it is decorated with ``jax.jit``/``jax.custom_vjp``
  or is referenced inside a ``jax.jit(...)`` / ``jax.lax.scan(...)`` /
  ``shard_map(...)`` call anywhere in the same module.  ``is None`` /
  ``isinstance`` / ``hasattr``-style static tests are exempt.
* ``static-arg`` — a non-hashable literal (list/dict/set) or an
  array-valued expression (``np.``/``jnp.``/``jax.numpy`` call) passed
  via ``static_argnums``/``static_argnames`` or as a keyword that a
  ``functools.partial(jax.jit, ...)`` marks static.  Unhashable statics
  fail at call time; array statics retrace on every call.
* ``host-jnp`` — ``jnp.*`` work inside a serving tick-loop hot path
  (``ServingEngine.step``/``_admit*``/``_step_chunked``/``run``): each
  host-side jnp op dispatches a device program per tick outside the
  fused jits.  ``jnp.asarray`` (the H2D upload of freshly built host
  buffers) is allowed.
* ``mutable-default`` — a mutable literal (list/dict/set) default
  argument: shared across calls, a classic aliasing bug.
* ``broad-except`` — a bare ``except:`` or ``except Exception``/
  ``except BaseException`` that does not re-``raise``: swallows
  tracebacks from genuinely broken code (the dryrun sweep bugs).

Waivers: append ``# repro: allow(<rule>[, <rule>...]) <reason>`` to the
flagged line (or the ``def``/``except`` line introducing it).  A file-
level ``# repro: allow-file(<rule>)`` anywhere in the file waives the
rule for the whole file.  Waivers are the escape hatch for *reviewed*
hazards — the reason is part of the syntax on purpose.

Baseline: ``repro/analysis/lint_baseline.txt`` lists tolerated findings
as ``path::rule::line-hash`` entries.  The committed baseline is EMPTY —
the repo lints clean — and stays the mechanism by which a future rule
can land before its violations are burned down (``--update-baseline``).
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Iterable, List, Optional, Set, Tuple

__all__ = [
    "LintFinding",
    "RULES",
    "lint_source",
    "lint_paths",
    "load_baseline",
    "format_baseline",
]

RULES = (
    "traced-cond",
    "static-arg",
    "host-jnp",
    "mutable-default",
    "broad-except",
)

# Serving tick-loop hot paths: per-tick host work here multiplies with
# every decode step served.  Qualified as ClassName.method.
HOT_PATHS = {
    "ServingEngine.step",
    "ServingEngine.run",
    "ServingEngine._admit",
    "ServingEngine._admit_prefill",
    "ServingEngine._admit_replay",
    "ServingEngine._step_chunked",
    "ServingEngine._insert_wave",
    "ServingEngine._decode_args",
    "ServingEngine._preempt",
    "ServingEngine._ensure_growth",
    "ServingEngine.dispatch_decode",
    "ServingEngine._postprocess",
    # async front end: every method on the per-tick scheduling path
    "ServeFrontend.tick",
    "ServeFrontend.drain",
    "ServeFrontend.serve",
    "ServeFrontend._dispatch",
    "ServeFrontend._land_inflight",
    "ServeFrontend._land",
    "ServeFrontend._chain_safe",
    "ServeFrontend._ensure_chain",
    "ServeFrontend._flush_streams",
}
# Allowed in hot paths: the H2D upload of freshly built host buffers,
# plus dtype *names* (jnp.int32 etc. is a type object, not a device op).
HOT_JNP_ALLOWED = {
    "asarray",
    "int8", "int16", "int32", "int64",
    "uint8", "uint16", "uint32", "uint64",
    "float16", "float32", "float64", "bfloat16", "bool_", "dtype",
}

_WAIVE_LINE = re.compile(r"#\s*repro:\s*allow\(([^)]*)\)")
_WAIVE_FILE = re.compile(r"#\s*repro:\s*allow-file\(([^)]*)\)")


@dataclasses.dataclass
class LintFinding:
    path: str
    line: int
    rule: str
    message: str
    source_line: str = ""

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def baseline_key(self) -> str:
        digest = hashlib.sha1(
            self.source_line.strip().encode()
        ).hexdigest()[:12]
        return f"{self.path}::{self.rule}::{digest}"


def _dotted(node: ast.AST) -> str:
    """'jax.lax.scan' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


_JIT_CALLS = ("jax.jit", "jit", "pjit", "jax.pmap", "pmap")
_SCAN_CALLS = (
    "jax.lax.scan", "lax.scan", "scan",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.cond", "lax.cond",
    "shard_map", "jax.vmap", "vmap",
)
_STATIC_TEST_CALLS = {"isinstance", "hasattr", "callable", "getattr"}


def _traced_function_names(tree: ast.Module) -> Set[str]:
    """Function names referenced as jit/scan/vmap targets in this module."""
    targets: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = _dotted(node.func)
            if fn in _JIT_CALLS + _SCAN_CALLS:
                for arg in list(node.args) + [kw.value for kw in
                                              node.keywords]:
                    if isinstance(arg, ast.Name):
                        targets.add(arg.id)
                    # functools.partial(body_fn, ...) as the scanned fn
                    elif isinstance(arg, ast.Call):
                        inner = _dotted(arg.func)
                        if inner in ("functools.partial", "partial"):
                            if arg.args and isinstance(arg.args[0], ast.Name):
                                targets.add(arg.args[0].id)
    return targets


def _is_jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        name = _dotted(dec if not isinstance(dec, ast.Call) else dec.func)
        if name in _JIT_CALLS + ("jax.custom_vjp", "custom_vjp",
                                 "jax.custom_jvp", "custom_jvp"):
            return True
        # functools.partial(jax.jit, static_argnums=...) as a decorator
        if isinstance(dec, ast.Call) and name in ("functools.partial",
                                                  "partial"):
            if dec.args and _dotted(dec.args[0]) in _JIT_CALLS + (
                "jax.custom_vjp", "custom_vjp"
            ):
                return True
    return False


def _static_test(test: ast.AST) -> bool:
    """Tests that are legal host logic even on traced-adjacent names."""
    if isinstance(test, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
    if isinstance(test, ast.Call):
        if _dotted(test.func).split(".")[-1] in _STATIC_TEST_CALLS:
            return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _static_test(test.operand)
    if isinstance(test, ast.BoolOp):
        return all(_static_test(v) for v in test.values)
    return False


def _names_in(node: ast.AST) -> Set[str]:
    return {
        n.id for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.findings: List[LintFinding] = []
        self.traced_fns: Set[str] = set()
        self.class_stack: List[str] = []
        self.fn_stack: List[Tuple[str, Set[str], bool]] = []  # name, params, traced

    # ---------------------------------------------------------------- utils
    def add(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        src = self.lines[line - 1] if line <= len(self.lines) else ""
        self.findings.append(
            LintFinding(self.path, line, rule, message, source_line=src)
        )

    # ------------------------------------------------------------ functions
    def _visit_fn(self, node) -> None:
        params = {
            a.arg
            for a in (
                node.args.posonlyargs + node.args.args + node.args.kwonlyargs
            )
        } - {"self", "cls"}
        traced = _is_jit_decorated(node) or node.name in self.traced_fns
        qual = ".".join(self.class_stack + [node.name]) if self.class_stack \
            else node.name

        # mutable-default
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for d in defaults:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and _dotted(d.func) in ("list", "dict", "set")
            ):
                self.add(d, "mutable-default",
                         f"mutable default argument in {qual}() is shared "
                         "across calls")

        self.fn_stack.append((qual, params, traced))
        self.generic_visit(node)
        self.fn_stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_fn(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def visit_Lambda(self, node):
        params = {a.arg for a in node.args.args}
        self.fn_stack.append(("<lambda>", params, False))
        self.generic_visit(node)
        self.fn_stack.pop()

    # -------------------------------------------------------- rule: traced
    def _check_cond(self, node) -> None:
        if not self.fn_stack:
            return
        _, params, traced = self.fn_stack[-1]
        if not traced or _static_test(node.test):
            return
        hit = _names_in(node.test) & params
        if hit:
            kind = "while" if isinstance(node, ast.While) else "if"
            self.add(node, "traced-cond",
                     f"Python `{kind}` on parameter(s) {sorted(hit)} of a "
                     "jitted/scanned function — traced values cannot drive "
                     "host control flow (use lax.cond/select or hoist the "
                     "value to a static argument)")

    def visit_If(self, node):
        self._check_cond(node)
        self.generic_visit(node)

    def visit_While(self, node):
        self._check_cond(node)
        self.generic_visit(node)

    # ---------------------------------------------------- rule: static-arg
    def visit_Call(self, node):
        fn = _dotted(node.func)
        if fn in _JIT_CALLS or (
            fn in ("functools.partial", "partial")
            and node.args and _dotted(node.args[0]) in _JIT_CALLS
        ):
            for kw in node.keywords:
                if kw.arg in ("static_argnums", "static_argnames"):
                    continue           # the spec itself may be a tuple/list
                if kw.arg is None:
                    continue
                if self._unhashable_or_array(kw.value):
                    self.add(kw.value, "static-arg",
                             f"{fn}(..., {kw.arg}=<{self._describe(kw.value)}>"
                             ") — non-hashable or array-valued static "
                             "argument retraces or fails at call time")
        # calls THROUGH a partial-jitted function with literal statics is
        # covered by the mutable literal check at jit time above.
        self.generic_visit(node)

    @staticmethod
    def _unhashable_or_array(v: ast.AST) -> bool:
        if isinstance(v, (ast.List, ast.Dict, ast.Set)):
            return True
        if isinstance(v, ast.Call):
            name = _dotted(v.func)
            if name.startswith(("np.", "jnp.", "numpy.", "jax.numpy.")):
                return True
        return False

    @staticmethod
    def _describe(v: ast.AST) -> str:
        if isinstance(v, ast.List):
            return "list"
        if isinstance(v, ast.Dict):
            return "dict"
        if isinstance(v, ast.Set):
            return "set"
        return "array"

    # ------------------------------------------------------ rule: host-jnp
    def visit_Attribute(self, node):
        if self.fn_stack and self.fn_stack[-1][0] in HOT_PATHS:
            root = node
            while isinstance(root, ast.Attribute):
                attr, root = root.attr, root.value
            if isinstance(root, ast.Name) and root.id == "jnp" \
                    and attr not in HOT_JNP_ALLOWED:
                self.add(node, "host-jnp",
                         f"host-side jnp.{attr} in serving hot path "
                         f"{self.fn_stack[-1][0]} dispatches a device op "
                         "per tick outside the fused jits")
        self.generic_visit(node)

    # -------------------------------------------------- rule: broad-except
    def visit_ExceptHandler(self, node):
        broad = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id in ("Exception", "BaseException")
        )
        if broad:
            reraises = any(
                isinstance(n, ast.Raise) and n.exc is None
                for n in ast.walk(node)
            )
            if not reraises:
                what = "bare except" if node.type is None else \
                    f"except {node.type.id}"
                self.add(node, "broad-except",
                         f"{what} swallows unrelated failures — catch the "
                         "specific exceptions and log what was suppressed")
        self.generic_visit(node)


def _waived_rules_for_line(lines: List[str], lineno: int) -> Set[str]:
    """Waivers on the flagged line or its decorated/def parent line."""
    if not (1 <= lineno <= len(lines)):
        return set()
    m = _WAIVE_LINE.search(lines[lineno - 1])
    if not m:
        return set()
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


def lint_source(source: str, path: str = "<string>") -> List[LintFinding]:
    """Lint one module's source; waivers already applied."""
    tree = ast.parse(source)
    linter = _Linter(path, source)
    linter.traced_fns = _traced_function_names(tree)
    linter.visit(tree)

    lines = source.splitlines()
    file_waived: Set[str] = set()
    for line in lines:
        m = _WAIVE_FILE.search(line)
        if m:
            file_waived |= {r.strip() for r in m.group(1).split(",")}

    kept = []
    for f in linter.findings:
        if f.rule in file_waived:
            continue
        if f.rule in _waived_rules_for_line(lines, f.line):
            continue
        kept.append(f)
    return kept


def iter_py_files(roots: Iterable[str]) -> List[str]:
    out = []
    for root in roots:
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out += [
                os.path.join(dirpath, f)
                for f in filenames if f.endswith(".py")
            ]
    return sorted(out)


def lint_paths(
    roots: Iterable[str],
    baseline: Optional[Set[str]] = None,
) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for path in iter_py_files(roots):
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            file_findings = lint_source(source, path)
        except SyntaxError as e:
            findings.append(LintFinding(path, e.lineno or 1, "broad-except",
                                        f"unparseable file: {e.msg}"))
            continue
        findings += file_findings
    if baseline:
        findings = [
            f for f in findings if f.baseline_key() not in baseline
        ]
    return findings


# ------------------------------------------------------------- baseline IO

def baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "lint_baseline.txt")


def load_baseline(path: Optional[str] = None) -> Set[str]:
    path = path or baseline_path()
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        return {
            line.strip() for line in f
            if line.strip() and not line.startswith("#")
        }


def format_baseline(findings: Iterable[LintFinding]) -> str:
    header = (
        "# repro.analysis lint baseline — tolerated findings, one\n"
        "# `path::rule::line-hash` per line.  Kept EMPTY on main: new\n"
        "# rules land by burning their violations down, not baselining\n"
        "# them.  Regenerate with `python -m repro.analysis --lint "
        "--update-baseline`.\n"
    )
    keys = sorted({f.baseline_key() for f in findings})
    return header + "".join(k + "\n" for k in keys)
