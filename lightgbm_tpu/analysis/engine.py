"""graft-lint: AST rule engine for JAX hot-path hazards.

Walks the package source (no imports, stdlib ``ast`` only — safe to run
in environments with no jax backend), hands each module to per-rule
visitors (``rules.py``), and reconciles the findings against a
checked-in suppression baseline (``lint_baseline.json`` at the repo
root).  Findings serialize through the telemetry event model
(``make_event`` / ``JsonlSink``) so ``--format json`` output is the
same JSONL dialect as every other subsystem's sink.

Fingerprints are content-addressed, not line-addressed: sha1 of
``rule|relpath|enclosing-symbol|normalized-snippet`` plus an occurrence
index for duplicates, so pure line drift (edits above a finding) never
invalidates a baseline entry, while editing the flagged line itself
does.

Device-function reachability (shared by R001/R003/R005): a function is
"device code" when it is (a) decorated with / passed to a jax tracing
transform (jit, vmap, pmap, shard_map, checkpoint, pallas_call) or a
``lax`` control-flow combinator (while_loop, fori_loop, scan, cond,
switch, map), (b) lexically nested inside device code, or (c) a local
function CALLED from device code (one call-graph closure over the
module's top-level defs, so e.g. ``find_best_split`` is device because
the growers call it under jit).  Cross-module reachability is
approximated by each rule's path scoping.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..telemetry.sinks import make_event

__all__ = ["Finding", "ModuleContext", "LintEngine", "BASELINE_NAME"]

BASELINE_NAME = "lint_baseline.json"

# jax-ish module roots whose members mark device entry (see module doc)
_JAX_ROOTS = ("jax", "jax.numpy", "jax.lax", "jax.experimental",
              "jax.experimental.pallas", "numpy", "functools")


def _jaxish_module(mod: Optional[str]) -> bool:
    if not mod:
        return False
    return mod == "jax" or mod.startswith("jax.")

# callee terminal name -> positions of function-valued arguments
_DEVICE_WRAPPERS: Dict[str, Tuple[object, ...]] = {
    "jit": (0,), "vmap": (0,), "pmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "checkpoint": (0,), "remat": (0,),
    "custom_jvp": (0,), "custom_vjp": (0,), "shard_map": (0,),
    "pallas_call": (0,), "named_call": (0,),
    "while_loop": (0, 1), "fori_loop": (2,), "scan": (0,),
    "cond": (1, 2, 3, 4), "switch": ("rest",), "map": (0,),
    "associative_scan": (0,),
}
_DEVICE_KWARGS = {"true_fun", "false_fun", "body_fun", "cond_fun", "f",
                  "fun", "kernel", "body"}

# callbacks whose function argument runs on HOST with concrete values
# (numpy/syncs are fine in there, even when lexically inside device code)
_HOST_WRAPPERS = {"callback": (0,), "pure_callback": (0,),
                  "io_callback": (0,)}


@dataclasses.dataclass
class Finding:
    """One rule violation, serializable as a telemetry event."""
    rule: str
    path: str            # repo-relative posix path
    line: int
    col: int
    symbol: str          # enclosing dotted def path, or "<module>"
    message: str
    snippet: str         # stripped source line
    fingerprint: str = ""  # filled by LintEngine.fingerprint()

    def base_hash(self) -> str:
        norm = " ".join(self.snippet.split())
        key = "|".join((self.rule, self.path, self.symbol, norm))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def to_event(self) -> dict:
        return make_event(
            "lint.finding", self.rule, path=self.path, line=self.line,
            col=self.col, symbol=self.symbol, message=self.message,
            snippet=self.snippet, fingerprint=self.fingerprint)

    def text(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message} [{self.fingerprint}]")


# ------------------------------------------------------------ helpers
def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Interval:
    __slots__ = ("start", "end", "node", "qualname")

    def __init__(self, node, qualname):
        self.start = node.lineno
        self.end = getattr(node, "end_lineno", node.lineno)
        self.node = node
        self.qualname = qualname

    def contains(self, line: int) -> bool:
        return self.start <= line <= self.end


class ModuleContext:
    """Parsed module + the derived maps every rule needs."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.module = self._module_name(self.relpath)
        self.is_package = self.relpath.endswith("__init__.py")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        # import alias maps
        self.module_aliases: Dict[str, str] = {}   # local name -> module
        self.from_imports: Dict[str, Tuple[str, str]] = {}  # -> (mod, orig)
        self._collect_imports()
        # function intervals (defs + lambdas) with dotted qualnames
        self.functions: List[_Interval] = []
        self._collect_functions()
        # device code intervals
        self.device: List[_Interval] = []
        self._detect_device()

    # ---------------------------------------------------------- naming
    @staticmethod
    def _module_name(relpath: str) -> str:
        mod = relpath[:-3] if relpath.endswith(".py") else relpath
        mod = mod.replace("/", ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        return mod

    def resolve_relative(self, level: int, name: Optional[str]) -> str:
        """Absolute module for a ``from ...x import y`` statement."""
        parts = self.module.split(".")
        # non-package module: level 1 == its parent package; for a
        # package __init__, level 1 is the package itself
        keep = len(parts) - level + (1 if self.is_package else 0)
        base = parts[: max(keep, 0)]
        if name:
            base = base + name.split(".")
        return ".".join(base)

    def _collect_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.module_aliases[a.asname or
                                        a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
                    if a.asname:
                        self.module_aliases[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom):
                mod = (self.resolve_relative(node.level, node.module)
                       if node.level else (node.module or ""))
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.from_imports[a.asname or a.name] = (mod, a.name)

    def alias_targets(self, roots: Sequence[str]) -> Set[str]:
        """Local names bound to any module in `roots` (by import)."""
        out = set()
        for local, mod in self.module_aliases.items():
            if mod in roots:
                out.add(local)
        for local, (mod, orig) in self.from_imports.items():
            full = f"{mod}.{orig}"
            if full in roots or mod in roots:
                if full in roots:
                    out.add(local)
        return out

    @property
    def np_names(self) -> Set[str]:
        return {k for k, v in self.module_aliases.items()
                if v == "numpy"}

    @property
    def jnp_names(self) -> Set[str]:
        return {k for k, v in self.module_aliases.items()
                if v == "jax.numpy"}

    @property
    def jax_names(self) -> Set[str]:
        return {k for k, v in self.module_aliases.items() if v == "jax"}

    @property
    def lax_names(self) -> Set[str]:
        out = {k for k, v in self.module_aliases.items()
               if v == "jax.lax"}
        for local, (mod, orig) in self.from_imports.items():
            if mod == "jax" and orig == "lax":
                out.add(local)
        return out

    def is_jaxish_callee(self, func: ast.AST) -> Optional[str]:
        """Terminal name when `func` is <jax-ish module>.<name> or a
        name imported from a jax module; else None."""
        dn = dotted_name(func)
        if dn is None:
            return None
        parts = dn.split(".")
        if len(parts) == 1:
            fi = self.from_imports.get(parts[0])
            if fi and _jaxish_module(fi[0]):
                return fi[1]
            return None
        base = parts[0]
        mod = self.module_aliases.get(base)
        if _jaxish_module(mod):
            return parts[-1]
        fi = self.from_imports.get(base)
        if fi and _jaxish_module(fi[0]):
            return parts[-1]
        return None

    # ------------------------------------------------------- functions
    def _collect_functions(self):
        def visit(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    self.functions.append(_Interval(child, q))
                    visit(child, q + ".")
                elif isinstance(child, ast.Lambda):
                    self.functions.append(_Interval(child,
                                                    prefix + "<lambda>"))
                    visit(child, prefix)
                else:
                    visit(child, prefix)

        visit(self.tree, "")

    def symbol_at(self, line: int) -> str:
        """Innermost enclosing def's dotted path for a source line."""
        best = None
        for iv in self.functions:
            if iv.contains(line):
                if best is None or iv.start >= best.start:
                    best = iv
        return best.qualname if best else "<module>"

    # ------------------------------------------------- device analysis
    def _detect_device(self):
        by_name: Dict[str, List[_Interval]] = {}
        for iv in self.functions:
            if isinstance(iv.node, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                by_name.setdefault(iv.node.name, []).append(iv)
        device_nodes: Set[ast.AST] = set()
        host_nodes: Set[ast.AST] = set()

        def mark_arg(arg, into=device_nodes):
            if isinstance(arg, ast.Lambda):
                into.add(arg)
            elif isinstance(arg, ast.Name):
                for iv in by_name.get(arg.id, ()):
                    into.add(iv.node)
            elif isinstance(arg, ast.Call):
                # functools.partial(f, ...) / contract(...)(f)
                for inner in list(arg.args):
                    mark_arg(inner, into)

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) \
                        else dec
                    name = self.is_jaxish_callee(target)
                    if name in _DEVICE_WRAPPERS:
                        device_nodes.add(node)
                    if isinstance(dec, ast.Call):
                        # @partial(jax.jit, ...)
                        dn = dotted_name(dec.func) or ""
                        if dn.endswith("partial") and dec.args and \
                                self.is_jaxish_callee(dec.args[0]) \
                                in _DEVICE_WRAPPERS:
                            device_nodes.add(node)
            elif isinstance(node, ast.Call):
                name = self.is_jaxish_callee(node.func)
                if name in _HOST_WRAPPERS:
                    for pos in _HOST_WRAPPERS[name]:
                        if pos < len(node.args):
                            mark_arg(node.args[pos], host_nodes)
                    for kw in node.keywords:
                        if kw.arg in ("callback", "fun"):
                            mark_arg(kw.value, host_nodes)
                elif name in _DEVICE_WRAPPERS:
                    spec = _DEVICE_WRAPPERS[name]
                    if spec == ("rest",):
                        for a in node.args[1:]:
                            mark_arg(a)
                    else:
                        for pos in spec:
                            if isinstance(pos, int) and \
                                    pos < len(node.args):
                                mark_arg(node.args[pos])
                    for kw in node.keywords:
                        if kw.arg in _DEVICE_KWARGS:
                            mark_arg(kw.value)

        # call-graph closure over local defs: a function called from
        # device code is device-reachable
        changed = True
        while changed:
            changed = False
            device_ivs = [iv for iv in self.functions
                          if iv.node in device_nodes]
            for iv in device_ivs:
                for node in ast.walk(iv.node):
                    if isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Name):
                        for target in by_name.get(node.func.id, ()):
                            if target.node not in device_nodes:
                                device_nodes.add(target.node)
                                changed = True
        device_nodes -= host_nodes
        self.device = [iv for iv in self.functions
                       if iv.node in device_nodes]
        self.host = [iv for iv in self.functions
                     if iv.node in host_nodes]

    def in_device(self, line: int) -> bool:
        return any(iv.contains(line) for iv in self.device)

    def in_host_callback(self, line: int) -> bool:
        """Line sits inside a function passed to jax.debug.callback /
        pure_callback / io_callback — host code with concrete values,
        exempt from device-code rules."""
        return any(iv.contains(line) for iv in self.host)

    def device_roots(self) -> List[_Interval]:
        """Device intervals not nested inside another device interval
        (walk these to visit every device line exactly once)."""
        out = []
        for iv in self.device:
            nested = any(o is not iv and o.start <= iv.start
                         and iv.end <= o.end for o in self.device)
            if not nested:
                out.append(iv)
        return out

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""


# ------------------------------------------------------------- engine
class LintEngine:
    """Walk the package, run the rules, reconcile with the baseline."""

    def __init__(self, root: Optional[str] = None, rules=None,
                 baseline_path: Optional[str] = None):
        if root is None:
            pkg = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            root = os.path.dirname(pkg)
        self.root = os.path.abspath(root)
        if rules is None:
            from .rules import default_rules
            rules = default_rules()
        self.rules = list(rules)
        self.baseline_path = baseline_path or os.path.join(
            self.root, BASELINE_NAME)

    # ------------------------------------------------------- file walk
    def collect_files(self, paths: Optional[Sequence[str]] = None
                      ) -> List[str]:
        if paths:
            out = []
            for p in paths:
                p = os.path.join(self.root, p) \
                    if not os.path.isabs(p) else p
                if os.path.isdir(p):
                    out.extend(self._walk_dir(p))
                else:
                    out.append(p)
            return sorted(out)
        pkg = os.path.join(self.root, "lightgbm_tpu")
        base = pkg if os.path.isdir(pkg) else self.root
        return sorted(self._walk_dir(base))

    @staticmethod
    def _walk_dir(base: str) -> Iterable[str]:
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for f in filenames:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)

    def _contexts(self, files: Sequence[str]) -> List[ModuleContext]:
        ctxs = []
        for path in files:
            rel = os.path.relpath(path, self.root)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            try:
                ctxs.append(ModuleContext(path, rel, src))
            except SyntaxError as e:
                f = Finding("E000", rel.replace(os.sep, "/"),
                            e.lineno or 0, e.offset or 0, "<module>",
                            f"syntax error: {e.msg}", "")
                self._syntax_errors.append(f)
        return ctxs

    # ------------------------------------------------------------- run
    def run(self, paths: Optional[Sequence[str]] = None
            ) -> List[Finding]:
        self._syntax_errors: List[Finding] = []
        ctxs = self._contexts(self.collect_files(paths))
        for rule in self.rules:
            collect = getattr(rule, "collect", None)
            if collect is not None:
                for ctx in ctxs:
                    collect(ctx)
        findings: List[Finding] = list(self._syntax_errors)
        for ctx in ctxs:
            for rule in self.rules:
                for f in rule.check(ctx):
                    findings.append(f)
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
        self._assign_fingerprints(findings)
        return findings

    @staticmethod
    def _assign_fingerprints(findings: List[Finding]) -> None:
        seen: Dict[str, int] = {}
        for f in findings:
            base = f.base_hash()
            occ = seen.get(base, 0)
            seen[base] = occ + 1
            f.fingerprint = f"{base}.{occ}"

    # -------------------------------------------------------- baseline
    def load_baseline(self) -> Dict[str, dict]:
        if not os.path.exists(self.baseline_path):
            return {}
        with open(self.baseline_path, encoding="utf-8") as f:
            data = json.load(f)
        return {e["fingerprint"]: e for e in data.get("findings", [])}

    def compare(self, findings: Sequence[Finding]):
        """-> (new, baselined, stale_fingerprints)."""
        base = self.load_baseline()
        new = [f for f in findings if f.fingerprint not in base]
        kept = [f for f in findings if f.fingerprint in base]
        have = {f.fingerprint for f in findings}
        stale = sorted(fp for fp in base if fp not in have)
        return new, kept, stale

    def write_baseline(self, findings: Sequence[Finding]) -> None:
        old = self.load_baseline()
        entries = []
        for f in findings:
            e = {"fingerprint": f.fingerprint, "rule": f.rule,
                 "path": f.path, "symbol": f.symbol,
                 "snippet": " ".join(f.snippet.split())}
            note = old.get(f.fingerprint, {}).get("note")
            if note:
                e["note"] = note
            entries.append(e)
        payload = {
            "version": 1,
            "tool": "graft-lint",
            "comment": ("Suppressed findings. Entries are content-"
                        "fingerprinted (rule|path|symbol|snippet), so "
                        "they survive line drift but not edits to the "
                        "flagged line. Regenerate with: python -m "
                        "lightgbm_tpu lint --update-baseline"),
            "findings": entries,
        }
        with open(self.baseline_path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=1, sort_keys=False)
            f.write("\n")
