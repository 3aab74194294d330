"""jaxlint framework: findings, suppressions, baseline, module context.

Zero dependencies beyond the stdlib ``ast`` module: analysis never
imports the code under scan, so a module with broken imports (or a
broken jax install under it) still lints — per-file syntax errors are
reported, not fatal. (The ``python -m sagecal_tpu.analysis`` entry
point does import the parent package — and through it jax — so run the
checkers via ``sagecal_tpu.analysis.core`` directly if you need to
lint from an environment where that import itself is broken.)

The per-module :class:`ModuleCtx` does the shared heavy lifting every
checker needs: parent links, a registry of jit-wrapped callables with
their ``donate_argnums``/``static_argnames``, and the traced-body set
(functions whose bodies execute under a jax trace — jit-decorated defs,
lambdas handed to ``lax`` control flow, and the module-local closure of
functions they call).
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field

RULES = {
    "use-after-donate": (
        "donated buffer read after the donating call / caller-owned "
        "buffer donated without a copy-guard"),
    "retrace": (
        "jax.jit constructed per call or per iteration, non-hashable "
        "static args, or Python control flow on tracer values"),
    "host-sync": (
        "host synchronization (.item()/np.asarray/device_get/print/"
        "float-of-device-value) inside traced code or un-gated in a "
        "hot-path host loop"),
    "dtype-promotion": (
        "dtype-less array creation or wide-dtype literal inside a "
        "traced solver kernel"),
    "storage-accum": (
        "reduction/contraction over a reduced-storage (bf16/f16) "
        "array without a named f32 accumulator "
        "(preferred_element_type= or an explicit upcast)"),
    "cond-cost": (
        "lax.cond branch inlines heavy ops instead of calling a "
        "module-level priceable function"),
    "suppression": (
        "malformed jaxlint suppression (missing reason or unknown "
        "rule), or a stale one whose rule no longer fires there"),
    # -- threadlint (ISSUE 19): the concurrency contracts ---------------
    "shared-state": (
        "module-global or instance mutable state written from more "
        "than one thread role without a named Lock/Queue/thread-local "
        "guarding it"),
    "lock-order": (
        "inconsistent lock acquisition order (a cycle in the static "
        "with-nesting graph) or nested reacquisition of a "
        "non-reentrant lock — a deadlock window"),
    "handoff-ownership": (
        "object handed to an inter-thread queue/ring/writer and then "
        "read or mutated by the producer (the host-object "
        "generalization of use-after-donate)"),
    "scope-discipline": (
        "thread-scoped telemetry context (dtrace.scope / "
        "obs.scope_labels / fleet.device_scope / fleet.job_scope) "
        "entered outside a with statement or spanning a thread spawn "
        "— scope stacks are strictly thread-local"),
}

# modules whose host loops are hot-path territory for host-sync, and
# whose traced kernels the dtype lint covers (ISSUE 4 scope; sched.py
# joined in ISSUE 5 — the overlap layer's thread loops must never grow
# a per-iteration sync; serve/ joined in ISSUE 8 — the device-owner
# scheduler loop and the per-job thread code sit upstream of EVERY
# job's solve, so a sync or a use-after-donate there taxes all tenants;
# obs/ joined in ISSUE 9 — the metrics layer runs inside every hot
# loop it instruments, so an un-gated device read there would tax
# exactly the paths it exists to observe; faults.py joined in ISSUE 10
# — the injection/retry layer wraps every I/O seam's hot loop, and its
# ``faults.active()`` gate is blessed alongside ``dtrace.active()`` /
# ``obs.active()`` by _is_active_gate's ``.active`` suffix match;
# ops/ joined in ISSUE 11 — the Pallas kernel bodies (coh_pallas)
# ARE the hottest per-row code in the tree, and a
# reduced-dtype kernel accumulator is exactly the storage-accum bug
# class: pl.pallas_call joined _TRACE_WRAPPERS so kernel bodies count
# as traced)
_HOT_SEGMENTS = ("solvers", "consensus", "rime", "serve", "obs", "ops")
_HOT_BASENAMES = ("pipeline.py", "sched.py", "faults.py")


def is_hot_path(relpath: str) -> bool:
    parts = relpath.replace(os.sep, "/").split("/")
    return (any(seg in parts for seg in _HOT_SEGMENTS)
            or parts[-1] in _HOT_BASENAMES)


@dataclass
class Finding:
    rule: str
    path: str               # relative to the scan root
    line: int
    col: int
    message: str
    code: str = ""          # stripped source line (fingerprint input)
    fingerprint: str = ""   # filled by the runner (occurrence-indexed)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule}: "
                f"{self.message}")


# ---------------------------------------------------------------------------
# suppressions: ``# jaxlint: disable=<rule>[,<rule>] -- <reason>``
# ---------------------------------------------------------------------------

_SUPP_RE = re.compile(
    r"#\s*jaxlint:\s*disable=([A-Za-z0-9_,-]+)\s*(?:--\s*(\S.*))?")


def parse_suppressions(lines):
    """{applies-to-line (1-based): (rules, reason, comment-line)} plus
    malformed-suppression findings data [(line, message)].

    A trailing comment suppresses its own line; a standalone comment
    line suppresses the next non-comment, non-blank line. The reason
    after ``--`` is REQUIRED — an unexplained suppression is itself a
    finding, so every accepted violation carries its why in-tree.
    """
    supp: dict = {}
    bad: list = []
    for i, raw in enumerate(lines, start=1):
        m = _SUPP_RE.search(raw)
        if not m:
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        reason = (m.group(2) or "").strip()
        unknown = [r for r in rules if r not in RULES]
        if unknown:
            bad.append((i, f"unknown rule(s) in suppression: "
                           f"{', '.join(unknown)}"))
        if not reason:
            bad.append((i, "suppression without a reason (use "
                           "'# jaxlint: disable=<rule> -- <why>')"))
            continue
        target = i
        if raw.lstrip().startswith("#"):
            # standalone comment: attach to the next code line
            j = i
            while j < len(lines) and (
                    not lines[j].strip()
                    or lines[j].lstrip().startswith("#")):
                j += 1
            target = j + 1 if j < len(lines) else i
        supp.setdefault(target, []).append((frozenset(rules), reason, i))
    return supp, bad


# ---------------------------------------------------------------------------
# thread roles: ``# thread-role: <role>[, <role>]`` (ISSUE 19)
# ---------------------------------------------------------------------------

_ROLE_RE = re.compile(r"#\s*thread-role:\s*([A-Za-z0-9_][A-Za-z0-9_, -]*)")


def parse_thread_roles(lines):
    """{applies-to-line (1-based): (role, ...)} — the threadlint role
    annotation grammar. Attachment follows the suppression rule: a
    trailing comment annotates its own line, a standalone comment
    annotates the next code line. Placed on (or above) a ``def``, it
    declares which thread role(s) execute that function's body,
    overriding spawn-site inference — the escape hatch for roles the
    static spawn graph cannot see (e.g. a method called from another
    class's worker thread)."""
    out: dict = {}
    for i, raw in enumerate(lines, start=1):
        m = _ROLE_RE.search(raw)
        if not m:
            continue
        roles = tuple(r.strip() for r in m.group(1).split(",")
                      if r.strip())
        if not roles:
            continue
        target = i
        if raw.lstrip().startswith("#"):
            j = i
            while j < len(lines) and (
                    not lines[j].strip()
                    or lines[j].lstrip().startswith("#")):
                j += 1
            target = j + 1 if j < len(lines) else i
        out[target] = roles
    return out


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def dotted(node) -> str | None:
    """'jax.lax.cond' for nested Attribute/Name chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return None if base is None else f"{base}.{node.attr}"
    return None


def _const_ints(node):
    """Tuple of ints from a literal tuple/list/int, ``tuple(range(a,b))``
    or a conditional whose truthy side is one of those (the
    ``make_admm_runner(donate=)`` escape hatch lowers to
    ``tuple(range(6, 15)) if donate else ()`` — donation assumed on)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    if (isinstance(node, ast.Call) and dotted(node.func) == "tuple"
            and len(node.args) == 1
            and isinstance(node.args[0], ast.Call)
            and dotted(node.args[0].func) == "range"):
        rargs = [a.value for a in node.args[0].args
                 if isinstance(a, ast.Constant)]
        if len(rargs) == len(node.args[0].args) and rargs:
            return tuple(range(*rargs))
    if isinstance(node, ast.IfExp):
        return _const_ints(node.body) or _const_ints(node.orelse)
    return None


def _const_strs(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, str)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


_JIT_NAMES = {"jax.jit", "jit"}
# callables whose function-valued arguments run under a jax trace
_TRACE_WRAPPERS = {
    "jax.jit", "jit", "jax.vmap", "vmap", "jax.pmap",
    "jax.grad", "jax.value_and_grad", "jax.jacfwd", "jax.jacrev",
    "jax.checkpoint", "jax.remat", "shard_map",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.scan", "lax.scan",
    "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch",
    "jax.lax.map", "lax.map",
    # a Pallas kernel body runs under the Pallas trace — its reductions
    # and dtype choices are hot-path territory like any jitted kernel
    # (the per-cell block arrives as a Ref, but the body's jnp ops are
    # ordinary traced code)
    "pl.pallas_call", "pallas_call",
}


@dataclass
class JitEntry:
    """One jit-wrapped callable visible in a module."""
    name: str                       # bare name, or attribute name
    donate: tuple = ()              # donated positional indices
    donate_names: tuple = ()        # donate_argnames not yet resolved
    static_names: tuple = ()
    static_nums: tuple = ()
    is_attr: bool = False           # matched via ``<expr>.name(...)``
    fn_def: object = None           # decorated FunctionDef, when known


def _jit_kwargs(call: ast.Call):
    """(donate_nums, donate_names, static_names, static_nums) from a
    jax.jit(...) call or a partial(jax.jit, ...) decorator."""
    donate, dnames, snames, snums = (), (), (), ()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            donate = _const_ints(kw.value) or ()
        elif kw.arg == "donate_argnames":
            dnames = _const_strs(kw.value) or ()
        elif kw.arg == "static_argnames":
            snames = _const_strs(kw.value) or ()
        elif kw.arg == "static_argnums":
            snums = _const_ints(kw.value) or ()
    return donate, dnames, snames, snums


def _names_to_positions(fn, names):
    """Positional indices of ``names`` in ``fn``'s signature — how
    donate_argnames reaches positionally passed call args."""
    params = [p.arg for p in fn.args.posonlyargs + fn.args.args]
    return tuple(params.index(n) for n in names if n in params)


def _jit_call(node):
    """The jax.jit(...) Call inside ``node`` (possibly wrapped:
    ``jax.jit(shard_map(...), donate_argnums=...)``), else None."""
    if isinstance(node, ast.Call) and dotted(node.func) in _JIT_NAMES:
        return node
    return None


class ModuleCtx:
    """Parsed module + the shared indexes every checker queries."""

    def __init__(self, path: str, relpath: str, src: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.src = src
        self.lines = src.splitlines()
        self.tree = ast.parse(src, filename=path)
        self.hot = is_hot_path(self.relpath)
        self.parents: dict = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        # module-scope names: top-level defs, classes and imports —
        # call targets resolving here are "priceable boundaries" for
        # the cond-cost rule and known statics for retrace
        self.module_defs: dict = {}
        self.module_names: set = set()
        for n in self.tree.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.module_defs[n.name] = n
                self.module_names.add(n.name)
            elif isinstance(n, ast.ClassDef):
                self.module_names.add(n.name)
            elif isinstance(n, ast.Import):
                self.module_names.update(
                    a.asname or a.name.split(".")[0] for a in n.names)
            elif isinstance(n, ast.ImportFrom):
                self.module_names.update(
                    a.asname or a.name for a in n.names)
            elif isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        self.module_names.add(t.id)
        self.jits = self._index_jits()
        self.traced = self._traced_bodies()
        self.thread_roles = parse_thread_roles(self.lines)
        self.lock_names, self.rlock_names = self._index_locks()

    # -- jit registry ------------------------------------------------------

    def _index_jits(self) -> dict:
        jits: dict = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    entry = self._entry_from_decorator(node, dec)
                    if entry:
                        jits[entry.name] = entry
            elif isinstance(node, ast.Assign):
                call = _jit_call(node.value)
                if call is None:
                    continue
                donate, dnames, snames, snums = _jit_kwargs(call)
                # jax.jit(<module def>, donate_argnames=...): resolve
                # the names to positions through the def's signature so
                # positionally passed call args are tracked too
                inner = (self.module_defs.get(dotted(call.args[0]))
                         if call.args else None)
                if dnames and inner is not None:
                    donate = tuple(sorted(
                        set(donate) | set(_names_to_positions(inner,
                                                              dnames))))
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jits[t.id] = JitEntry(t.id, donate, dnames,
                                              snames, snums)
                    elif isinstance(t, ast.Attribute):
                        jits[t.attr] = JitEntry(t.attr, donate, dnames,
                                                snames, snums,
                                                is_attr=True)
        return jits

    def _entry_from_decorator(self, fn, dec):
        if dotted(dec) in _JIT_NAMES:
            return JitEntry(fn.name, fn_def=fn)
        call = None
        if (isinstance(dec, ast.Call)
                and dotted(dec.func) in ("functools.partial", "partial")
                and dec.args and dotted(dec.args[0]) in _JIT_NAMES):
            call = dec
        elif isinstance(dec, ast.Call) and dotted(dec.func) in _JIT_NAMES:
            call = dec
        if call is None:
            return None
        donate, dnames, snames, snums = _jit_kwargs(call)
        if dnames:
            donate = tuple(sorted(
                set(donate) | set(_names_to_positions(fn, dnames))))
        return JitEntry(fn.name, donate, dnames, snames, snums,
                        fn_def=fn)

    # -- traced-body closure ----------------------------------------------

    def _traced_bodies(self) -> set:
        """FunctionDef/Lambda nodes whose bodies run under a trace."""
        traced: set = set()
        # local def tables per enclosing function, for Name resolution
        local_defs: dict = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                table = {}
                for sub in ast.walk(node):
                    if (isinstance(sub, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))
                            and sub is not node):
                        table.setdefault(sub.name, sub)
                local_defs[node] = table

        def resolve(name, scope):
            while scope is not None:
                if (isinstance(scope, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                        and name in local_defs.get(scope, ())):
                    return local_defs[scope][name]
                scope = self.parents.get(scope)
            return self.module_defs.get(name)

        for entry in self.jits.values():
            if entry.fn_def is not None:
                traced.add(entry.fn_def)
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and dotted(node.func) in _TRACE_WRAPPERS):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
                elif isinstance(arg, ast.Name):
                    target = resolve(arg.id, self.parents.get(node))
                    if target is not None:
                        traced.add(target)
        # closure: defs nested in traced bodies + module-local callees
        changed = True
        while changed:
            changed = False
            for fn in list(traced):
                for sub in ast.walk(fn):
                    cand = None
                    if (isinstance(sub, (ast.FunctionDef, ast.Lambda))
                            and sub is not fn and sub not in traced):
                        cand = sub
                    elif (isinstance(sub, ast.Call)
                          and isinstance(sub.func, ast.Name)):
                        cand = resolve(sub.func.id, self.parents.get(sub))
                        if cand in traced:
                            cand = None
                    if cand is not None and cand not in traced:
                        traced.add(cand)
                        changed = True
        return traced

    # -- lock registry (threadlint) ----------------------------------------

    _LOCK_CTORS = ("threading.Lock", "threading.RLock",
                   "threading.Condition", "Lock", "RLock", "Condition",
                   "threadsan.make_lock", "threadsan.make_rlock",
                   "make_lock", "make_rlock")
    _RLOCK_CTORS = ("threading.RLock", "RLock", "threadsan.make_rlock",
                    "make_rlock")

    def _index_locks(self):
        """Names (attribute or binding) assigned a lock constructor
        anywhere in the module: ``self._lock = threading.Lock()`` marks
        ``_lock``. The shared-state guard test and the lock-order
        acquisition graph both key on this set (plus the name
        heuristic — any name containing 'lock')."""
        locks: set = set()
        rlocks: set = set()
        for node in ast.walk(self.tree):
            val = None
            targets = ()
            if isinstance(node, ast.Assign):
                val, targets = node.value, node.targets
            elif isinstance(node, ast.AnnAssign) and node.value:
                val, targets = node.value, (node.target,)
            if not isinstance(val, ast.Call):
                continue
            fn = dotted(val.func)
            if fn not in self._LOCK_CTORS:
                continue
            for t in targets:
                name = t.id if isinstance(t, ast.Name) else (
                    t.attr if isinstance(t, ast.Attribute) else None)
                if name is None:
                    continue
                locks.add(name)
                if fn in self._RLOCK_CTORS:
                    rlocks.add(name)
        return locks, rlocks

    def is_lockish(self, name: str) -> bool:
        """Heuristic lock identity for a bare attribute/binding name:
        assigned a lock constructor in this module, or named like one
        (``_lock``, ``clock``, ``mutex``)."""
        low = name.lower()
        return (name in self.lock_names or "lock" in low
                or "mutex" in low)

    # -- per-checker conveniences ------------------------------------------

    def enclosing_functions(self, node):
        """Innermost-first chain of enclosing FunctionDef/Lambda nodes."""
        out = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                out.append(cur)
            cur = self.parents.get(cur)
        return out

    def in_traced_body(self, node) -> bool:
        return any(fn in self.traced
                   for fn in self.enclosing_functions(node))

    def enclosing_loop(self, node, stop_at=None):
        """Nearest enclosing For/While below ``stop_at`` (a function)."""
        cur = self.parents.get(node)
        while cur is not None and cur is not stop_at:
            if isinstance(cur, (ast.For, ast.While)):
                return cur
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return None
            cur = self.parents.get(cur)
        return None

    @staticmethod
    def _is_active_gate(test) -> bool:
        """A blessed telemetry-gate test: ``<mod>.active()`` — the
        diag tracer's ``dtrace.active()``, the obs registry's
        ``obs.active()``, and the fault harness's ``faults.active()``
        (obs/metrics.py and faults.py keep the identical
        no-op-when-disabled contract) — or a BoolOp combining only
        such calls (``dtrace.active() or obs.active()``: the body
        still executes only when telemetry is on, so its syncs never
        run on the disabled path)."""
        if isinstance(test, ast.Call):
            return (dotted(test.func) or "").endswith(".active")
        if isinstance(test, ast.BoolOp):
            return all(ModuleCtx._is_active_gate(v) for v in test.values)
        return False

    def under_trace_gate(self, node) -> bool:
        """True inside an ``if dtrace.active():`` / ``if obs.active():``
        block (or a BoolOp of such gates) — the blessed telemetry
        gates (diag/trace.py, obs/metrics.py): statements there only
        execute when telemetry is on. ``with dtrace.phase(...)`` does
        NOT gate: its body runs unconditionally (null context when
        tracing is off), so syncs inside a phase body are still
        leaks."""
        cur = node
        while cur is not None:
            parent = self.parents.get(cur)
            if isinstance(parent, ast.If):
                if self._is_active_gate(parent.test) \
                        and cur in parent.body:
                    return True
            cur = parent
        return False

    def finding(self, rule, node, message) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        code = (self.lines[line - 1].strip()
                if 0 < line <= len(self.lines) else "")
        return Finding(rule, self.relpath, line, col, message, code)


# ---------------------------------------------------------------------------
# runner + baseline
# ---------------------------------------------------------------------------

def _checkers():
    # late import: checkers import core for helpers
    from sagecal_tpu.analysis import (condcost, donate, dtype_rules,
                                      hostsync, retrace, threadlint)
    return (donate.check, retrace.check, hostsync.check,
            dtype_rules.check, condcost.check, threadlint.check)


def _fingerprint(findings):
    """Stable ids: hash of (rule, path, code line) + occurrence index —
    line-number independent, so unrelated edits don't churn the
    baseline."""
    seen: dict = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.col)):
        key = (f.rule, f.path, f.code)
        k = seen.get(key, 0)
        seen[key] = k + 1
        raw = f"{f.rule}|{f.path}|{f.code}|{k}"
        f.fingerprint = hashlib.sha1(raw.encode()).hexdigest()[:16]
    return findings


def collect_files(paths):
    """.py files under ``paths`` (files pass through), sorted; the
    analysis package itself is exempt (its checker sources quote the
    very patterns they hunt)."""
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git")]
            if os.path.basename(root) == "analysis" and \
                    os.path.exists(os.path.join(root, "core.py")):
                continue
            out.extend(os.path.join(root, f) for f in files
                       if f.endswith(".py"))
    return sorted(set(out))


def run_paths(paths, root=None):
    """Analyze ``paths`` -> (findings, suppressed, errors).

    ``findings`` carry fingerprints; ``suppressed`` is the list of
    (finding, reason) pairs silenced inline; ``errors`` are unparsable
    files (reported, never fatal — a syntax error is pytest's job)."""
    files = collect_files(paths)
    if root is None:
        root = (os.path.commonpath([os.path.abspath(p) for p in paths])
                if paths else os.getcwd())
        if os.path.isfile(root):
            root = os.path.dirname(root)
    findings: list = []
    suppressed: list = []
    errors: list = []
    for path in files:
        rel = os.path.relpath(os.path.abspath(path), root)
        try:
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            ctx = ModuleCtx(path, rel, src)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            errors.append((rel, f"{type(e).__name__}: {e}"))
            continue
        supp, bad = parse_suppressions(ctx.lines)
        raw: list = []
        for check in _checkers():
            raw.extend(check(ctx))
        for line, msg in bad:
            raw.append(Finding("suppression", ctx.relpath, line, 0, msg,
                               ctx.lines[line - 1].strip()))
        matched: set = set()
        for f in raw:
            hit = None
            for rules, reason, cl in supp.get(f.line, ()):
                if f.rule in rules:
                    hit = reason
                    matched.add(cl)
                    break
            if hit is not None and f.rule != "suppression":
                suppressed.append((f, hit))
            else:
                findings.append(f)
        # stale-suppression audit (ISSUE 19): a well-formed directive
        # whose rule no longer fires on its target line is DEAD — the
        # violation it excused was fixed (or moved), and a lingering
        # disable would silently swallow the next regression there.
        # Directives with unknown rules already produced a finding
        # above; only known-rule, reasoned directives are audited.
        for target, entries in supp.items():
            for rules, _reason, cl in entries:
                if cl in matched or not rules <= set(RULES):
                    continue
                findings.append(Finding(
                    "suppression", ctx.relpath, cl, 0,
                    f"stale suppression: no {'/'.join(sorted(rules))} "
                    f"finding fires on its target line ({target}) — "
                    "remove the dead disable",
                    ctx.lines[cl - 1].strip()))
    return _fingerprint(findings), suppressed, errors


BASELINE_NAME = "jaxlint_baseline.json"


def load_baseline(path):
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    return {e["fingerprint"]: e for e in data.get("entries", [])}


def write_baseline(path, findings, reasons=None):
    """Pin ``findings`` as accepted. ``reasons`` maps fingerprints to
    the written why — a baseline entry without a reason is a TODO, not
    an endorsement."""
    reasons = reasons or {}
    entries = [{
        "fingerprint": f.fingerprint,
        "rule": f.rule,
        "path": f.path,
        "line": f.line,
        "code": f.code,
        "reason": reasons.get(f.fingerprint, ""),
    } for f in sorted(findings, key=lambda f: (f.path, f.line))]
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "entries": entries}, fh, indent=1)
        fh.write("\n")


def diff_baseline(findings, baseline):
    """(new_findings, stale_entries): what --ci fails on, and which
    pinned entries no longer exist (the sync test keeps those at
    zero)."""
    new = [f for f in findings if f.fingerprint not in baseline]
    live = {f.fingerprint for f in findings}
    stale = [e for fp, e in baseline.items() if fp not in live]
    return new, stale
