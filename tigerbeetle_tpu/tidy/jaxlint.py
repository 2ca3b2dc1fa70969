"""Device hot-path lints: hidden host syncs, retrace hazards, and
nondeterministic reductions over the jitted commit kernels.

The e2e bar (ROADMAP: ≥1M accepted tx/s, p50 ≤10ms) hinges on the
device side staying clean in three ways nothing used to check:

  - `host-sync` / `traced-branch` / `unfenced-sync` — a `float()/int()/
    bool()/.item()/np.asarray()` or an `if` on a traced value inside a
    jit-reachable function either fails at trace time or silently
    blocks on a device→host transfer; on the host side, materializing
    a device handle outside the sanctioned dispatch/finish seam
    (manifest.JAXLINT_SYNC_SEAM) serializes the overlapped pipeline.
  - `retrace-shape` / `retrace-static-arg` / `retrace-kwargs` — a jit
    entry called with batch-dependent shapes (unpadded slices,
    runtime-sized np constructors), a batch-dependent value in a
    static argument position, or `**` dict expansion recompiles per
    batch: one retrace costs more than the batch it serves.
  - `float-dtype` / `unordered-reduce` / `axis-order` — float
    accumulation is not associative, so float scatters/segment-sums
    and collectives over unordered axis sets break byte-identical
    determinism across replicas.

The analysis is a lexical taint pass in the tidy tradition (see
tidy/ownership.py's Limits): within each manifest.JAXLINT_MODULES
module it finds jit roots (`@jax.jit`, `jax.jit(f)`, `partial(jax.jit,
...)`, functions passed to `shard_map`), closes over the intra-set
call graph (device-hot set, nested defs included), and tracks a
two-point taint per local: DEVICE (traced value) vs STATIC (trace-time
constant: shapes, dtypes, closure config, `static=`-annotated
parameters, `X is None` tests). Escapes are explicit: `# tidy:
static=param|return` declares trace-time-constant parameters/results,
`# tidy: allow=<code> reason` waives a rule with its justification.

The runtime leg is the CompileRegistry at the bottom: a jit
cache-miss counter (per tracked entry point via `_cache_size()`, plus
a global XLA compile counter via jax.monitoring) recorded by
profile_e2e.py / bench.py and gated EXACTLY by tools/bench_gate.py —
a retrace regression fails CI the same way a >10% perf drop does.

Run via tools/check.py (passes: host-sync, retrace, reduction);
docs/STATIC_ANALYSIS.md has the rule catalog.
"""

from __future__ import annotations

import ast
import pathlib
from typing import Dict, List, Optional, Set, Tuple

from tigerbeetle_tpu.tidy import annotations as ann_mod
from tigerbeetle_tpu.tidy import manifest
from tigerbeetle_tpu.tidy.findings import Finding

# Taint lattice: STATIC < DEVICE.
STATIC = 0
DEVICE = 1

# Module heads whose call results are traced values regardless of args.
DEVICE_HEADS = ("jnp", "jax", "u128", "lax")

# Callables whose result is a trace-time constant even on device args.
UNTAINT_CALLS = frozenset(("len", "isinstance", "range", "type", "getattr",
                           "hasattr", "zip", "enumerate"))
# Attribute reads that are static under jit (shape metadata).
UNTAINT_ATTRS = frozenset(("shape", "dtype", "ndim", "size", "_fields"))

# Host materializers: applied to a DEVICE value they force a sync (or a
# trace-time error inside jit).
MATERIALIZERS = frozenset(("float", "int", "bool"))
# numpy-module functions that materialize device arrays.
NP_MATERIALIZERS = frozenset(("asarray", "array", "ascontiguousarray"))
# numpy constructors whose runtime-sized results at a jit-entry call
# site mean per-batch shapes (the retrace-shape rule).
NP_SIZED = frozenset(("asarray", "array", "zeros", "empty", "arange", "full",
                      "ones", "ascontiguousarray"))

FLOAT_DTYPES = frozenset(("float32", "float64", "float16", "bfloat16"))
REDUCE_TAILS = frozenset(("segment_sum", "segment_max", "segment_min",
                          "bincount"))
COLLECTIVES = frozenset(("psum", "pmean", "pmax", "pmin", "all_gather",
                         "all_to_all", "axis_index"))


def _allowed(anns, lines, code: str, pass_name: str) -> bool:
    for line in lines:
        a = ann_mod.lookup(anns, line)
        if a is not None and (a.allows(code) or a.allows(pass_name)):
            return True
    return False


def _dotted(node) -> Optional[str]:
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    parts.append(cur.id)
    return ".".join(reversed(parts))


def _call_tail(func) -> Optional[str]:
    """Last attribute / bare name of a call target (`self._ops.f` → f)."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _static_params(fn: ast.FunctionDef, anns) -> Tuple[Set[str], bool]:
    """(declared static parameter names, whether the return is static)
    from a `# tidy: static=a|b|return` def-line annotation."""
    a = ann_mod.lookup(anns, fn.lineno)
    if a is None or "static" not in a:
        return set(), False
    vals = a.roles("static")
    return {v for v in vals if v != "return"}, "return" in vals


def _literal_strs(node) -> Set[str]:
    out: Set[str] = set()
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.add(node.value)
    return out


class _ModuleInfo:
    """One module's functions (nested included, by qualname), jit roots
    with their static argnames, and import aliases."""

    def __init__(self, rel: str, tree: ast.Module, anns) -> None:
        self.rel = rel
        self.tree = tree
        self.anns = anns
        self.funcs: Dict[str, ast.FunctionDef] = {}   # qualname -> def
        self.parent: Dict[str, Optional[str]] = {}    # qualname -> enclosing fn
        self.by_name: Dict[str, List[str]] = {}       # bare name -> qualnames
        self.jit_static: Dict[str, Set[str]] = {}     # root qualname -> static names
        self.np_aliases: Set[str] = set()             # local names for numpy
        self.np_funcs: Dict[str, str] = {}            # from-import alias -> numpy fn
        self.module_imports: Dict[str, str] = {}      # alias -> dotted module
        self._collect()

    def _collect(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name.split(".")[0]
                    if a.name.split(".")[0] == "numpy":
                        self.np_aliases.add(alias)
                    self.module_imports[alias] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    alias = a.asname or a.name
                    self.module_imports[alias] = f"{node.module}.{a.name}"
                    if node.module.split(".")[0] == "numpy":
                        # `from numpy import asarray` — bare-name calls
                        # must still hit the numpy materializer/sizing
                        # rules.
                        self.np_funcs[alias] = a.name

        def walk_fns(body, prefix: str, parent: Optional[str]):
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{prefix}{node.name}"
                    self.funcs[q] = node
                    self.parent[q] = parent
                    self.by_name.setdefault(node.name, []).append(q)
                    walk_fns(node.body, f"{q}.", q)
                elif isinstance(node, ast.ClassDef):
                    walk_fns(node.body, f"{prefix}{node.name}.", parent)

        walk_fns(self.tree.body, "", None)
        self._find_roots()

    def np_func(self, call: ast.Call) -> Optional[str]:
        """The numpy function name a call resolves to (`np.asarray`,
        `from numpy import asarray`), else None."""
        func = call.func
        if isinstance(func, ast.Name):
            return self.np_funcs.get(func.id)
        if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
            if func.value.id in self.np_aliases:
                return func.attr
        return None

    # --- jit root discovery ------------------------------------------------

    def _jit_call_info(self, call: ast.Call):
        """(wrapped function name, static argnames) if `call` is
        jax.jit(f, ...) / partial(jax.jit, ...) applied later, else None."""
        d = _dotted(call.func)
        if d not in ("jax.jit", "jit"):
            return None
        fn_name = None
        if call.args and isinstance(call.args[0], ast.Name):
            fn_name = call.args[0].id
        static: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                static |= _literal_strs(kw.value)
        return fn_name, static

    def _mark_root(self, bare: str, static: Set[str]) -> None:
        for q in self.by_name.get(bare, ()):
            self.jit_static.setdefault(q, set()).update(static)

    def _find_roots(self) -> None:
        for q, fn in self.funcs.items():
            for dec in fn.decorator_list:
                d = _dotted(dec) if not isinstance(dec, ast.Call) else None
                if d in ("jax.jit", "jit"):
                    self.jit_static.setdefault(q, set())
                elif isinstance(dec, ast.Call):
                    dd = _dotted(dec.func)
                    if dd in ("jax.jit", "jit"):
                        info = self._jit_call_info(dec)
                        static = info[1] if info else set()
                        self.jit_static.setdefault(q, set()).update(static)
                    elif dd in ("functools.partial", "partial") and dec.args:
                        inner = _dotted(dec.args[0])
                        if inner in ("jax.jit", "jit"):
                            static = set()
                            for kw in dec.keywords:
                                if kw.arg == "static_argnames":
                                    static |= _literal_strs(kw.value)
                            self.jit_static.setdefault(q, set()).update(static)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            info = self._jit_call_info(node)
            if info and info[0]:
                self._mark_root(info[0], info[1])
            tail = _call_tail(node.func)
            if tail in ("shard_map", "_shard_map") and node.args:
                if isinstance(node.args[0], ast.Name):
                    self._mark_root(node.args[0].id, set())


def _device_hot(infos: Dict[str, _ModuleInfo]) -> Set[Tuple[str, str]]:
    """Closure of (rel, qualname) reachable from jit roots through bare
    and alias-resolved calls within the analyzed module set, plus every
    function nested inside a hot one (it executes during tracing)."""
    # module path -> rel for import resolution among analyzed files.
    path_by_mod: Dict[str, str] = {}
    for rel in infos:
        mod = rel[:-3].replace("/", ".")
        path_by_mod[mod] = rel
    hot: Set[Tuple[str, str]] = set()
    work: List[Tuple[str, str]] = []
    for rel, info in infos.items():
        for q in info.jit_static:
            hot.add((rel, q))
            work.append((rel, q))
    while work:
        rel, q = work.pop()
        info = infos[rel]
        fn = info.funcs.get(q)
        if fn is None:
            continue
        # Nested defs trace inline.
        for cq, parent in info.parent.items():
            if parent == q and (rel, cq) not in hot:
                hot.add((rel, cq))
                work.append((rel, cq))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee: Optional[Tuple[str, str]] = None
            if isinstance(node.func, ast.Name):
                qs = info.by_name.get(node.func.id)
                if qs:
                    callee = (rel, qs[0])
            elif isinstance(node.func, ast.Attribute) and isinstance(
                node.func.value, ast.Name
            ):
                alias = node.func.value.id
                target_mod = info.module_imports.get(alias)
                target_rel = path_by_mod.get(target_mod or "")
                if target_rel is not None:
                    tq = infos[target_rel].by_name.get(node.func.attr)
                    if tq:
                        callee = (target_rel, tq[0])
            if callee is not None and callee not in hot:
                hot.add(callee)
                work.append(callee)
    return hot


class _Taint:
    """Two-point taint over one function body (2-pass fixed point)."""

    def __init__(self, info: _ModuleInfo, fn: ast.FunctionDef, qual: str,
                 static_params: Set[str], static_return_fns: Set[str]) -> None:
        self.info = info
        self.fn = fn
        self.qual = qual
        self.env: Dict[str, int] = {}
        self.varargs: Set[str] = set()
        self.static_return_fns = static_return_fns
        args = fn.args
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        ):
            if a.arg in ("self", "cls") or a.arg in static_params:
                self.env[a.arg] = STATIC
            else:
                self.env[a.arg] = DEVICE
        for va in (args.vararg, args.kwarg):
            if va is not None:
                self.env[va.arg] = DEVICE
                self.varargs.add(va.arg)

    # --- expression taint --------------------------------------------------

    def taint(self, node) -> int:
        if node is None or isinstance(node, ast.Constant):
            return STATIC
        if isinstance(node, ast.Name):
            return self.env.get(node.id, STATIC)
        if isinstance(node, ast.Attribute):
            if node.attr in UNTAINT_ATTRS:
                return STATIC
            return self.taint(node.value)
        if isinstance(node, ast.Subscript):
            return max(self.taint(node.value), self.taint(node.slice))
        if isinstance(node, ast.Slice):
            return max(self.taint(node.lower), self.taint(node.upper),
                       self.taint(node.step))
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None`: pytree STRUCTURE, static at
            # trace time even for device-typed optionals.
            if (
                len(node.ops) == 1
                and isinstance(node.ops[0], (ast.Is, ast.IsNot))
                and isinstance(node.comparators[0], ast.Constant)
                and node.comparators[0].value is None
            ):
                return STATIC
            return max(self.taint(node.left),
                       *(self.taint(c) for c in node.comparators))
        if isinstance(node, ast.BoolOp):
            return max(self.taint(v) for v in node.values)
        if isinstance(node, ast.BinOp):
            return max(self.taint(node.left), self.taint(node.right))
        if isinstance(node, ast.UnaryOp):
            return self.taint(node.operand)
        if isinstance(node, ast.IfExp):
            return max(self.taint(node.body), self.taint(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return max((self.taint(e) for e in node.elts), default=STATIC)
        if isinstance(node, ast.Dict):
            return max((self.taint(v) for v in node.values if v is not None),
                       default=STATIC)
        if isinstance(node, ast.Starred):
            return self.taint(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return max(
                max((self.taint(g.iter) for g in node.generators),
                    default=STATIC),
                self.taint(node.elt),
            )
        if isinstance(node, ast.Call):
            return self.call_taint(node)
        if isinstance(node, ast.JoinedStr):
            return STATIC
        return DEVICE  # unmodeled: stay conservative

    def call_taint(self, node: ast.Call) -> int:
        tail = _call_tail(node.func)
        d = _dotted(node.func)
        if tail in UNTAINT_CALLS:
            return STATIC
        if d is not None and d.split(".")[0] in ("jnp", "jax"):
            # Shape arithmetic and named-axis sizes are trace-time ints.
            if tail in ("broadcast_shapes", "axis_size"):
                return STATIC
            return DEVICE
        # Locally-resolved callee with a `static=return` declaration.
        if isinstance(node.func, ast.Name):
            for q in self.info.by_name.get(node.func.id, ()):
                if q in self.static_return_fns:
                    return STATIC
        if d is not None and d.split(".")[0] in DEVICE_HEADS:
            return DEVICE
        arg_taints = [self.taint(a) for a in node.args]
        arg_taints += [self.taint(kw.value) for kw in node.keywords]
        if isinstance(node.func, ast.Attribute):
            # Method call: x.sum() carries the receiver's taint.
            arg_taints.append(self.taint(node.func.value))
        return max(arg_taints, default=STATIC)

    def test_taint(self, node) -> int:
        """Branch-test taint: vararg truthiness is pytree structure."""
        if isinstance(node, ast.Name) and node.id in self.varargs:
            return STATIC
        return self.taint(node)

    # --- statement walk (assignments update env) ---------------------------

    def _bind(self, target, t: int) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = max(self.env.get(target.id, STATIC), t)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, t)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, t)

    def propagate(self) -> None:
        for _ in range(2):  # loop-carried names need a second pass
            for node in ast.walk(self.fn):
                if _owner(self.info, node, self.fn) is not self.fn:
                    continue
                if isinstance(node, ast.Assign):
                    t = self.taint(node.value)
                    for tgt in node.targets:
                        self._bind(tgt, t)
                elif isinstance(node, ast.AugAssign):
                    self._bind(node.target,
                               max(self.taint(node.target), self.taint(node.value)))
                elif isinstance(node, ast.AnnAssign) and node.value is not None:
                    self._bind(node.target, self.taint(node.value))
                elif isinstance(node, ast.For):
                    self._bind(node.target, self.taint(node.iter))
                elif isinstance(node, ast.withitem) and node.optional_vars:
                    self._bind(node.optional_vars, self.taint(node.context_expr))
                elif isinstance(node, ast.NamedExpr):
                    self._bind(node.target, self.taint(node.value))


def _owner(info: _ModuleInfo, node, fn: ast.FunctionDef):
    """The innermost function whose body (not a nested def) holds `node`.
    Cheap variant: nodes inside any nested def of `fn` are skipped by
    comparing line spans of the nested defs."""
    if not hasattr(node, "lineno"):
        return fn
    for q, child in info.funcs.items():
        if child is fn:
            continue
        if info.parent.get(q) and info.funcs.get(info.parent[q]) is fn:
            end = getattr(child, "end_lineno", child.lineno)
            if child.lineno <= node.lineno <= end:
                return child
    return fn


class _ModuleLint:
    """All three jaxlint passes over one module (shared hot-set/taint)."""

    def __init__(self, info: _ModuleInfo, hot: Set[Tuple[str, str]],
                 seam: frozenset, pad_helpers: frozenset,
                 jit_entries: Dict[str, tuple]) -> None:
        self.info = info
        self.hot = hot
        self.seam = seam
        self.pad_helpers = pad_helpers
        self.jit_entries = jit_entries
        self.findings: Dict[str, List[Finding]] = {
            "host-sync": [], "retrace": [], "reduction": [],
        }
        self.static_return_fns = {
            q for q, fn in info.funcs.items()
            if _static_params(fn, info.anns)[1]
        }

    def _flag(self, pass_name: str, code: str, line: int, scope: str,
              subject: str, message: str, def_line: int) -> None:
        if _allowed(self.info.anns, (line, def_line), code, pass_name):
            return
        self.findings[pass_name].append(Finding(
            pass_name, code, self.info.rel, line, scope, subject, message,
        ))

    def run(self) -> None:
        for qual, fn in self.info.funcs.items():
            scope = qual
            is_hot = (self.info.rel, qual) in self.hot
            static_params, _ = _static_params(fn, self.info.anns)
            static_params |= self.info.jit_static.get(qual, set())
            taint = _Taint(self.info, fn, qual, static_params,
                           self.static_return_fns)
            if is_hot:
                taint.propagate()
                self._lint_hot(fn, qual, scope, taint)
            else:
                self._lint_host(fn, qual, scope)
            self._lint_call_sites(fn, qual, scope)

    # --- device-hot functions: syncs + branches + float introduction ------

    def _lint_hot(self, fn, qual, scope, taint: _Taint) -> None:
        def_line = fn.lineno
        for node in ast.walk(fn):
            if _owner(self.info, node, fn) is not fn:
                continue
            if isinstance(node, ast.Call):
                tail = _call_tail(node.func)
                np_name = self.info.np_func(node)
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in MATERIALIZERS
                    and node.args
                    and taint.taint(node.args[0]) == DEVICE
                ):
                    self._flag(
                        "host-sync", "host-sync", node.lineno, scope,
                        node.func.id,
                        f"{node.func.id}() on a traced value forces a "
                        "device→host sync (trace-time error inside jit)",
                        def_line,
                    )
                elif tail == "item" and isinstance(node.func, ast.Attribute):
                    if taint.taint(node.func.value) == DEVICE:
                        self._flag(
                            "host-sync", "host-sync", node.lineno, scope,
                            ".item", ".item() on a traced value forces a "
                            "device→host sync", def_line,
                        )
                elif (
                    np_name in NP_MATERIALIZERS
                    and node.args
                    and taint.taint(node.args[0]) == DEVICE
                ):
                    self._flag(
                        "host-sync", "host-sync", node.lineno, scope,
                        f"np.{np_name}",
                        f"np.{np_name}() on a traced value materializes the "
                        "device array on host", def_line,
                    )
                elif tail == "block_until_ready":
                    self._flag(
                        "host-sync", "unfenced-sync", node.lineno, scope,
                        "block_until_ready",
                        "block_until_ready inside jitted code", def_line,
                    )
                # Float introduction (reduction pass).
                self._lint_float_call(node, scope, def_line, taint)
            elif isinstance(node, (ast.If, ast.While)):
                if taint.test_taint(node.test) == DEVICE:
                    self._flag(
                        "host-sync", "traced-branch", node.lineno, scope,
                        "if" if isinstance(node, ast.If) else "while",
                        "branch on a traced value — data-dependent Python "
                        "control flow concretizes (sync or trace error); "
                        "use jnp.where/lax.cond", def_line,
                    )
            elif isinstance(node, ast.IfExp):
                if taint.test_taint(node.test) == DEVICE:
                    self._flag(
                        "host-sync", "traced-branch", node.lineno, scope,
                        "ifexp",
                        "conditional expression on a traced value", def_line,
                    )
            elif isinstance(node, ast.Assert):
                if taint.taint(node.test) == DEVICE:
                    self._flag(
                        "host-sync", "traced-branch", node.lineno, scope,
                        "assert", "assert on a traced value", def_line,
                    )
            elif isinstance(node, ast.Constant) and isinstance(node.value, float):
                self._flag(
                    "reduction", "float-dtype", node.lineno, scope,
                    repr(node.value),
                    "float constant in an integer device kernel — float "
                    "accumulation order is nondeterministic", def_line,
                )
            elif isinstance(node, ast.Attribute) and node.attr in FLOAT_DTYPES:
                self._flag(
                    "reduction", "float-dtype", node.lineno, scope,
                    node.attr,
                    f"{node.attr} in an integer device kernel — float "
                    "accumulation order is nondeterministic", def_line,
                )
            elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div):
                self._flag(
                    "reduction", "float-dtype", node.lineno, scope, "/",
                    "true division produces floats in a device kernel; "
                    "use // for integer math", def_line,
                )

    def _lint_float_call(self, node: ast.Call, scope, def_line, taint) -> None:
        tail = _call_tail(node.func)
        if tail in REDUCE_TAILS:
            self._flag(
                "reduction", "unordered-reduce", node.lineno, scope, tail,
                f"{tail} — segment/scatter reductions are unordered; prove "
                "integer dtype or fix the order", def_line,
            )
        elif tail in ("add", "mul", "max", "min") and isinstance(
            node.func, ast.Attribute
        ):
            # x.at[ix].add(v): nondeterministic only for float operands.
            recv = node.func.value
            if (
                isinstance(recv, ast.Subscript)
                and isinstance(recv.value, ast.Attribute)
                and recv.value.attr == "at"
            ):
                args_src = [ast.dump(a) for a in node.args]
                floaty = any(f in s for s in args_src for f in FLOAT_DTYPES)
                floaty |= any(
                    f in ast.dump(recv.value.value) for f in FLOAT_DTYPES
                )
                floaty |= any(
                    self._name_floaty(a) for a in node.args
                ) or self._name_floaty(recv.value.value)
                if floaty:
                    self._flag(
                        "reduction", "unordered-reduce", node.lineno, scope,
                        f".at.{tail}",
                        f"float scatter-{tail} — unordered float "
                        "accumulation diverges across runs/shards",
                        def_line,
                    )
        elif tail in COLLECTIVES:
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, ast.Set) or (
                    isinstance(a, ast.Call)
                    and isinstance(a.func, ast.Name)
                    and a.func.id in ("set", "frozenset")
                ):
                    self._flag(
                        "reduction", "axis-order", node.lineno, scope, tail,
                        f"{tail} over a set of axis names — iteration order "
                        "is hash-salted; pass an ordered tuple", def_line,
                    )

    def _name_floaty(self, node) -> bool:
        """Name assigned from a float-dtype expression in this module
        (single-assignment heuristic)."""
        if not isinstance(node, ast.Name):
            return False
        target = node.id
        for n in ast.walk(self.info.tree):
            if isinstance(n, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == target for t in n.targets
            ):
                if any(f in ast.dump(n.value) for f in FLOAT_DTYPES):
                    return True
        return False

    # --- host-side functions: seam enforcement -----------------------------

    def _device_handles(self, fn) -> Set[str]:
        """Names bound from jit-entry call results in this function."""
        out: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                tail = _call_tail(node.value.func)
                if tail in self.jit_entries:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            out.add(tgt.id)
                        elif isinstance(tgt, (ast.Tuple, ast.List)):
                            for e in tgt.elts:
                                if isinstance(e, ast.Name):
                                    out.add(e.id)
        return out

    def _lint_host(self, fn, qual, scope) -> None:
        def_line = fn.lineno
        in_seam = (self.info.rel, qual) in self.seam
        handles = self._device_handles(fn)

        def is_handle(node) -> bool:
            return isinstance(node, ast.Name) and node.id in handles

        for node in ast.walk(fn):
            if _owner(self.info, node, fn) is not fn:
                continue
            if not isinstance(node, ast.Call):
                continue
            tail = _call_tail(node.func)
            if tail == "block_until_ready" and not in_seam:
                self._flag(
                    "host-sync", "unfenced-sync", node.lineno, scope,
                    "block_until_ready",
                    "block_until_ready outside the sanctioned dispatch/"
                    "finish seam (manifest.JAXLINT_SYNC_SEAM)", def_line,
                )
            if in_seam or not handles:
                continue
            np_name = self.info.np_func(node)
            if (
                isinstance(node.func, ast.Name)
                and node.func.id in MATERIALIZERS
                and node.args
                and any(is_handle(s) for s in ast.walk(node.args[0]))
            ):
                self._flag(
                    "host-sync", "host-sync", node.lineno, scope,
                    node.func.id,
                    f"{node.func.id}() on a device handle outside the "
                    "dispatch/finish seam hides a blocking sync on the "
                    "commit path", def_line,
                )
            elif (
                np_name in NP_MATERIALIZERS
                and node.args
                and any(is_handle(s) for s in ast.walk(node.args[0]))
            ):
                self._flag(
                    "host-sync", "host-sync", node.lineno, scope,
                    f"np.{np_name}",
                    f"np.{np_name}() on a device handle outside the dispatch/"
                    "finish seam hides a blocking sync", def_line,
                )
            elif tail == "item" and isinstance(node.func, ast.Attribute) and (
                any(is_handle(s) for s in ast.walk(node.func.value))
            ):
                self._flag(
                    "host-sync", "host-sync", node.lineno, scope, ".item",
                    ".item() on a device handle outside the dispatch/"
                    "finish seam hides a blocking sync", def_line,
                )

    # --- jit-entry call sites: retrace hazards ----------------------------

    def _padded_names(self, fn) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                tail = _call_tail(node.value.func)
                if tail in self.pad_helpers:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            out.add(tgt.id)
                        elif isinstance(tgt, (ast.Tuple, ast.List)):
                            for e in tgt.elts:
                                if isinstance(e, ast.Name):
                                    out.add(e.id)
        return out

    def _runtime_sized(self, arg, padded: Set[str]) -> Optional[str]:
        """Why this argument expression is batch-shaped, or None. Bare
        names are judged at their construction site (_suspect_names)."""
        if isinstance(arg, ast.Name):
            return None
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Call):
                tail = _call_tail(sub.func)
                np_name = self.info.np_func(sub)
                if (
                    np_name in NP_SIZED
                    and sub.args
                    and not isinstance(sub.args[0], ast.Constant)
                    and not (
                        isinstance(sub.args[0], ast.Name)
                        and sub.args[0].id in padded
                    )
                ):
                    return f"np.{np_name}(...) sized by runtime data"
                if tail in self.pad_helpers:
                    return None  # explicitly padded inline
            if isinstance(sub, ast.Subscript) and isinstance(sub.slice, ast.Slice):
                sl = sub.slice
                for bound in (sl.lower, sl.upper):
                    if bound is not None and not isinstance(bound, ast.Constant):
                        return "slice with runtime bounds"
        return None

    def _suspect_names(self, fn, padded: Set[str]) -> Dict[str, int]:
        """Local names bound from a runtime-sized expression (and not
        re-bound from a pad helper) → their construction line. Named
        temporaries must not dodge the retrace-shape rule; the finding
        (and any `allow=`) anchors at the construction site, where the
        padding fix belongs."""
        out: Dict[str, int] = {}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if isinstance(node.value, ast.Call) and (
                _call_tail(node.value.func) in self.jit_entries
            ):
                continue  # jit results are flagged at their own call site
            why = self._runtime_sized(node.value, padded)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    if why is not None and tgt.id not in padded:
                        out[tgt.id] = node.lineno
                    elif tgt.id in out and why is None:
                        del out[tgt.id]  # re-bound to something benign
        return out

    def _lint_call_sites(self, fn, qual, scope) -> None:
        def_line = fn.lineno
        padded = self._padded_names(fn)
        suspects = self._suspect_names(fn, padded)
        is_hot = (self.info.rel, qual) in self.hot
        for node in ast.walk(fn):
            if _owner(self.info, node, fn) is not fn:
                continue
            if not isinstance(node, ast.Call):
                continue
            tail = _call_tail(node.func)
            if tail not in self.jit_entries:
                continue
            if is_hot:
                continue  # a traced inner call is one compile, not a retrace
            static_names = self.jit_entries[tail]
            # Positional static args: map index → parameter name through
            # the in-module signature (external entries like self._ops.*
            # are only checkable by keyword).
            params = []
            for q in self.info.by_name.get(tail, ()):
                params = [p.arg for p in self.info.funcs[q].args.args]
                break
            for i, arg in enumerate(node.args):
                if i < len(params) and params[i] in static_names and not (
                    isinstance(arg, (ast.Constant, ast.Name))
                ):
                    self._flag(
                        "retrace", "retrace-static-arg", arg.lineno, scope,
                        f"{tail}.{params[i]}",
                        f"non-constant value for static argument "
                        f"{params[i]!r} of {tail}() (positional) — every "
                        "new value is a full recompile", def_line,
                    )
            for kw in node.keywords:
                if kw.arg is None:
                    self._flag(
                        "retrace", "retrace-kwargs", node.lineno, scope, tail,
                        f"** expansion at jit entry {tail}() — dict-ordered "
                        "argument passing is a retrace/ordering hazard; "
                        "pass arguments explicitly", def_line,
                    )
                elif kw.arg in static_names and not isinstance(
                    kw.value, (ast.Constant, ast.Name)
                ):
                    # Bare Names are judged where they are constructed;
                    # a computed expression in a static slot is a
                    # retrace-per-value at THIS site.
                    self._flag(
                        "retrace", "retrace-static-arg", kw.value.lineno, scope,
                        f"{tail}.{kw.arg}",
                        f"non-constant value for static argument "
                        f"{kw.arg!r} of {tail}() — every new value is a "
                        "full recompile", def_line,
                    )
            shaped_args = list(node.args) + [
                kw.value for kw in node.keywords
                if kw.arg is not None and kw.arg not in static_names
            ]
            for arg in shaped_args:
                if isinstance(arg, ast.Name) and arg.id in suspects:
                    self._flag(
                        "retrace", "retrace-shape", suspects[arg.id], scope,
                        tail,
                        f"{arg.id!r} is sized by runtime data and reaches "
                        f"jit entry {tail}() — pad to a power-of-two bucket "
                        "(see _device_batch) or the call recompiles per "
                        "shape", def_line,
                    )
                    continue
                why = self._runtime_sized(arg, padded)
                if why is not None:
                    self._flag(
                        "retrace", "retrace-shape", node.lineno, scope, tail,
                        f"jit entry {tail}() called with a batch-shaped "
                        f"argument ({why}) — pad to a power-of-two bucket "
                        "(see _device_batch) or the call recompiles per "
                        "shape", def_line,
                    )


def _analyze(root, rels, passes, seam=None, pad_helpers=None,
             jit_entries=None) -> Dict[str, List[Finding]]:
    root = pathlib.Path(root)
    seam = manifest.JAXLINT_SYNC_SEAM if seam is None else seam
    pad_helpers = (
        manifest.JAXLINT_PAD_HELPERS if pad_helpers is None else pad_helpers
    )
    jit_entries = manifest.JIT_ENTRIES if jit_entries is None else jit_entries
    infos: Dict[str, _ModuleInfo] = {}
    for rel in rels:
        path = root / rel
        if not path.exists():
            continue
        source = path.read_text()
        infos[rel] = _ModuleInfo(rel, ast.parse(source), ann_mod.collect(source))
    hot = _device_hot(infos)
    out: Dict[str, List[Finding]] = {p: [] for p in passes}
    for rel, info in infos.items():
        lint = _ModuleLint(info, hot, seam, pad_helpers, jit_entries)
        lint.run()
        for p in passes:
            out[p].extend(lint.findings[p])
    for p in passes:
        out[p].sort(key=lambda f: (f.file, f.line, f.code))
    return out


def analyze_file(path, root, passes=("host-sync", "retrace", "reduction"),
                 seam=None, pad_helpers=None, jit_entries=None):
    """Single-file entry for the analyzer's own tests (fixtures)."""
    path = pathlib.Path(path)
    root = pathlib.Path(root)
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    by_pass = _analyze(root, (rel,), passes, seam=seam,
                       pad_helpers=pad_helpers, jit_entries=jit_entries)
    out: List[Finding] = []
    for p in passes:
        out.extend(by_pass[p])
    out.sort(key=lambda f: (f.file, f.line, f.code))
    return out


def run_selected(root, passes) -> List[Finding]:
    """Run any subset of the three jaxlint passes over ONE shared
    module analysis (parse + hot-set + taint are computed once, not
    once per pass — tools/check.py calls this for the whole trio)."""
    by_pass = _analyze(root, manifest.JAXLINT_MODULES, tuple(passes))
    out: List[Finding] = []
    for p in passes:
        out.extend(by_pass[p])
    return out


def run_hostsync(root) -> List[Finding]:
    return run_selected(root, ("host-sync",))


def run_retrace(root) -> List[Finding]:
    return run_selected(root, ("retrace",))


def run_reduction(root) -> List[Finding]:
    return run_selected(root, ("reduction",))


# ---------------------------------------------------------------------------
# Runtime mode: the jit compile-count registry.


class CompileRegistry:
    """Steady-state jit cache-miss counter.

    Two signals, both cheap: per-entry-point compile counts via the
    PjitFunction `_cache_size()` introspection (exact, attributable),
    and a global XLA compile counter hooked on jax.monitoring's
    `/jax/core/compile/backend_compile_duration` event (catches
    entry points nobody registered). `snapshot()`/`delta()` bracket a
    measured window; after warmup the delta must be ZERO — bench.py
    records it per workload and tools/bench_gate.py gates it exactly,
    so one retrace regression fails CI like a >10% perf drop.
    """

    _MONITOR_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self._entries: Dict[str, object] = {}
        self._global = 0
        self._installed = False

    def install(self) -> bool:
        """Hook the global compile-event listener (idempotent). Returns
        False when jax is unavailable."""
        if self._installed:
            return True
        try:
            import jax.monitoring as monitoring
        except ImportError:
            return False

        def _on_event(name, value, **kw):
            if name == self._MONITOR_EVENT:
                self._global += 1

        monitoring.register_event_duration_secs_listener(_on_event)
        self._installed = True
        return True

    def track(self, name: str, jitted) -> None:
        """Register a jitted entry point (anything with _cache_size)."""
        if hasattr(jitted, "_cache_size"):
            self._entries[name] = jitted

    def track_default_entries(self) -> None:
        """Register the repo's module-level jit entry points."""
        from tigerbeetle_tpu.ops import commit, commit_exact

        for mod, names in (
            (commit, ("create_transfers_fast", "register_accounts",
                      "write_balances", "read_balances")),
            (commit_exact, ("create_transfers_exact",)),
        ):
            for n in names:
                self.track(n, getattr(mod, n, None) or 0)

    def counts(self) -> Dict[str, int]:
        out = {
            name: int(fn._cache_size())
            for name, fn in self._entries.items()
        }
        out["__global__"] = self._global
        return out

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts())

    def delta(self, snap: Dict[str, int]) -> Dict[str, int]:
        """Compiles since `snap`, per entry (only nonzero-capable keys)."""
        now = self.counts()
        return {k: now.get(k, 0) - snap.get(k, 0) for k in now}

    def total_delta(self, snap: Dict[str, int]) -> int:
        """Global compile count since snap (covers untracked entries)."""
        return self.counts()["__global__"] - snap.get("__global__", 0)


# Process-wide registry: profile_e2e.py / bench.py share one hook.
compile_registry = CompileRegistry()
