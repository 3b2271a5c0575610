"""Pass 2 — JAX trace discipline over ``jylis_tpu/ops/`` (rules JL2xx).

The merge kernels' speed rests on trace discipline: a host sync inside a
jit function serialises the device pipeline, a Python branch on a traced
value either crashes at trace time or silently bakes one side into the
compiled program, an implicit dtype leaves promotion to the ambient
``jax_enable_x64`` state (the lattices are u64; the documented guard is
``with enable_x64(False)`` around kernel-dtype blocks), and a
``jax.jit`` constructed per call throws
the compile cache away every time.

Reachability: a function is "jit code" when decorated with ``jax.jit`` /
``@partial(jax.jit, …)`` (static args read from ``static_argnums`` /
``static_argnames``), or when a jit-decorated function in the same
module calls it by name (transitively).
"""

from __future__ import annotations

import ast

from . import Finding, Source, dotted_name, parent_map

HOST_SYNC_METHODS = {"item", "block_until_ready", "tolist"}
HOST_SYNC_CALLS = {"np.asarray", "np.array", "jax.device_get", "numpy.asarray", "numpy.array"}
HOST_CASTS = {"float", "int", "bool"}
DTYPE_IMPLICIT_CTORS = {
    "jnp.asarray", "jnp.array", "jnp.zeros", "jnp.ones", "jnp.full",
    "jnp.empty", "jnp.arange",
}
SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}
# function-name prefixes allowed to construct jits (setup, not serving)
JIT_CTOR_OK_PREFIXES = ("__init__", "make", "build", "_make", "_build", "warm", "setup")


def _jit_decorator_info(fn: ast.FunctionDef):
    """(is_jit, static_param_names) from the decorator list."""
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = dotted_name(target)
        inner = None
        if name.endswith("partial") and isinstance(dec, ast.Call) and dec.args:
            inner = dotted_name(dec.args[0])
            if not (inner == "jit" or inner.endswith(".jit")):
                continue
        elif not (name == "jit" or name.endswith(".jit")):
            continue
        static: set[str] = set()
        if isinstance(dec, ast.Call):
            params = [a.arg for a in fn.args.args]
            for kw in dec.keywords:
                if kw.arg == "static_argnames" and isinstance(
                    kw.value, (ast.Tuple, ast.List, ast.Constant)
                ):
                    elts = (
                        kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value]
                    )
                    static |= {
                        e.value for e in elts
                        if isinstance(e, ast.Constant) and isinstance(e.value, str)
                    }
                if kw.arg == "static_argnums" and isinstance(
                    kw.value, (ast.Tuple, ast.List, ast.Constant)
                ):
                    elts = (
                        kw.value.elts
                        if isinstance(kw.value, (ast.Tuple, ast.List))
                        else [kw.value]
                    )
                    for e in elts:
                        if isinstance(e, ast.Constant) and isinstance(e.value, int):
                            if 0 <= e.value < len(params):
                                static.add(params[e.value])
        return True, static
    return False, set()


def _module_functions(tree: ast.AST) -> dict[str, ast.FunctionDef]:
    return {
        n.name: n
        for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef)
    }


def _jit_reachable(tree: ast.AST):
    """{fn_name: static_params} for jit roots and their same-module
    callees (callees inherit an empty static set — conservatively every
    parameter of a helper is treated as traced)."""
    fns = _module_functions(tree)
    reach: dict[str, set[str]] = {}
    frontier: list[str] = []
    for name, fn in fns.items():
        is_jit, static = _jit_decorator_info(fn)
        if is_jit:
            reach[name] = static
            frontier.append(name)
    while frontier:
        cur = frontier.pop()
        for node in ast.walk(fns[cur]):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                callee = node.func.id
                if callee in fns and callee not in reach:
                    reach[callee] = set()
                    frontier.append(callee)
    return fns, reach


def _in_x64_guard(node: ast.AST, parents) -> bool:
    while node in parents:
        node = parents[node]
        if isinstance(node, ast.With):
            for item in node.items:
                if "enable_x64" in ast.unparse(item.context_expr):
                    return True
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
    return False


def _walk_body(fn: ast.FunctionDef):
    """Own body statements only — decorators are not the body (a
    `@partial(jax.jit, …)` decorator is the sanctioned spelling, not a
    per-call jit), and nested defs get their own reachability entry."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _traced_param_names(fn: ast.FunctionDef, static: set[str]) -> set[str]:
    args = fn.args
    names = {
        a.arg
        for a in args.args + args.posonlyargs + args.kwonlyargs
        if a.arg not in ("self", "cls")
    }
    return names - static


def _shape_derived(expr: ast.AST, static_locals: set[str]) -> bool:
    """Does the expression bottom out in trace-time shape data —
    `.shape`/`.ndim`/`len(…)` anywhere inside, or a local previously
    assigned from one?"""
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr in SHAPE_ATTRS:
            return True
        if (
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name)
            and n.func.id == "len"
        ):
            return True
        if isinstance(n, ast.Name) and n.id in static_locals:
            return True
    return False


def _static_locals(fn: ast.FunctionDef) -> set[str]:
    """Locals assigned from shape-derived expressions (transitively):
    `w = plane.shape[-1]` makes `w` a trace-time constant."""
    static: set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if (
                    isinstance(t, ast.Name)
                    and t.id not in static
                    and _shape_derived(node.value, static)
                ):
                    static.add(t.id)
                    changed = True
    return static


def _name_use_is_static_shaped(
    name_node: ast.Name, parents, static_locals: set[str]
) -> bool:
    """Uses that read trace-time constants, not traced data:
    `param.shape[0] > 1` (any attribute chain reaching .shape/.ndim/
    .dtype), `len(param)`, `isinstance(param, …)`, `param is None`, and
    comparisons whose other side is shape-derived (`if width == w` where
    `w = plane.shape[-1]` — the host-static width convention)."""
    node: ast.AST = name_node
    while True:
        parent = parents.get(node)
        if isinstance(parent, ast.Attribute):
            if parent.attr in SHAPE_ATTRS:
                return True
            node = parent
            continue
        if isinstance(parent, ast.Subscript) and node is parent.value:
            node = parent
            continue
        break
    if isinstance(parent, ast.Call) and isinstance(parent.func, ast.Name):
        if parent.func.id in ("len", "isinstance"):
            return True
    if isinstance(parent, ast.Compare):
        if any(isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops):
            return True
        others = [
            o
            for o in [parent.left] + list(parent.comparators)
            if o is not node
        ]
        if others and all(_shape_derived(o, static_locals) for o in others):
            return True
    return False


def run(sources: list[Source]) -> list[Finding]:
    out: list[Finding] = []
    for src in sources:
        parents = parent_map(src.tree)
        fns, reach = _jit_reachable(src.tree)

        # JL204 applies module-wide (jit construction anywhere hot)
        for fn in ast.walk(src.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name.startswith(JIT_CTOR_OK_PREFIXES):
                continue
            for node in _walk_body(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                is_jit_ctor = name == "jit" or name.endswith(".jit")
                if not is_jit_ctor and name.endswith("partial") and node.args:
                    inner = dotted_name(node.args[0])
                    is_jit_ctor = inner == "jit" or inner.endswith(".jit")
                if is_jit_ctor:
                    out.append(
                        Finding(
                            "JL204", src.rel, node.lineno,
                            f"`jax.jit` constructed inside `{fn.name}` — a "
                            "fresh jit per call discards the compile cache; "
                            "hoist it to module level or a setup path",
                            src.line_src(node.lineno),
                        )
                    )

        for name, static in sorted(reach.items()):
            fn = fns[name]
            traced = _traced_param_names(fn, static)
            statics = _static_locals(fn)
            for node in _walk_body(fn):
                # JL201: host syncs
                if isinstance(node, ast.Call):
                    cname = dotted_name(node.func)
                    if cname in HOST_SYNC_CALLS:
                        out.append(
                            Finding(
                                "JL201", src.rel, node.lineno,
                                f"`{cname}` inside jit-reachable `{name}` — "
                                "forces a device->host sync under trace",
                                src.line_src(node.lineno),
                            )
                        )
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in HOST_SYNC_METHODS
                        and not node.args
                    ):
                        out.append(
                            Finding(
                                "JL201", src.rel, node.lineno,
                                f"`.{node.func.attr}()` inside jit-reachable "
                                f"`{name}` — host sync on a traced value",
                                src.line_src(node.lineno),
                            )
                        )
                    elif (
                        isinstance(node.func, ast.Name)
                        and node.func.id in HOST_CASTS
                        and node.args
                        and isinstance(node.args[0], ast.Name)
                        and node.args[0].id in traced
                    ):
                        out.append(
                            Finding(
                                "JL201", src.rel, node.lineno,
                                f"`{node.func.id}({node.args[0].id})` inside "
                                f"jit-reachable `{name}` — concretises a "
                                "traced value on the host",
                                src.line_src(node.lineno),
                            )
                        )
                    # JL203: dtype-implicit constructors
                    if (
                        dotted_name(node.func) in DTYPE_IMPLICIT_CTORS
                        and not any(kw.arg == "dtype" for kw in node.keywords)
                        and len(node.args) < 2  # positional dtype (2nd arg)
                        and not _in_x64_guard(node, parents)
                    ):
                        out.append(
                            Finding(
                                "JL203", src.rel, node.lineno,
                                f"`{dotted_name(node.func)}` without an "
                                f"explicit dtype inside jit-reachable "
                                f"`{name}` — result dtype depends on the "
                                "ambient x64 state; pass dtype= or guard "
                                "with enable_x64",
                                src.line_src(node.lineno),
                            )
                        )
                # JL202: data-dependent branching
                if isinstance(node, (ast.If, ast.While)):
                    for n in ast.walk(node.test):
                        if (
                            isinstance(n, ast.Name)
                            and n.id in traced
                            and not _name_use_is_static_shaped(
                                n, parents, statics
                            )
                        ):
                            out.append(
                                Finding(
                                    "JL202", src.rel, node.lineno,
                                    f"Python branch on traced `{n.id}` inside "
                                    f"jit-reachable `{name}` — use lax.cond/"
                                    "jnp.where, or mark the arg static",
                                    src.line_src(node.lineno),
                                )
                            )
                            break
    return out
