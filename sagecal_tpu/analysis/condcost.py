"""cond-cost honesty: lax.cond branches must be priceable.

XLA's ``cost_analysis`` sums BOTH branches of a ``lax.cond`` — a
bytes/FLOPs account of it charges every execution for work the
common case never runs (the phantom-bytes class: PR 3 measured +31%
on LM damping trips until ``_chol_solve_shift`` was split out of
``_solve_damped`` so pricing could lower the executed body alone).

The contract: heavy work in a cond branch lives behind a MODULE-LEVEL
function (priceable standalone via ``roofline.lower_cost``). A branch
that inlines heavy ops — ``einsum``/``matmul``/``dot``/``linalg.*``/
``jax.scipy.*``/``lax.scan|while_loop|fori_loop|map``/``vmap`` — in a
lambda or local closure cannot be priced apart from its sibling.
Cheap elementwise glue (``jnp.where``, arithmetic) is fine; local
helpers are expanded one level, so a closure that merely forwards to a
module-level function passes.
"""

from __future__ import annotations

import ast

from sagecal_tpu.analysis.core import dotted

RULE = "cond-cost"

_COND_NAMES = ("jax.lax.cond", "lax.cond", "jax.lax.switch",
               "lax.switch")
_HEAVY_SUFFIXES = ("einsum", "matmul", "dot", "tensordot", "vdot",
                   "outer", "conv", "conv_general_dilated")
_HEAVY_PREFIXES = ("jnp.linalg.", "jax.numpy.linalg.", "jax.scipy.",
                   "jsp.", "scipy.")
_HEAVY_LAX = ("while_loop", "fori_loop", "scan", "map")


def _is_heavy_call(d: str | None) -> bool:
    if d is None:
        return False
    if any(d.startswith(p) for p in _HEAVY_PREFIXES):
        return True
    base = d.rsplit(".", 1)[-1]
    if base in _HEAVY_SUFFIXES:
        return True
    if base in _HEAVY_LAX and (d.startswith("lax.")
                               or d.startswith("jax.lax.")):
        return True
    if d in ("jax.vmap", "vmap", "jax.pmap"):
        return True
    return False


def _local_defs_in_scope(ctx, node):
    """name -> FunctionDef for defs local to any function enclosing
    ``node`` (the one-level expansion table)."""
    table: dict = {}
    for fn in ctx.enclosing_functions(node):
        for sub in ast.walk(fn):
            if (isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and sub is not fn):
                table.setdefault(sub.name, sub)
        # assigned lambdas count as local helpers too
        for sub in ast.walk(fn):
            if (isinstance(sub, ast.Assign)
                    and isinstance(sub.value, ast.Lambda)):
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        table.setdefault(t.id, sub.value)
    return table


def _branch_bodies(branch, locals_table):
    """The AST bodies a branch argument expands to: a lambda's body, a
    local def's body (expanded one level through local helpers), or
    nothing for module-level references (priceable boundary)."""
    if isinstance(branch, ast.Lambda):
        return [branch.body]
    if isinstance(branch, ast.Name) and branch.id in locals_table:
        fn = locals_table[branch.id]
        return fn.body if isinstance(fn.body, list) else [fn.body]
    return []


def _heavy_sites(ctx, bodies, locals_table, depth=0):
    """Heavy calls inlined in ``bodies``, expanding local-helper calls
    one extra level (module-level call targets are priceable
    boundaries and stop the walk)."""
    hits = []
    for body in bodies:
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if _is_heavy_call(d):
                hits.append((node, d))
            elif (depth < 2 and isinstance(node.func, ast.Name)
                  and node.func.id in locals_table
                  and node.func.id not in ctx.module_defs):
                inner = locals_table[node.func.id]
                inner_body = (inner.body if isinstance(inner.body, list)
                              else [inner.body])
                hits.extend(_heavy_sites(ctx, inner_body, locals_table,
                                         depth + 1))
    return hits


def check(ctx):
    findings: list = []
    for call in ast.walk(ctx.tree):
        if not (isinstance(call, ast.Call)
                and dotted(call.func) in _COND_NAMES):
            continue
        locals_table = _local_defs_in_scope(ctx, call)
        for branch in call.args[1:3]:
            bodies = _branch_bodies(branch, locals_table)
            if not bodies:
                continue               # module-level ref: priceable
            hits = _heavy_sites(ctx, bodies, locals_table)
            if not hits:
                continue
            ops = sorted({d for _, d in hits})
            bname = (branch.id if isinstance(branch, ast.Name)
                     else "<lambda>")
            findings.append(ctx.finding(
                RULE, branch,
                f"lax.cond branch '{bname}' inlines heavy op(s) "
                f"{', '.join(ops)} — cost analysis charges BOTH "
                f"branches every execution; move the body into a "
                f"module-level function so pricing can lower the "
                f"executed branch (PR 3 phantom-bytes class)"))
    return findings
