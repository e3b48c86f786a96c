"""jit-purity — recompile/staleness hazards inside jitted functions.

A ``@jax.jit`` body runs ONCE per (shape, static-arg) signature at
trace time; host-side calls inside it are baked into the compiled
program — the classic "it worked until the trace cache warmed" bug
family, and the static counterpart to PR 12's runtime compile ledger.

Rules (checked inside any function reached by jit — decorator forms
``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)`` /
``@functools.partial(jax.jit, ...)``, and ``name = jax.jit(fn)``
wrapping of a module-level function):

* **J001** — host clocks (``time.time/monotonic/perf_counter/...``,
  ``datetime.now``): the traced value is frozen at compile time.
* **J002** — host RNG (``random.*``, ``np.random.*``, ``os.urandom``,
  ``uuid.*``): same freeze, plus it silently de-determinizes the
  sampling path (the engine threads explicit PRNG keys instead).
* **J003** — iterating a ``set``/``frozenset`` (literal or call):
  iteration order varies across processes (PYTHONHASHSEED), so the
  traced program differs per process — a recompile / cross-host
  divergence hazard.  Wrap in ``sorted(...)``.
* **J004** — ``print`` inside a jit body: executes once at trace time,
  then never again — misleading during debugging and a tracer-leak
  smell in committed code.

Nested ``def``s inside a jitted function are traced too and are
checked; calls OUT to helper functions are not followed (annotate /
lint the helper where it is defined if it is jit-reached — the two
dispatch-site modules this repo jits from, ops/ and models/, keep
their helpers local).
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from vgate_tpu.analysis import _astutil as A
from vgate_tpu.analysis.core import Checker, Project, Violation

_CLOCK_CALLS = {
    "time.time",
    "time.monotonic",
    "time.perf_counter",
    "time.perf_counter_ns",
    "time.process_time",
    "time.time_ns",
    "datetime.now",
    "datetime.utcnow",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
}

_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.", "uuid.")
_RNG_CALLS = {"os.urandom"}


def _is_jit_decorator(dec: ast.expr) -> bool:
    """@jax.jit / @jit / @partial(jax.jit, ...) /
    @functools.partial(jax.jit, ...)"""
    chain = A.attr_chain(dec)
    if chain and chain[-1] == "jit":
        return True
    if isinstance(dec, ast.Call):
        name = A.dec_last_name(dec)
        if name == "jit":
            return True
        if name == "partial" and dec.args:
            first = A.attr_chain(dec.args[0])
            return bool(first) and first[-1] == "jit"
    return False


def _jit_wrapped_names(tree: ast.AST) -> Set[str]:
    """Function names wrapped via ``x = jax.jit(fn, ...)`` anywhere in
    the module (module level, __init__ bodies, ...)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = A.attr_chain(node.func)
        if not chain or chain[-1] != "jit":
            continue
        if node.args and isinstance(node.args[0], ast.Name):
            out.add(node.args[0].id)
    return out


class JitPurityChecker(Checker):
    name = "jit-purity"
    description = (
        "host clocks / RNG / set-iteration / print inside "
        "jit-traced functions (recompile + staleness hazards)"
    )
    scope = ("vgate_tpu/**/*.py", "benchmarks/**/*.py")

    def run(self, project: Project) -> List[Violation]:
        out: List[Violation] = []
        for ctx in project.files(*self.scope):
            tree = ctx.tree
            if tree is None:
                continue
            wrapped = _jit_wrapped_names(tree)
            for node in ast.walk(tree):
                if not isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                jitted = node.name in wrapped or any(
                    _is_jit_decorator(d) for d in node.decorator_list
                )
                if jitted:
                    out.extend(
                        self._check_body(ctx.relpath, node)
                    )
        return out

    def _check_body(
        self, relpath: str, fn: ast.stmt
    ) -> Iterable[Violation]:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                v = self._check_call(relpath, fn.name, node)
                if v is not None:
                    yield v
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = (
                    node.iter
                    if isinstance(node, ast.For)
                    else node.iter
                )
                if self._is_set_expr(it):
                    yield Violation(
                        checker=self.name,
                        path=relpath,
                        line=getattr(node, "lineno", fn.lineno),
                        rule="J003",
                        message=(
                            "iteration over a set inside jitted "
                            f"function {fn.name!r}: set order varies "
                            "per process (PYTHONHASHSEED) — the "
                            "traced program differs across hosts; "
                            "wrap in sorted(...)"
                        ),
                        symbol=f"{fn.name}:set-iter",
                    )

    def _check_call(
        self, relpath: str, fname: str, call: ast.Call
    ) -> Optional[Violation]:
        name = A.call_name(call)
        if name is None:
            return None
        if name in _CLOCK_CALLS:
            return Violation(
                checker=self.name,
                path=relpath,
                line=call.lineno,
                rule="J001",
                message=(
                    f"host clock {name}() inside jitted function "
                    f"{fname!r}: the value is frozen at trace time "
                    "(measure outside the jit boundary, or pass the "
                    "timestamp in as an argument)"
                ),
                symbol=f"{fname}:{name}",
            )
        if name in _RNG_CALLS or any(
            name.startswith(p) for p in _RNG_PREFIXES
        ):
            return Violation(
                checker=self.name,
                path=relpath,
                line=call.lineno,
                rule="J002",
                message=(
                    f"host RNG {name}() inside jitted function "
                    f"{fname!r}: the draw is frozen at trace time "
                    "and breaks replay determinism — thread a "
                    "jax.random key instead"
                ),
                symbol=f"{fname}:{name}",
            )
        if name == "print":
            return Violation(
                checker=self.name,
                path=relpath,
                line=call.lineno,
                rule="J004",
                message=(
                    f"print() inside jitted function {fname!r} runs "
                    "once at trace time, then never again — use "
                    "jax.debug.print or log outside the jit"
                ),
                symbol=f"{fname}:print",
            )
        return None

    @staticmethod
    def _is_set_expr(node: ast.expr) -> bool:
        if isinstance(node, ast.Set):
            return True
        if isinstance(node, ast.Call):
            chain = A.attr_chain(node.func)
            return bool(chain) and chain[-1] in ("set", "frozenset")
        return False
