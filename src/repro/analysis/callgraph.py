"""Project index: call graph, jit registry, donation propagation.

Resolution is deliberately conservative for a linter:

* ``Name`` callees resolve through module-level defs and import maps.
* ``self.m(...)`` resolves to the enclosing class's method.
* ``obj.m(...)`` falls back to *every* project method named ``m`` —
  an over-approximation that keeps reachability sound (FS003/FS004
  would rather scan one extra function than miss the hot path behind
  ``runner.decode`` / ``pools.copy_in_staged``).

Donation facts start at ``jax.jit(..., donate_argnums=...)`` defs and
propagate to wrappers: a function that forwards its own parameter into
a donated position donates that parameter too, so FS001 holds callers
of ``DecodeRunner.decode`` to the same rebind contract as callers of
the raw jitted step.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.astutil import (
    FunctionInfo,
    ModuleInfo,
    call_name,
    iter_source_files,
    last_component,
    load_module,
    source_roots,
)
from repro.analysis.core import Config


@dataclass
class JitSpec:
    qualname: str
    static_argnames: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()


def _const_strs(node: ast.expr) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return tuple(out)
    return ()


def _const_ints(node: ast.expr) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _is_jax_jit(node: ast.expr) -> bool:
    name = None
    if isinstance(node, (ast.Name, ast.Attribute)):
        from repro.analysis.astutil import dotted_path
        name = dotted_path(node)
    return name in ("jax.jit", "jit")


# SPMD wrappers that preserve the wrapped function's signature: a
# ``jax.jit(shard_map(f, ...), donate_argnums=...)`` site donates f's
# params exactly like ``jax.jit(f, ...)`` would, so the indexer must
# see through them or every mesh-sharded step shows up as an undonated
# unbucketed hot-path jit (false FS001/FS002/FS006).
_SPMD_WRAPPERS = ("shard_map", "jax.shard_map",
                  "jax.experimental.shard_map.shard_map",
                  "shmap", "pjit", "jax.experimental.pjit.pjit")


def _unwrap_jit_target(node: ast.expr) -> Optional[str]:
    """Dotted name of the function a jit call-form ultimately wraps.

    Sees through signature-preserving SPMD wrappers (``shard_map``,
    ``pjit``) and ``functools.partial`` so assignment-style specs like
    ``g = jax.jit(shard_map(f, mesh=..., out_specs=...), ...)`` map the
    alias ``g`` back onto ``f``'s def (param names then resolve for
    donation/bucketing facts).  None when the target is dynamic."""
    from repro.analysis.astutil import dotted_path
    if isinstance(node, (ast.Name, ast.Attribute)):
        return dotted_path(node)
    if isinstance(node, ast.Call) and node.args:
        callee = call_name(node)
        if callee in _SPMD_WRAPPERS + ("functools.partial", "partial"):
            return _unwrap_jit_target(node.args[0])
    return None


def parse_jit_decorator(dec: ast.expr) -> Optional[Tuple[Tuple[str, ...],
                                                         Tuple[int, ...]]]:
    """(static_argnames, donate_argnums) if ``dec`` is a jax.jit
    decoration, else None."""
    if _is_jax_jit(dec):
        return (), ()
    if isinstance(dec, ast.Call):
        callee = call_name(dec)
        if callee in ("functools.partial", "partial") and dec.args \
                and _is_jax_jit(dec.args[0]):
            static: Tuple[str, ...] = ()
            donate: Tuple[int, ...] = ()
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    static = _const_strs(kw.value)
                elif kw.arg == "donate_argnums":
                    donate = _const_ints(kw.value)
            return static, donate
        if _is_jax_jit(dec.func):
            static = ()
            donate = ()
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    static = _const_strs(kw.value)
                elif kw.arg == "donate_argnums":
                    donate = _const_ints(kw.value)
            return static, donate
    return None


class Project:
    """Cross-module index over a set of scanned source files."""

    def __init__(self, paths: List[Path], repo_root: Path, config: Config):
        self.config = config
        self.repo_root = repo_root
        self.modules: Dict[str, ModuleInfo] = {}
        roots = source_roots(paths)
        for f in iter_source_files(paths):
            mod = load_module(f, roots, repo_root)
            if mod is not None:
                self.modules[mod.modname] = mod

        # qualname -> FunctionInfo, plus a bare-name index for the
        # conservative attribute-call fallback.
        self.functions: Dict[str, FunctionInfo] = {}
        self.by_bare_name: Dict[str, List[FunctionInfo]] = {}
        for mod in self.modules.values():
            for qual, fi in mod.functions.items():
                self.functions[qual] = fi
                self.by_bare_name.setdefault(fi.name, []).append(fi)

        self.jit_specs: Dict[str, JitSpec] = {}
        # assignment-style alias qual -> the def it wraps (possibly
        # through shard_map/pjit/partial); _build_edges links the two so
        # reachable_from(jit_specs) covers the wrapped body ("inside the
        # trace" facts like FS006's donation exemption hold for it).
        self._jit_alias_of: Dict[str, str] = {}
        self._index_jit_defs()

        # qualname -> donated param names (seeded from jit specs,
        # closed under wrapper propagation).
        self.donated_params: Dict[str, Set[str]] = {}
        self._propagate_donation()

        self._edges: Dict[str, Set[str]] = {}
        self._build_edges()

        self.bucketing_sources: Set[str] = set(config.bucketing_helpers)
        self._derive_bucketing_sources()

        self.hot: Set[str] = set()
        self._compute_hot_set()

    # -- jit registry ---------------------------------------------------

    def _index_jit_defs(self) -> None:
        for qual, fi in self.functions.items():
            for dec in fi.node.decorator_list:
                parsed = parse_jit_decorator(dec)
                if parsed is not None:
                    static, donate = parsed
                    self.jit_specs[qual] = JitSpec(qual, static, donate)
                    break
        # assignment-style: g = jax.jit(f, donate_argnums=..., ...)
        for mod in self.modules.values():
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and _is_jax_jit(node.value.func)):
                    continue
                parsed = parse_jit_decorator(node.value)
                # re-parse as a call form: jax.jit(f, kw=...)
                static: Tuple[str, ...] = ()
                donate: Tuple[int, ...] = ()
                for kw in node.value.keywords:
                    if kw.arg == "static_argnames":
                        static = _const_strs(kw.value)
                    elif kw.arg == "donate_argnums":
                        donate = _const_ints(kw.value)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        qual = f"{mod.modname}.{tgt.id}"
                        self.jit_specs[qual] = JitSpec(qual, static, donate)
                        # map the alias onto the wrapped def so param
                        # names resolve — including through shard_map/
                        # pjit/partial wrappers (signature-preserving)
                        if node.value.args:
                            wrapped = _unwrap_jit_target(node.value.args[0])
                            if wrapped:
                                src = mod.functions.get(
                                    f"{mod.modname}.{wrapped}")
                                if src is not None:
                                    self.functions.setdefault(qual, src)
                                    self._jit_alias_of[qual] = src.qualname
                                    # rules look up facts by the qualname
                                    # a call RESOLVES to — the wrapped
                                    # def — so mirror the spec there
                                    self.jit_specs.setdefault(
                                        src.qualname,
                                        JitSpec(src.qualname, static,
                                                donate))

    def jit_spec_for(self, fi: FunctionInfo) -> Optional[JitSpec]:
        return self.jit_specs.get(fi.qualname)

    # -- call resolution ------------------------------------------------

    def resolve_call(self, call: ast.Call, mod: ModuleInfo,
                     caller: Optional[FunctionInfo]) -> List[FunctionInfo]:
        name = call_name(call)
        if name is None:
            return []
        return self.resolve_name(name, mod, caller)

    def resolve_name(self, name: str, mod: ModuleInfo,
                     caller: Optional[FunctionInfo]) -> List[FunctionInfo]:
        parts = name.split(".")
        # plain name: module-level def, then imported symbol
        if len(parts) == 1:
            fi = self.functions.get(f"{mod.modname}.{name}")
            if fi is not None:
                return [fi]
            full = mod.imports.get(name)
            if full is not None and full in self.functions:
                return [self.functions[full]]
            return []
        # self.m / cls.m -> method on the enclosing class
        if parts[0] in ("self", "cls") and caller is not None \
                and caller.class_name is not None and len(parts) == 2:
            fi = self.functions.get(
                f"{mod.modname}.{caller.class_name}.{parts[1]}")
            return [fi] if fi is not None else []
        # import-alias rooted: ops.insert_prefill, repro.kernels.ops.f
        root = mod.imports.get(parts[0])
        if root is not None:
            full = ".".join([root] + parts[1:])
            if full in self.functions:
                return [self.functions[full]]
        if name in self.functions:
            return [self.functions[name]]
        # Class.method in same module (e.g. FaultInjector.wrap_copy)
        if len(parts) == 2:
            fi = self.functions.get(f"{mod.modname}.{parts[0]}.{parts[1]}")
            if fi is not None:
                return [fi]
        # conservative fallback: any method with the same bare name
        bare = parts[-1]
        return [fi for fi in self.by_bare_name.get(bare, ())
                if fi.is_method]

    # -- call edges / reachability ---------------------------------------

    def _build_edges(self) -> None:
        for qual, fi in self.functions.items():
            callees: Set[str] = set()
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                # calls inside a nested named def belong to that def's
                # edge set (it is indexed separately); lambdas fold
                # into the enclosing def.
                owner = fi.module.function_for(node)
                if owner is not None and owner.node is not fi.node:
                    continue
                for target in self.resolve_call(node, fi.module, fi):
                    callees.add(target.qualname)
                # a nested def called locally also contributes an edge
                # to itself implicitly via resolve_call's qual lookup —
                # additionally link container -> nested def so
                # reachability descends into closures that are only
                # *referenced* (registered as callbacks), not called.
            for sub_qual, sub_fi in fi.module.functions.items():
                if sub_fi.node is not fi.node and \
                        sub_qual.startswith(qual + "."):
                    callees.add(sub_qual)
            wrapped = self._jit_alias_of.get(qual)
            if wrapped is not None:
                callees.add(wrapped)
            self._edges[qual] = callees

    def callees(self, qual: str) -> Set[str]:
        return self._edges.get(qual, set())

    def reachable_from(self, roots: Iterable[str]) -> Set[str]:
        seen: Set[str] = set()
        stack = [r for r in roots if r in self.functions]
        while stack:
            cur = stack.pop()
            if cur in seen:
                continue
            seen.add(cur)
            stack.extend(self._edges.get(cur, ()))
        return seen

    # -- hot set ---------------------------------------------------------

    def _compute_hot_set(self) -> None:
        cfg = self.config
        roots = []
        for qual, fi in self.functions.items():
            if fi.name in cfg.hot_root_names or \
                    any(fi.name.startswith(p) for p in cfg.hot_root_prefixes):
                roots.append(qual)
        self.hot = self.reachable_from(roots)

    # -- donation ---------------------------------------------------------

    def _propagate_donation(self) -> None:
        for qual, spec in self.jit_specs.items():
            fi = self.functions.get(qual)
            if fi is None or not spec.donate_argnums:
                continue
            pos = fi.positional_params
            names = {pos[i] for i in spec.donate_argnums if i < len(pos)}
            if names:
                self.donated_params[qual] = names

        changed = True
        while changed:
            changed = False
            for qual, fi in self.functions.items():
                my_params = set(fi.params)
                for node in ast.walk(fi.node):
                    if not isinstance(node, ast.Call):
                        continue
                    for callee in self.resolve_call(node, fi.module, fi):
                        donated = self.donated_params.get(callee.qualname)
                        if not donated:
                            continue
                        for pname, arg in self.map_call_args(node, callee):
                            if pname in donated and isinstance(arg, ast.Name) \
                                    and arg.id in my_params:
                                cur = self.donated_params.setdefault(
                                    qual, set())
                                if arg.id not in cur:
                                    cur.add(arg.id)
                                    changed = True

    def map_call_args(self, call: ast.Call,
                      callee: FunctionInfo) -> List[Tuple[str, ast.expr]]:
        """(param_name, arg_expr) pairs for a call site.  For methods
        called attribute-style the implicit self consumes the first
        positional parameter."""
        params = callee.positional_params
        offset = 0
        if callee.is_method and isinstance(call.func, ast.Attribute):
            from repro.analysis.astutil import dotted_path
            root = dotted_path(call.func)
            # ClassName.method(obj, ...) passes self explicitly
            if not (root and root.split(".")[0] == callee.class_name):
                offset = 1
        pairs: List[Tuple[str, ast.expr]] = []
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                break
            idx = i + offset
            if idx < len(params):
                pairs.append((params[idx], arg))
        for kw in call.keywords:
            if kw.arg is not None:
                pairs.append((kw.arg, kw.value))
        return pairs

    # -- bucketing sources (FS002) ----------------------------------------

    def _derive_bucketing_sources(self) -> None:
        """A function whose return expression calls an approved
        bucketing helper is itself a bucketing source (``_pad_runs``
        returns pow2-padded run tables)."""
        changed = True
        while changed:
            changed = False
            for qual, fi in self.functions.items():
                if last_component(qual) in self.bucketing_sources:
                    continue
                for node in ast.walk(fi.node):
                    if not isinstance(node, ast.Return) or node.value is None:
                        continue
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Call):
                            cn = call_name(sub)
                            if cn and last_component(cn) in \
                                    self.bucketing_sources:
                                self.bucketing_sources.add(
                                    last_component(qual))
                                changed = True
                                break

    def is_bucketing_call(self, call: ast.Call) -> bool:
        cn = call_name(call)
        return cn is not None and last_component(cn) in self.bucketing_sources
