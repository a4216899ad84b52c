"""Core data model for fslint: findings, configuration, suppressions.

A ``Finding`` is one rule violation at one source location.  Its
``fingerprint`` deliberately excludes the line number so a committed
baseline survives unrelated edits above the finding (see
``baseline.py``).

Suppressions are per-site trailing comments of the form
``fslint: disable=FS001(caller rebinds via return)`` (preceded by a
hash sign; spelled out here so this docstring does not register one).
A suppression applies to findings on its own line and on the line
directly below it (so it can sit on its own line above a long
statement).  The reason is mandatory — a bare ``disable=FS001`` is
itself reported as FS000 so undocumented waivers cannot accumulate.
"""
from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# FS000 is reserved for malformed suppression comments and cannot be
# disabled itself.
BAD_SUPPRESSION = "FS000"

_SUPPRESS_RE = re.compile(r"#\s*fslint:\s*disable=(.*)$")
_CLAUSE_RE = re.compile(r"\s*(FS\d{3})\s*\(([^()]*)\)\s*")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str        # repo-relative, forward slashes
    line: int        # 1-based
    col: int         # 0-based
    qualname: str    # enclosing function (module-qualified) or "<module>"
    message: str

    def fingerprint(self) -> str:
        """Stable, line-independent identity used by the baseline."""
        key = "|".join((self.rule, self.path, self.qualname, self.message))
        return hashlib.blake2b(key.encode("utf-8"), digest_size=10).hexdigest()

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.qualname}] {self.message}")

    def to_json(self) -> dict:
        return {
            "rule": self.rule, "path": self.path, "line": self.line,
            "col": self.col, "qualname": self.qualname,
            "message": self.message, "fingerprint": self.fingerprint(),
        }


@dataclass
class Config:
    """Repo-tuned knobs; rules read conventions from here, never from
    hard-coded strings, so tests can retarget them at fixture trees."""

    # -- FS002: approved pow2 bucketing helpers (call by any alias path
    # whose final component matches).  Functions whose *return value*
    # contains a call to one of these become derived bucketing sources
    # (e.g. kernels/ops.py::_pad_runs).
    bucketing_helpers: Tuple[str, ...] = (
        "next_pow2", "_next_pow2", "slab_bucket_blocks",
        "_grow_to", "pow2_bucket",
    )

    # -- FS003: modules whose calls produce device values, and the
    # documented staged-copy sync points that are allowed to block.
    device_modules: Tuple[str, ...] = (
        "jax", "jnp", "jax.numpy", "jax.random", "jax.lax", "jax.nn",
    )
    device_functions: Tuple[str, ...] = ("sample_tokens",)
    sync_allowlist: Tuple[str, ...] = (
        "PagedPools.copy_out_staged", "PagedPools.copy_in_staged",
    )

    # -- hot-path roots: a function is "hot" when its bare name matches
    # one of these (or starts with a listed prefix) or it is reachable
    # from a hot function through the project call graph.
    hot_root_names: Tuple[str, ...] = ("step", "decode")
    hot_root_prefixes: Tuple[str, ...] = ("prefill",)

    # -- FS004: attribute paths whose final component names a device
    # pool; assignments to these (or ``X = X.at[..].set(..)`` updates of
    # them) outside donated jit bodies count as pool mutation.
    pool_attr_names: Tuple[str, ...] = ("gpu", "pool")
    # Wrappers that return their callable argument (possibly decorated):
    # closure direction labels flow through them unchanged.
    passthrough_wrappers: Tuple[str, ...] = ("wrap_copy",)
    # Attribute/keyword names under which data-plane closures are
    # registered for (possibly threaded) execution.
    copy_fn_names: Tuple[str, ...] = ("copy_fn",)
    # Name of the direction variable tested to segregate d2h from h2d.
    direction_var: str = "direction"
    out_label: str = "out"

    # -- FS005: lock attributes are recognised by suffix match on the
    # final component.
    lock_suffix: str = "lock"

    # -- FS007: calls that block the event loop inside ``async def``
    # bodies (the front-end's streaming server shares one loop across
    # every connection — one blocking call stalls them all).  Dotted
    # entries match the call path exactly or by suffix ("time.sleep"
    # also catches an aliased import); attr entries match any
    # ``obj.<attr>()`` call.  A call that is DIRECTLY awaited is exempt
    # (``await ws.recv()`` yields to the loop).
    async_blocking_calls: Tuple[str, ...] = (
        "time.sleep", "jax.block_until_ready", "jax.device_get",
    )
    async_blocking_attrs: Tuple[str, ...] = (
        "result", "recv", "recv_into", "recvfrom", "sendall", "accept",
    )

    # Rules to run (None = all registered).
    rules: Optional[Tuple[str, ...]] = None


@dataclass
class Suppressions:
    """Parsed per-site disable comments for one file."""

    # line -> {rule -> reason}
    by_line: Dict[int, Dict[str, str]] = field(default_factory=dict)
    malformed: List[Tuple[int, str]] = field(default_factory=list)

    def covers(self, line: int, rule: str) -> bool:
        for ln in (line, line - 1):
            clauses = self.by_line.get(ln)
            if clauses and rule in clauses:
                return True
        return False


def parse_suppressions(source: str) -> Suppressions:
    sup = Suppressions()
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if m is None:
            continue
        body = m.group(1).strip()
        clauses: Dict[str, str] = {}
        pos, ok = 0, True
        while pos < len(body):
            cm = _CLAUSE_RE.match(body, pos)
            if cm is None:
                ok = False
                break
            rule, reason = cm.group(1), cm.group(2).strip()
            if not reason or rule == BAD_SUPPRESSION:
                ok = False
                break
            clauses[rule] = reason
            pos = cm.end()
            if pos < len(body):
                if body[pos] != ",":
                    ok = False
                    break
                pos += 1
        if ok and clauses:
            sup.by_line[lineno] = clauses
        else:
            sup.malformed.append((lineno, body))
    return sup
