"""Analysis configuration: the declared lock hierarchy + the doc catalog.

Two sources of truth feed the checker:

* ``analysis/lock_hierarchy.toml`` — the canonical lock hierarchy
  (rank-ordered lock levels, which locks are hot, which lock classes
  have many instances and a legal same-class acquisition order), plus
  the blocking-call list for the blocking-under-lock detector.
* ``docs/architecture.md`` — the metric catalog and span catalog tables
  (§6 Observability).  The contract lints parse the *documentation*, so
  an undocumented metric or span is a finding: the docs stay complete
  by construction.
"""

from __future__ import annotations

import os
import re
import tomllib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple


def load_toml(path: str) -> dict:
    """One analysis config file, parsed by the stdlib."""
    with open(path, "rb") as fh:
        return tomllib.load(fh)


# --------------------------------------------------------------------- #
# lock hierarchy
# --------------------------------------------------------------------- #
@dataclass
class LockLevel:
    name: str
    rank: int
    hot: bool = False
    # "none"      — single instance, nested same-class acquire is a bug
    # "reentrant" — RLock semantics: same-instance re-acquire is legal
    # "ascending" — many instances, must be acquired in ascending
    #               order-key order (the group-write rule)
    multi: str = "none"


@dataclass
class Hierarchy:
    levels: Dict[str, LockLevel] = field(default_factory=dict)
    blocking_calls: List[str] = field(default_factory=list)

    def rank(self, name: str) -> Optional[int]:
        lvl = self.levels.get(name)
        return None if lvl is None else lvl.rank

    def is_hot(self, name: str) -> bool:
        lvl = self.levels.get(name)
        return lvl is not None and lvl.hot

    def multi(self, name: str) -> str:
        lvl = self.levels.get(name)
        return "none" if lvl is None else lvl.multi

    def ordered(self) -> List[LockLevel]:
        return sorted(self.levels.values(), key=lambda l: l.rank)

    @classmethod
    def load(cls, path: Optional[str]) -> "Hierarchy":
        if path is None:
            return cls()
        doc = load_toml(path)
        levels: Dict[str, LockLevel] = {}
        for name, spec in doc.get("locks", {}).items():
            if not isinstance(spec, dict) or "rank" not in spec:
                raise ValueError(f"lock level {name!r} needs a rank")
            multi = str(spec.get("multi", "none"))
            if multi not in ("none", "reentrant", "ascending"):
                raise ValueError(f"lock level {name!r}: bad multi={multi!r}")
            levels[name] = LockLevel(
                name=name, rank=int(spec["rank"]),
                hot=bool(spec.get("hot", False)), multi=multi)
        ranks: Dict[int, str] = {}
        for lvl in levels.values():
            if lvl.rank in ranks:
                raise ValueError(
                    f"lock levels {ranks[lvl.rank]!r} and {lvl.name!r} "
                    f"share rank {lvl.rank} — the hierarchy must be a "
                    "total order over declared locks")
            ranks[lvl.rank] = lvl.name
        blocking = [str(c) for c in
                    doc.get("blocking", {}).get("calls", [])]
        return cls(levels=levels, blocking_calls=blocking)


# --------------------------------------------------------------------- #
# doc catalog (metrics + spans) parsed from architecture.md
# --------------------------------------------------------------------- #
_BACKTICK = re.compile(r"`([^`]+)`")
_PAREN = re.compile(r"\([^)]*\)")


@dataclass
class Catalog:
    metrics: Dict[str, Set[str]] = field(default_factory=dict)  # name→labels
    spans: Set[str] = field(default_factory=set)

    @classmethod
    def load(cls, path: Optional[str]) -> "Catalog":
        if path is None or not os.path.exists(path):
            return cls()
        with open(path, "r", encoding="utf-8") as fh:
            text = fh.read()
        return cls.parse(text)

    @classmethod
    def parse(cls, text: str) -> "Catalog":
        metrics: Dict[str, Set[str]] = {}
        spans: Set[str] = set()
        mode = None
        for line in text.splitlines():
            stripped = line.strip()
            if not stripped.startswith("|"):
                mode = None
                continue
            cells = [c.strip() for c in stripped.strip("|").split("|")]
            if not cells:
                continue
            head = cells[0].lower()
            if head == "metric":
                mode = "metrics"
                continue
            if head == "span":
                mode = "spans"
                continue
            if set(cells[0]) <= {"-", ":", " "}:    # separator row
                continue
            if mode == "metrics" and len(cells) >= 3:
                names = _BACKTICK.findall(cells[0])
                label_cell = _PAREN.sub("", cells[2])
                labels = set(_BACKTICK.findall(label_cell))
                for name in names:
                    metrics[name.strip()] = labels
            elif mode == "spans":
                for name in _BACKTICK.findall(cells[0]):
                    spans.add(name.strip())
        return cls(metrics=metrics, spans=spans)


# --------------------------------------------------------------------- #
# config discovery
# --------------------------------------------------------------------- #
def find_repo_root(start: str) -> Optional[str]:
    """Walk up from ``start`` to the directory holding ``analysis/`` (or
    ``pyproject.toml``) — where the default config files live."""
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        if (os.path.isdir(os.path.join(cur, "analysis"))
                or os.path.isfile(os.path.join(cur, "pyproject.toml"))):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent


def default_paths(root: Optional[str]) -> Tuple[Optional[str], Optional[str],
                                                Optional[str]]:
    """(hierarchy, suppressions, catalog) paths under ``root`` that exist."""
    if root is None:
        return None, None, None

    def opt(*parts: str) -> Optional[str]:
        p = os.path.join(root, *parts)
        return p if os.path.exists(p) else None

    return (opt("analysis", "lock_hierarchy.toml"),
            opt("analysis", "suppressions.toml"),
            opt("docs", "architecture.md"))
