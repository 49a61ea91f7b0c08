"""Findings and the justification-required suppression file.

Every check emits :class:`Finding` records with a *stable id* — the
suppression key.  ``analysis/suppressions.toml`` maps exact ids to
one-line justifications; there are deliberately no wildcard or
per-file blanket ignores, so every intentional violation in the tree is
individually visible and carries its reason next to it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .config import load_toml


@dataclass
class Finding:
    kind: str           # e.g. "lock-cycle", "blocking-under-lock"
    id: str             # stable suppression key
    message: str        # human explanation with provenance
    module: str = ""    # repo-relative path of the principal site
    line: int = 0
    severity: str = "error"     # "error" | "warning"

    def format(self) -> str:
        loc = f"{self.module}:{self.line}" if self.module else "<global>"
        return f"[{self.kind}] {loc}\n  id: {self.id}\n  {self.message}"


class SuppressionError(ValueError):
    pass


@dataclass
class Suppressions:
    """Exact-id suppression set, each entry with a required reason."""

    entries: Dict[str, str] = field(default_factory=dict)

    @classmethod
    def load(cls, path: Optional[str]) -> "Suppressions":
        if path is None:
            return cls()
        doc = load_toml(path)
        entries: Dict[str, str] = {}
        for item in doc.get("suppress", []):
            sid = item.get("id", "")
            reason = str(item.get("reason", "")).strip()
            if not sid:
                raise SuppressionError("suppression entry without an id")
            if not reason:
                raise SuppressionError(
                    f"suppression {sid!r} has no justification — every "
                    "suppressed finding must say why it is intentional")
            if "*" in sid or sid.endswith(":"):
                raise SuppressionError(
                    f"suppression {sid!r} looks like a blanket ignore; "
                    "only exact finding ids are accepted")
            if sid in entries:
                raise SuppressionError(f"duplicate suppression {sid!r}")
            entries[sid] = reason
        return cls(entries)

    def split(self, findings: List[Finding]
              ) -> Tuple[List[Finding], List[Tuple[Finding, str]], List[str]]:
        """Partition into (active, suppressed-with-reason, unused-ids)."""
        active: List[Finding] = []
        suppressed: List[Tuple[Finding, str]] = []
        used = set()
        for f in findings:
            reason = self.entries.get(f.id)
            if reason is not None:
                suppressed.append((f, reason))
                used.add(f.id)
            else:
                active.append(f)
        unused = sorted(set(self.entries) - used)
        return active, suppressed, unused
