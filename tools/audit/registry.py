"""Registry enumeration: the compile-cost contract, checked statically.

Every ``engine.plan.cached_program`` family declares (name, key schema,
bucket dims, max_programs) via ``declare_family``.  This module re-derives
each family's *reachable* bucket ladder from the owning module's quantizer
constants — the same pow2/bucket math the call sites run, swept over every
input size the pipeline admits — and fails if the reachable count exceeds
the declared ceiling.  That is the recompile-explosion detector: a
data-dependent size leaking into a key makes the reachable set unbounded
(or at least larger than declared) without ever running the pipeline.

Also the AST layer: every ``cached_program`` call site in the audited tree
must pass a literal family string that is actually declared (the
``audit-family`` lint rule runs the same scan jax-free inside CI's lint
job; here it feeds the cross-check against the live registry).
"""

from __future__ import annotations

import ast
from pathlib import Path

from .trace import Finding

# the packed-key gate: sbcn_candidates_packed requires lo * n + hi to fit
# int32, so no pair axis ever exceeds this many points
N_PACK_MAX = 46340
# predict query batches are bucketed q_pad = max(64, pow2(q)); the audit
# bounds the ladder at 2^21 queries per batch (16 buckets) — a batch above
# that is a deployment error long before it is a compile-cost problem
PREDICT_QPAD_MAX = 1 << 21


def _pow2_ceil(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


def enumerate_tier_emit() -> set[tuple]:
    """(kaa, kbb, chunk) buckets of the small-tier SBCN path.

    Call-site rules (core/sbcn.py): pairs are canonicalized to al <= bl,
    small means al <= _SMALL_AMAX and bl <= _SMALL_BMAX, the (1, 1)
    singleton pair is emitted on the host, and chunk is a pure function of
    the pow2-exact (kaa, kbb) tier.
    """
    from repro.core import sbcn

    out: set[tuple] = set()
    for al in range(1, sbcn._SMALL_AMAX + 1):
        for bl in range(al, sbcn._SMALL_BMAX + 1):
            if al == 1 and bl == 1:
                continue  # host-emitted singleton-singleton pair
            kaa, kbb = _pow2_ceil(al), _pow2_ceil(bl)
            chunk = max(8, sbcn._TIER_CHUNK_ELEMS // (kaa * kbb))
            out.add((kaa, kbb, chunk))
    return out


def enumerate_rowpath_emit() -> set[tuple]:
    """(rc, nc, nb, pb) buckets of the row-path SBCN family.

    rc/nc depend only on the pair's |A| and nb only on |B|; sweep both
    axes over every admissible size (1..N_PACK_MAX — the packed-key gate)
    and cross the A-classes with the B-classes reachable under the
    al <= bl canonicalization.
    """
    from repro.core import sbcn

    a_classes: dict[tuple, int] = {}   # (rc, nc) -> min achieving na
    for na in range(1, N_PACK_MAX + 1):
        rc = min(256, max(32, _pow2_ceil(na)))
        nc = _pow2_ceil(-(-na // rc))
        key = (rc, nc)
        if key not in a_classes:
            a_classes[key] = na
    b_classes: dict[int, int] = {}     # nb_pad -> max achieving nb
    for nb in range(1, N_PACK_MAX + 1):
        b_classes[max(64, _pow2_ceil(nb))] = nb

    out: set[tuple] = set()
    for (rc, nc), min_na in a_classes.items():
        for nbp, max_nb in b_classes.items():
            if min_na > max_nb:
                continue  # unreachable: canonicalization guarantees al <= bl
            out.add((rc, nc, nbp, sbcn._rowpath_block(rc, nc, nbp)))
    return out


def enumerate_edge_weights() -> set[tuple]:
    from repro.core import rng

    return {(rng._WEIGHT_CHUNK,)}


def enumerate_predict_attach() -> set[tuple]:
    out: set[tuple] = set()
    q = 64
    while q <= PREDICT_QPAD_MAX:
        out.add((q,))
        q *= 2
    return out


ENUMERATORS = {
    "tier_emit": enumerate_tier_emit,
    "rowpath_emit": enumerate_rowpath_emit,
    "edge_weights_canonical": enumerate_edge_weights,
    "predict_attach": enumerate_predict_attach,
}


def check_ladders() -> tuple[list[Finding], dict[str, int]]:
    """Reachable-ladder sizes vs declared ceilings, every declared family."""
    from repro.engine import plan

    findings: list[Finding] = []
    sizes: dict[str, int] = {}
    for name, fam in sorted(plan.PROGRAM_FAMILIES.items()):
        enum = ENUMERATORS.get(name)
        if enum is None:
            findings.append(Finding(
                name, "ladder",
                "declared family has no ladder enumerator in "
                "tools/audit/registry.py — the reachable key space cannot "
                "be bounded statically",
            ))
            continue
        ladder = enum()
        sizes[name] = len(ladder)
        if len(ladder) > fam.max_programs:
            findings.append(Finding(
                name, "ladder",
                f"reachable bucket ladder has {len(ladder)} programs but the "
                f"family declares max_programs={fam.max_programs} — "
                "recompile explosion: tighten the quantizer or raise the "
                "declared ceiling deliberately",
            ))
    for name in ENUMERATORS:
        if name not in plan.PROGRAM_FAMILIES:
            findings.append(Finding(
                name, "ladder",
                "enumerator exists but the family is not declared via "
                "engine.plan.declare_family",
            ))
    return findings, sizes


# -- AST call-site scan -----------------------------------------------------


def scan_callsites(root: Path) -> tuple[list[tuple[str, int, str | None]], list[Finding]]:
    """(file, line, family-or-None) for every cached_program call under root.

    ``None`` family means the key's first element is not a literal string —
    itself a finding (the registry check and the lint rule both need the
    literal).
    """
    sites: list[tuple[str, int, str | None]] = []
    findings: list[Finding] = []
    files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
    for py in files:
        try:
            tree = ast.parse(py.read_text(), filename=str(py))
        except SyntaxError:
            continue  # tools.lint reports parse errors; not this layer's job
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and _callee(node) == "cached_program" and node.args):
                continue
            fam = _literal_family(node.args[0])
            sites.append((str(py), node.lineno, fam))
            if fam is None:
                findings.append(Finding(
                    "<callsite>", "family-literal",
                    f"{py}:{node.lineno}: cached_program key does not start "
                    "with a literal family string — declarations cannot be "
                    "cross-checked statically",
                ))
    return sites, findings


def _callee(node: ast.Call) -> str | None:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _literal_family(key_node: ast.AST) -> str | None:
    if isinstance(key_node, ast.Tuple) and key_node.elts:
        first = key_node.elts[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            return first.value
    return None


def check_callsites(root: Path) -> list[Finding]:
    """Every call site names a declared family; every family has a site."""
    from repro.engine import plan

    sites, findings = scan_callsites(root)
    used: set[str] = set()
    for path, line, fam in sites:
        if fam is None:
            continue
        used.add(fam)
        if fam not in plan.PROGRAM_FAMILIES:
            findings.append(Finding(
                fam, "undeclared-family",
                f"{path}:{line}: cached_program call site uses family "
                f"{fam!r} which is not declared via declare_family "
                f"(declared: {sorted(plan.PROGRAM_FAMILIES)})",
            ))
    for name in sorted(set(plan.PROGRAM_FAMILIES) - used):
        findings.append(Finding(
            name, "dead-family",
            f"family is declared but no cached_program call site under the "
            "audited tree uses it",
        ))
    return findings
