"""The jaxpr/HLO dispatch auditor: statically verify the contract every
registered jitted entry point documents.

Three layers of evidence per entry point, cheapest first:

1. **Lowering metadata** — ``jit_fn.lower(*args).args_info`` carries a
   per-leaf ``donated`` flag: donation silently dropped (a wrapper re-jitted
   without ``donate_argnums``, a refactor moved an argument) is caught
   without compiling anything.  The StableHLO text is cross-checked for the
   ``tf.aliasing_output`` / ``jax.buffer_donor`` parameter attributes — the
   proof the donation survived into the program XLA sees.
2. **jaxpr walk** — every primitive in the traced graph (recursing through
   pjit/scan/cond sub-jaxprs) is scanned against the host-transfer denylist
   (callbacks, infeed/outfeed) and for forbidden dtype widenings
   (``convert_element_type`` uint8→float on the page paths, which must stay
   bit-exact).
3. **compiled HLO walk** — the post-optimization text is split with the
   :mod:`repro.roofline.hlo` walker (the same parser the roofline layer
   uses) and scanned for host-transfer opcodes and callback custom-calls
   that only appear after lowering.

Findings use the same :class:`~repro.analysis.findings.Finding` shape as
the AST linter; their ``path`` is the pseudo-path ``entry:<name>`` so the
one report covers both layers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.roofline import hlo as RH

# primitives whose presence inside a hot-path graph means a host round-trip
HOST_TRANSFER_PRIMITIVES = frozenset({
    "io_callback", "pure_callback", "debug_callback", "debug_print",
    "callback",
    "host_callback_call", "infeed", "outfeed",
})

# post-optimization HLO opcodes that cross the host boundary
HOST_TRANSFER_OPCODES = frozenset({
    "infeed", "outfeed", "send", "send-done", "recv", "recv-done",
})

_DONOR_ATTR = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")


@dataclasses.dataclass(frozen=True)
class EntryContract:
    """What one jitted entry point promises (engine/cluster docstrings made
    machine-checkable)."""
    donate: FrozenSet[int] = frozenset()    # positional args donated
    no_host_transfer: bool = True
    uint8_preserving: bool = False          # page path: no uint8->float
    dispatches_per_call: int = 1
    max_compiles: Optional[int] = None      # bound over the declared keys


@dataclasses.dataclass(frozen=True)
class AuditTarget:
    """One registered jitted entry point with example arguments at audit
    (reduced) geometry."""
    name: str
    fn: Callable                            # the jit-wrapped callable
    args: tuple
    contract: EntryContract
    kwargs: Dict[str, object] = dataclasses.field(default_factory=dict)


def _entry_finding(rule: str, target_name: str, msg: str) -> Finding:
    return Finding(rule=rule, path=f"entry:{target_name}", line=0,
                   message=msg)


# ---------------------------------------------------------------------------
# layer 1: donation
# ---------------------------------------------------------------------------

def donated_leaf_flags(lowered, n_args: int) -> List[List[bool]]:
    """Per positional arg, the ``donated`` flag of each flattened leaf."""
    args_info, _kwargs_info = lowered.args_info
    out: List[List[bool]] = []
    for i in range(n_args):
        leaves = jax.tree_util.tree_leaves(args_info[i])
        out.append([bool(leaf.donated) for leaf in leaves])
    return out


def check_donation(target: AuditTarget, lowered,
                   hlo_text: str) -> Tuple[Dict[str, int], List[Finding]]:
    findings: List[Finding] = []
    flags = donated_leaf_flags(lowered, len(target.args))
    expected_leaves = 0
    surviving_leaves = 0        # declared AND actually donated at lowering
    for i, leaf_flags in enumerate(flags):
        if i in target.contract.donate:
            expected_leaves += len(leaf_flags)
            surviving_leaves += leaf_flags.count(True)
            if not all(leaf_flags):
                n_bad = leaf_flags.count(False)
                findings.append(_entry_finding(
                    "audit-donation", target.name,
                    f"arg {i} is documented as donated but {n_bad}/"
                    f"{len(leaf_flags)} of its buffers are not — donation "
                    f"was silently dropped (copy fallback)"))
        elif any(leaf_flags):
            findings.append(_entry_finding(
                "audit-donation", target.name,
                f"arg {i} is donated but the contract does not declare it "
                f"— callers may still be holding the buffer"))
    # cross-check only what args_info says IS donated — a dropped donation
    # already fired above and must not double-report here
    marked = len(_DONOR_ATTR.findall(hlo_text))
    if marked < surviving_leaves:
        findings.append(_entry_finding(
            "audit-donation", target.name,
            f"lowered module marks only {marked} of {surviving_leaves} "
            f"donated buffers (tf.aliasing_output/jax.buffer_donor); "
            f"donation did not survive lowering"))
    return {"donated_leaves": sum(f.count(True) for f in flags),
            "expected_donated_leaves": expected_leaves,
            "hlo_donor_marks": marked}, findings


# ---------------------------------------------------------------------------
# layer 2: jaxpr walk
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: Dict):
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for u in vals:
            if hasattr(u, "eqns"):                      # Jaxpr
                yield u
            elif hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr                           # ClosedJaxpr

def iter_eqns(jaxpr):
    """Every equation in ``jaxpr`` and (recursively) its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def trace_jaxpr(fn, args, kwargs=None):
    # close over kwargs: make_jaxpr does not honor a pjit's static_argnames,
    # and every audited entry point's kwargs are static config
    if kwargs:
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args).jaxpr
    return jax.make_jaxpr(fn)(*args).jaxpr


def host_transfer_eqns(jaxpr) -> List[str]:
    return [e.primitive.name for e in iter_eqns(jaxpr)
            if e.primitive.name in HOST_TRANSFER_PRIMITIVES]


def uint8_upcast_eqns(jaxpr) -> List[str]:
    """convert_element_type equations that widen uint8 to floating — a
    page-path snapshot silently losing bit-exactness (and paying 4x the
    bytes)."""
    bad = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name != "convert_element_type":
            continue
        src = e.invars[0].aval.dtype
        dst = e.params.get("new_dtype")
        if src == jnp.uint8 and dst is not None and \
                jnp.issubdtype(dst, jnp.floating):
            bad.append(f"uint8->{jnp.dtype(dst).name}")
    return bad


# ---------------------------------------------------------------------------
# layer 3: compiled-HLO walk (the roofline parser as backend)
# ---------------------------------------------------------------------------

def hlo_host_transfer_ops(compiled_text: str) -> List[str]:
    """Opcodes crossing the host boundary in post-optimization HLO —
    parsed with the same :func:`repro.roofline.hlo.split_computations`
    walker the roofline layer uses."""
    out: List[str] = []
    for comp in RH.split_computations(compiled_text).values():
        for op in comp.ops:
            base = op.opcode.split(".")[0]
            if base in HOST_TRANSFER_OPCODES:
                out.append(base)
            elif base == "custom-call" and "callback" in op.rest:
                out.append("custom-call:callback")
    return out


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def audit_target(target: AuditTarget,
                 compiled: bool = True) -> Tuple[Dict, List[Finding]]:
    """Audit ONE entry point against its contract; returns the record for
    the report plus any findings."""
    findings: List[Finding] = []
    lowered = target.fn.lower(*target.args, **target.kwargs)
    record: Dict[str, object] = {
        "name": target.name,
        "dispatches_per_call": target.contract.dispatches_per_call,
    }

    info, dn_findings = check_donation(target, lowered, lowered.as_text())
    record.update(info)
    findings.extend(dn_findings)

    jaxpr = trace_jaxpr(target.fn, target.args, target.kwargs)
    host = host_transfer_eqns(jaxpr)
    record["jaxpr_host_transfer_eqns"] = len(host)
    if target.contract.no_host_transfer and host:
        findings.append(_entry_finding(
            "audit-host-transfer", target.name,
            f"host-transfer primitives inside the jitted graph: "
            f"{sorted(set(host))}"))

    if target.contract.uint8_preserving:
        ups = uint8_upcast_eqns(jaxpr)
        record["uint8_upcasts"] = len(ups)
        if ups:
            findings.append(_entry_finding(
                "audit-dtype", target.name,
                f"uint8 page path widens to float: {sorted(set(ups))} — "
                f"snapshots must stay bit-exact uint8"))

    if compiled:
        hlo_text = lowered.compile().as_text()
        ops = hlo_host_transfer_ops(hlo_text)
        record["hlo_host_transfer_ops"] = len(ops)
        if target.contract.no_host_transfer and ops:
            findings.append(_entry_finding(
                "audit-host-transfer", target.name,
                f"compiled HLO contains host-boundary ops: "
                f"{sorted(set(ops))}"))
    return record, findings


def audit_bucket_stability(engine, declared: Sequence[int]) -> List[Finding]:
    """The prefill compile-key set: the image of ``_bucket_len`` over every
    admissible prompt length must be exactly the declared bucket set —
    otherwise an unexpected length recompiles in production."""
    image = sorted({engine._bucket_len(n)
                    for n in range(1, engine.max_len + 1)})
    if image != sorted(declared):
        return [_entry_finding(
            "audit-compile-keys", "prefill",
            f"bucket image {image} over lengths 1..{engine.max_len} "
            f"!= declared bucket set {sorted(declared)}")]
    return []


def run_audit(targets: Sequence[AuditTarget], *, compiled: bool = True,
              extra_findings: Sequence[Finding] = ()) -> Dict[str, object]:
    """Audit every target; returns the report's ``audit`` section (findings
    inline, serialized)."""
    records, findings = [], list(extra_findings)
    for t in targets:
        rec, fs = audit_target(t, compiled=compiled)
        records.append(rec)
        findings.extend(fs)
    return {
        "targets": records,
        "compiled_hlo_checked": bool(compiled),
        "findings": [f.as_dict() for f in findings],
    }
