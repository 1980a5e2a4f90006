"""The built-in graph_lint passes over one program's optimized HLO.

Each pass proves (or refutes) one invariant the runtime forensics plane
can only observe post-mortem:

  donation              every donated buffer >= threshold actually
                        aliases in the executable (a silently dropped
                        donation doubles HBM for the largest buffers —
                        params and optimizer state)
  baked-constant        no closure-captured array >= threshold was
                        constant-folded into the executable (an
                        executable-resident copy of the table PLUS a
                        retrace every time the closure rebuilds — the
                        RecompileSentinel hazard, caught pre-launch)
  dtype-promotion       no unintended bf16/f16 -> f32 upcast >=
                        threshold inside AMP compute regions
                        (generalizes tools/hlo_copy_audit.py's single
                        hand-written check; loss_scale/optimizer/
                        grad_sync scopes are exempt — f32 master math
                        is their contract)
  implicit-replication  no all-gather materializes a full-size buffer
                        >= threshold (a shard_map out_spec or an
                        accidental replication re-assembling a sharded
                        param — the guardrail the unified sharding
                        planner (ROADMAP item 2) needs)
  f32-table-copy        no full-table f32 copy survives optimization
                        (VERDICT r4 weak #2, folded in from
                        tools/hlo_copy_audit.py — the CLI is now a shim
                        over this rule)

The cross-program collective-schedule verifier lives in
``analysis.schedule`` (it compares N rank/stage programs, not one).
Thresholds come from ``GraphLintConfig``; locations follow anatomy's
HLO-metadata op_name paths, so a finding reads
``jit(step)/.../attn/dot:convert`` — clickable back to the scope that
produced it.
"""
from __future__ import annotations

import re
from typing import List

from .engine import ProgramAudit, _SHAPE_RE, finding, rule
from .findings import Finding

__all__ = ["LAUNCH_RULES"]

# registration order = report order (severity ties broken by rule)
LAUNCH_RULES = ("donation", "baked-constant", "dtype-promotion",
                "implicit-replication", "f32-table-copy")


def _mib(n: int) -> str:
    return f"{n / (1 << 20):.2f} MiB"


@rule("donation")
def donation_audit(audit: ProgramAudit) -> List[Finding]:
    """Prove donated params/opt-state alias in the compiled executable
    (XLA's input_output_alias header vs jax's args_info donation
    flags, mapped through kept_var_idx)."""
    if audit.lowered is None:
        return []
    cfg = audit.config
    donated = [a for a in audit.flat_args()
               if a["donated"] and a["nbytes"] >= cfg.donation_bytes]
    if not donated:
        return []
    aliased = audit.alias_param_numbers()
    out: List[Finding] = []
    for a in donated:
        loc = f"{a['path']}:parameter"
        if not a["kept"]:
            out.append(Finding(
                rule="", severity="warning", location=loc,
                message=(f"donated {a['dtype']} buffer "
                         f"({_mib(a['nbytes'])}) is never used by the "
                         "program — the donation was dropped at "
                         "lowering (dead input: stop passing it, or "
                         "stop donating it)")))
        elif a["param"] not in aliased:
            out.append(finding(
                loc,
                f"donated {a['dtype']} buffer ({_mib(a['nbytes'])}) "
                "is NOT aliased in the compiled executable — the "
                "updated value allocates a second copy, doubling HBM "
                "for this buffer (entry parameter "
                f"{a['param']} missing from input_output_alias)"))
    return out


@rule("baked-constant")
def baked_constants(audit: ProgramAudit) -> List[Finding]:
    """Closure-captured arrays >= threshold constant-folded into the
    executable (recompile + HBM hazard for serving: the table lives in
    the program, and every closure rebuild is a new executable)."""
    cfg = audit.config
    out: List[Finding] = []
    for ins in audit.instructions():
        if ins.opcode != "constant":
            continue
        if ins.nbytes < cfg.constant_bytes:
            continue
        out.append(finding(
            ins.location,
            f"{ins.dtype}{list(ins.dims)} constant "
            f"({_mib(ins.nbytes)}) baked into the executable — pass "
            "it as an argument (donated if it is state); a "
            "closure-captured array recompiles on every rebuild and "
            "holds HBM inside the program image"))
    return out


_OPERAND_NAME_RE = re.compile(r"%?([\w.\-]+)")

_LOW_PRECISION = ("bf16", "f16")


def _first_operand_dtype(ins, dtype_of):
    """dtype of an instruction's first operand. Older XLA printed
    operand shapes inline (``convert(bf16[8,8]{1,0} %x)``); the
    installed one prints names only (``convert(%x)``), so the dtype is
    looked up at the operand's defining instruction."""
    m = _SHAPE_RE.search(ins.operands)
    if m:
        return m.group(1)
    m = _OPERAND_NAME_RE.search(ins.operands)
    return dtype_of.get(m.group(1)) if m else None


@rule("dtype-promotion")
def dtype_promotion(audit: ProgramAudit) -> List[Finding]:
    """Unintended f32/f64 upcasts of >=-threshold low-precision
    tensors inside AMP compute regions (scopes whose f32 math is the
    contract — loss_scale, optimizer, grad_sync — are exempt)."""
    cfg = audit.config
    out: List[Finding] = []
    dtype_of = {i.name: i.dtype for i in audit.instructions()}
    for ins in audit.instructions():
        if ins.opcode != "convert":
            continue
        if ins.dtype not in ("f32", "f64"):
            continue
        if ins.nbytes < cfg.promotion_bytes:
            continue
        src = _first_operand_dtype(ins, dtype_of)
        if src not in _LOW_PRECISION:
            continue
        sc = ins.scope()
        if sc in cfg.amp_exempt_scopes:
            continue
        out.append(finding(
            ins.location,
            f"{src} -> {ins.dtype} upcast materializes "
            f"{_mib(ins.nbytes)} "
            f"({ins.dtype}{list(ins.dims)}) inside "
            f"{'scope ' + sc if sc else 'an unattributed region'} — "
            "AMP compute should stay low-precision; an explicit "
            ".astype/f32 accumulation here doubles the bytes and "
            "defeats the MXU double-rate path"))
    return out


@rule("implicit-replication")
def implicit_replication(audit: ProgramAudit) -> List[Finding]:
    """shard_map outputs/intermediates that re-materialize full-size
    buffers: all-gathers whose result >= threshold (an out_spec that
    drops a mesh axis, or XLA re-assembling a sharded param)."""
    cfg = audit.config
    out: List[Finding] = []
    for ins in audit.instructions():
        if ins.opcode not in ("all-gather", "all-gather-start"):
            continue
        # async form yields (input, output) — the materialized result
        # is the LARGEST tuple member, not the first
        nbytes = ins.max_nbytes() if ins.opcode.endswith("-start") \
            else ins.nbytes
        if nbytes < cfg.replication_bytes:
            continue
        out.append(finding(
            ins.location,
            f"all-gather materializes {ins.dtype}{list(ins.dims)} "
            f"({_mib(nbytes)}) on every device — an implicit full "
            "replication (check the shard_map out_specs / sharding "
            "constraints; a planner output should stay sharded)"))
    return out


@rule("f32-table-copy")
def f32_table_copy(audit: ProgramAudit) -> List[Finding]:
    """Full-size f32 copies surviving in the optimized module (the
    hlo_copy_audit check, generalized from one hand-pinned vocab-table
    shape to a byte threshold)."""
    cfg = audit.config
    out: List[Finding] = []
    # copy-done included deliberately (the legacy hlo_copy_audit op
    # set): a start/done pair reports twice, but if a TPU layout
    # variant ever defeats the tuple parse on the -start line, the
    # plain-typed -done line still trips the rule — detection must
    # not hinge on one line parsing
    for ins in audit.instructions():
        if ins.opcode not in ("copy", "copy-start", "copy-done"):
            continue
        if ins.dtype not in ("f32", "f64"):
            continue
        if ins.nbytes < cfg.copy_bytes:
            continue
        out.append(finding(
            ins.location,
            f"{ins.dtype}{list(ins.dims)} {ins.opcode} "
            f"({_mib(ins.nbytes)}) survives in the optimized module — "
            "a full-table copy burns HBM bandwidth every step "
            "(VERDICT r4: ~6.3 ms/step on the f32 vocab table under "
            "AMP)"))
    return out
