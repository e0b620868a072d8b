"""Overlap-schedule rules: DEAD-DRAIN, PAIR-COUNT, NO-OVERLAP-WINDOW.

These three encode the core HDOT claims about the halo-exchange schedule:
no exchange is launched whose result nobody computes on (the PR-3 drain-step
bug), each mesh axis exchanges exactly one fwd+bwd ppermute pair per unrolled
step (over-decomposition did not duplicate traffic), and every non-trivial
collective has *some* computation it is dataflow-independent of (the static
precondition for the async scheduler to hide it).

All three lint the PRE-optimization HLO (``lowered.compiler_ir('hlo')``):
that dump preserves trace order and has not had dead code eliminated, so a
drain exchange the Python schedule emits pointlessly is still visible even
though XLA would DCE it — the lint catches the *schedule* bug, not whether
XLA happened to clean up after it.
"""
from __future__ import annotations

from collections import Counter
from typing import List

from repro.analysis.hlo_ir import (HloModule, computation_has_compute,
                                   independent_compute, reaches_live_compute)
from repro.analysis.rules.base import (Finding, LintContext, Rule,
                                       sized_collectives)


class DeadDrainRule(Rule):
    """A collective-permute whose result never reaches compute or the program
    output is a dead drain exchange: pure wire traffic with no consumer.

    An unpeeled halo_scan_nd makes exactly this: it issues the step-N
    exchange whose halos no step ever reads. Detected by tuple-aware
    interprocedural taint from each ppermute result.
    """
    id = "DEAD-DRAIN"
    fix_hint = ("peel the final exchange out of the steady-state loop "
                "(halo_scan_nd(..., peel=True)) so the drain step computes "
                "without communicating")

    def check(self, module: HloModule, ctx: LintContext) -> List[Finding]:
        out = []
        for comp, instr in module.collectives(["collective-permute"]):
            if not reaches_live_compute(module, comp, instr):
                out.append(self.op_finding(
                    f"collective-permute result is dead: no compute or "
                    f"program output ever reads it "
                    f"(pairs={list(instr.source_target_pairs)})",
                    comp, instr))
        return out


class PairCountRule(Rule):
    """Collective-permute pairs per axis per unrolled step must match the
    schedule's arithmetic: 2 * axes * steps for a peeled halo scan (each axis
    sends one forward + one backward halo per step; the peeled drain step
    sends none). More permutes means duplicated halo traffic; fewer means a
    missing exchange. Also checks fwd/bwd balance: every source_target_pairs
    ring must appear exactly as often as its reverse.

    The same arithmetic covers the MoE EP all-to-alls when
    ``expected_a2a_total`` is set: the chunked schedule emits exactly 2Q
    (dispatch + combine over Q capacity slices) per traced MoE layer body,
    forward and backward alike — more means duplicated token traffic, fewer
    a silently-merged (monolithic) dispatch. a2a is its own transpose, so
    there is no fwd/bwd ring-balance counterpart.
    """
    id = "PAIR-COUNT"
    fix_hint = ("one ppermute pair per axis per step: check the unroll "
                "factor, drain peeling, and that over-decomposition shares "
                "one exchange across interior chunks")

    def check(self, module: HloModule, ctx: LintContext) -> List[Finding]:
        permutes = module.collectives(["collective-permute"])
        out: List[Finding] = []
        if ctx.expected_permute_total is not None:
            got = len(permutes)
            if got != ctx.expected_permute_total:
                anchor = permutes[0] if permutes else None
                msg = (f"expected {ctx.expected_permute_total} "
                       f"collective-permutes for {ctx.target or 'schedule'}, "
                       f"found {got}")
                if anchor:
                    out.append(self.op_finding(msg, anchor[0], anchor[1]))
                else:
                    out.append(self.finding(msg))
        if ctx.expected_a2a_total is not None:
            a2as = module.collectives(["all-to-all"])
            got = len(a2as)
            if got != ctx.expected_a2a_total:
                msg = (f"expected {ctx.expected_a2a_total} all-to-alls for "
                       f"{ctx.target or 'schedule'} (2 x a2a_chunks per MoE "
                       f"layer body, dispatch + combine), found {got}")
                hint = ("the a2a_scan capacity chunking emits exactly "
                        "dispatch+combine per slice: check moe_a2a_chunks, "
                        "scan_layers (one textual body per direction) and "
                        "that remat is not re-tracing the MoE block")
                if a2as:
                    out.append(self.op_finding(msg, a2as[0][0], a2as[0][1],
                                               fix_hint=hint))
                else:
                    out.append(self.finding(msg, fix_hint=hint))
        # fwd/bwd balance: reverse of each ring pattern appears equally often
        pattern_counts = Counter(i.source_target_pairs for _, i in permutes)
        for pattern, n in sorted(pattern_counts.items()):
            rev = tuple(sorted((b, a) for a, b in pattern))
            canon = tuple(sorted(pattern))
            if canon == rev:
                continue  # self-inverse ring (2 devices)
            n_rev = sum(c for p, c in pattern_counts.items()
                        if tuple(sorted(p)) == rev)
            if n != n_rev:
                comp, instr = next((c, i) for c, i in permutes
                                   if i.source_target_pairs == pattern)
                out.append(self.op_finding(
                    f"unbalanced halo exchange: pattern {list(pattern)} "
                    f"appears {n}x but its reverse {n_rev}x — a shift "
                    f"without its counterpart is a lost halo",
                    comp, instr))
        return out


class NoOverlapWindowRule(Rule):
    """A collective with zero dataflow-independent compute in its computation
    cannot be overlapped no matter what the async scheduler does: every op
    either produces its operand or consumes its result. That is the
    two_phase shape (exchange -> barrier -> compute). HDOT lowerings must
    keep at least the interior chunks independent of every exchange.

    ``max_exposed_collectives`` allows the legitimate pipeline-fill ops
    (e.g. a scan's first exchange when steps stay in a while loop).
    """
    id = "NO-OVERLAP-WINDOW"
    fix_hint = ("restructure so interior compute does not consume the "
                "collective's result (over-decompose: boundary strips are "
                "the sole consumers, interior chunks run independently)")

    def check(self, module: HloModule, ctx: LintContext) -> List[Finding]:
        # a module with no compute anywhere (pure-communication microbench,
        # e.g. a standalone grad_sync jit) has nothing to hide latency
        # behind — the rule is about schedule shape, not about benchmarks
        if module.entry is None or not computation_has_compute(
                module, module.entry.name):
            return []
        exposed = []
        for comp, instr in sized_collectives(
                module, ["collective-permute", "all-reduce", "all-gather",
                         "reduce-scatter", "all-to-all"], ctx):
            if not independent_compute(module, comp, instr,
                                       min_elements=ctx.scalar_elements + 1):
                exposed.append((comp, instr))
        if len(exposed) <= ctx.max_exposed_collectives:
            return []
        return [self.op_finding(
            f"{instr.opcode} has zero dataflow-independent compute in "
            f"{comp.name}: nothing can hide its latency "
            f"({len(exposed)} exposed, "
            f"{ctx.max_exposed_collectives} allowed)",
            comp, instr) for comp, instr in exposed]
