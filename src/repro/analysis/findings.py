"""Finding records and the analysis report — the one output surface all
five lint layers (IR, plan, source, kernel, budget) emit into.

A `Finding` is a structured diagnostic: a rule id (``layer/rule-name``), a
severity, a location string (``file:line`` for source findings, a
``plan/group`` label for IR, plan, kernel and budget findings) and a human
message.  The `AnalysisReport` aggregates findings plus per-plan *proofs*
— the positive facts the verifier established (kernel present in N
groups, groups predicted == groups traced, zero f64 ops, cost envelopes
within budget) — and renders both; ``--ci`` exits nonzero iff any
error-severity finding survives.

**Severity profiles** (DESIGN.md §9): the same rule catalog serves three
consumers with different stakes.  ``severity_for(rule, profile)`` resolves
a rule's severity under a named profile — ``ci`` (the gate: suppressions
and baselines must be live, so stale-pragma / missing-baseline promote to
error), ``bench`` (the defaults: benchmarks record findings into health
blocks but should not abort a measurement run), ``notebook`` (advisory:
every error demotes to warning, nothing gates).  Per-rule overrides live
on the `Rule` itself; the notebook demotion is the profile-wide fallback.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["Severity", "Finding", "Rule", "AnalysisReport", "RULES",
           "PROFILES", "rule", "make_finding", "severity_for"]

# Severity order (render sorts errors first).
ERROR = "error"
WARNING = "warning"
INFO = "info"
Severity = str
_SEV_ORDER = {ERROR: 0, WARNING: 1, INFO: 2}

# Consumer profiles, strictest first.  "bench" is the default: rule
# severities apply as declared.
PROFILES = ("ci", "bench", "notebook")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One catalog entry: what a rule proves and why it matters."""

    id: str                 # "ir/f64-promotion"
    severity: Severity      # default severity of its findings
    summary: str            # one line, shown in renders
    rationale: str          # why violating it invalidates results
    # per-profile severity overrides as ((profile, severity), ...) pairs
    # (a tuple keeps the dataclass frozen/hashable)
    profiles: tuple = ()

    @property
    def layer(self) -> str:
        return self.id.split("/", 1)[0]

    def severity_in(self, profile: Optional[str]) -> Severity:
        """Effective severity under a named profile (None = declared)."""
        if profile is None or profile == "bench":
            return self.severity
        over = dict(self.profiles)
        if profile in over:
            return over[profile]
        if profile == "notebook" and self.severity == ERROR:
            return WARNING          # advisory: nothing gates a notebook
        return self.severity


# The full rule catalog.  DESIGN.md §7/§9 document each entry; tests
# assert every rule here fires on a deliberately-broken fixture.
RULES: dict[str, Rule] = {}


def rule(id: str, severity: Severity, summary: str, rationale: str,
         profiles: tuple = ()) -> Rule:
    for prof, sev in profiles:
        if prof not in PROFILES or sev not in _SEV_ORDER:
            raise ValueError(f"bad profile override {(prof, sev)!r} on {id}")
    r = Rule(id=id, severity=severity, summary=summary, rationale=rationale,
             profiles=profiles)
    if id in RULES:
        raise ValueError(f"duplicate rule id {id!r}")
    RULES[id] = r
    return r


def severity_for(rule_id: str, profile: Optional[str] = None) -> Severity:
    """A rule's effective severity under a profile (None = declared)."""
    if profile is not None and profile not in PROFILES:
        raise KeyError(f"unknown profile {profile!r}; known: {PROFILES}")
    return RULES[rule_id].severity_in(profile)


# --- IR layer -------------------------------------------------------------
rule("ir/kernel-missing", ERROR,
     "fused CC-tick kernel absent from a kernel-enabled lowering",
     "use_pallas_kernel=True must place the Pallas mltcp_cc_tick "
     "pallas_call inside the tick scan; its absence means the sweep runs "
     "the jnp oracle (perf claims about the fused path are void).")
rule("ir/kernel-fallback", ERROR,
     "config statically forces the kernel->oracle fallback",
     "non-default favoritism / non-linear F are outside the kernel's "
     "specialization; requesting use_pallas_kernel for such a config "
     "can only ever run unfused — fix the config or drop the flag.")
rule("ir/kernel-unexpected", WARNING,
     "pallas_call present in a kernel-disabled lowering",
     "a program that was asked for the jnp oracle must not dispatch the "
     "kernel; oracle-vs-kernel bit-equality checks depend on it.")
rule("ir/f64-promotion", ERROR,
     "float64 value or convert_element_type to f64 in the lowered program",
     "the engine and kernel are pinned bit-stable in f32; a silent f64 "
     "promotion (e.g. under jax_enable_x64) breaks kernel/oracle "
     "bit-equality and doubles memory traffic.")
rule("ir/host-callback", ERROR,
     "host callback / debug print / io callback in the hot path",
     "callbacks inside the tick scan force device->host syncs every "
     "iteration — timing figures measured with one in place are invalid.")
rule("ir/nested-control", ERROR,
     "non-whitelisted while/cond inside the tick-scan body",
     "the tick body is straight-line vectorized math; a stray lax.cond / "
     "while_loop usually means a python branch escaped tracing and will "
     "serialize the vmapped sweep.")

# --- plan layer -----------------------------------------------------------
rule("plan/group-split", INFO,
     "two plan points compile separately (group-split explainer)",
     "every extra compile group is an extra trace+compile; the explainer "
     "names the exact canonicalized fields that differ so splits are "
     "always accounted for.")
rule("plan/avoidable-split", WARNING,
     "compile-group split on value-only fields",
     "the differing fields are plain numeric values that could ride the "
     "batched sweep as traced SweepParams leaves (the PR-4 pattern); the "
     "split wastes traces.")
rule("plan/group-mismatch", ERROR,
     "predicted compile groups != programs actually traced",
     "grouping canonicalization and the jit static signature disagree — "
     "either the canonicalizer merges points the jit cache splits "
     "(silent retraces) or vice versa.")
rule("plan/retrace", ERROR,
     "re-tracing an already-traced compile group",
     "a warm group must hit the jaxpr cache; a retrace means something "
     "unhashable or dynamic leaked into the static config signature.")

# --- source layer ---------------------------------------------------------
rule("src/np-in-scan", ERROR,
     "numpy call in a function reachable from a scan body",
     "np.* inside traced code either fails under vmap/jit or silently "
     "constant-folds per trace; scan bodies must be pure jnp. "
     "Trace-time constants on static shapes may be whitelisted inline.")
rule("src/float-cast-traced", ERROR,
     "python float()/int()/bool() applied to a traced value",
     "concretizing a tracer raises under jit, or — worse — bakes a "
     "trace-time constant into the program so sweeps silently reuse the "
     "first point's value.")
rule("src/branch-on-traced", ERROR,
     "python `if` on a traced value inside traced code",
     "python control flow on tracers raises ConcretizationTypeError "
     "under jit; use jnp.where / lax.cond.")
rule("src/f64-literal", ERROR,
     "float64 literal outside NumPy-side config plumbing",
     "jnp.float64 / astype('float64') in traced code promotes the "
     "bit-stable f32 pipeline; np.float64 is fine only in numpy-side "
     "config plumbing (JobSpec.simple style) that never reaches a scan.")
rule("src/unit-suffix", ERROR,
     "add/subtract/compare across conflicting unit suffixes",
     "names suffixed _bytes/_s/_bps/_ticks carry units; summing or "
     "comparing across units (without a converting multiply/divide) is "
     "the classic silent protocol-parameter bug the RoCE CC sensitivity "
     "studies warn about.")
rule("src/stale-pragma", WARNING,
     "`# lint: allow(rule)` pragma that no longer suppresses anything",
     "a suppression must not outlive the code it excused: a pragma naming "
     "an unknown rule, or a rule that no longer fires on its line, is dead "
     "weight that will silently swallow the next real finding there.",
     profiles=(("ci", ERROR),))

# --- kernel layer (the Pallas CC-tick kernel body; DESIGN.md §9) ----------
rule("kernel/dyn-not-smem", ERROR,
     "DynamicParams operand is not an SMEM scalar vector",
     "the protocol scalars must ride as an f32[1, NDYN] SMEM ref: a VMEM (or "
     "missing) dyn operand means every grid step re-streams scalars "
     "through the vector path and the operand layout no longer matches "
     "ops.py's packing contract.")
rule("kernel/dyn-written", ERROR,
     "kernel body writes to the DynamicParams SMEM operand",
     "the dyn ref is read-only by contract — a store would make sweep "
     "points order-dependent (one point's protocol scalars leaking into "
     "the next grid step) and breaks the kernel/oracle bit-equality pin.")
rule("kernel/state-not-vmem", ERROR,
     "flow-state operand lives outside VMEM",
     "the perf claim is one HBM read per state array per tick with the "
     "working set VMEM-resident; an SMEM/HBM-pinned state ref silently "
     "serializes the vector loads the roofline model assumes.")
rule("kernel/block-misaligned", ERROR,
     "state block shape is not the (SUBLANES, LANES)-aligned tile",
     "blocks must tile (<=8, 128) exactly as ops.py packs [rows, 128] "
     "lanes; any other shape pads or splits vector registers and the "
     "static VMEM estimate (and the TPU lowering) no longer holds.")
rule("kernel/grid-remainder", ERROR,
     "grid does not cover exactly `rows` blocks",
     "ops.py pads flows so rows % block_rows == 0; a remainder grid step "
     "would need masking the kernel body does not implement — out-of-"
     "bounds lanes would read garbage and corrupt the padded flows.")
rule("kernel/operand-mismatch", ERROR,
     "kernel operand/result count differs from the specialization",
     "the (algo, variant, factors) specialization fixes the operand list "
     "(dyn + optional factors + IN_ORDER) and the result list (OUT_ORDER); "
     "a mismatch means ops.py's packing and the traced kernel disagree — "
     "state arrays are being dropped or duplicated.")
rule("kernel/f64-in-body", ERROR,
     "float64 value inside the kernel body",
     "the kernel is pinned elementwise f32 (bit-equal to the jnp oracle); "
     "an f64 intermediate doubles VMEM pressure and silently changes "
     "rounding versus the oracle.")
rule("kernel/gather-scatter", ERROR,
     "gather/scatter primitive inside the kernel body",
     "every body op must be elementwise over the [block, 128] tile; a "
     "gather or scatter breaks the one-pass VMEM-resident property and "
     "lowers to serialized memory traffic on TPU.")
rule("kernel/nested-control", ERROR,
     "while/cond/scan inside the kernel body",
     "algorithm and variant are static specialization parameters — the "
     "body must be straight-line; traced control flow means a python "
     "branch escaped specialization and will serialize the grid.")
rule("kernel/vmem-budget", ERROR,
     "static VMEM estimate per grid step exceeds the ceiling",
     "the kernel's whole working set (all in/out blocks) must fit VMEM "
     "with room for double buffering; exceeding the ceiling means the "
     "compiler will spill to HBM and the fused-tick perf claim is void.")

# --- budget layer (per-compile-group HLO cost envelopes) ------------------
rule("budget/drift", ERROR,
     "compile-group cost metric drifted beyond tolerance vs the baseline",
     "flops / HBM bytes / peak memory / collective bytes per compile "
     "group are pinned in analysis/budgets.json; unexplained drift means "
     "a change altered what the hot loop costs — either fix it or "
     "re-baseline deliberately via --update-budgets.")
rule("budget/missing-baseline", WARNING,
     "compile group has no baseline entry in budgets.json",
     "an unpinned group's cost can regress silently; record it with "
     "`python -m repro.analysis --update-budgets` (plans or groups can "
     "legitimately be new — hence warning outside CI).",
     profiles=(("ci", ERROR),))
rule("budget/stale-baseline", WARNING,
     "budgets.json pins groups the plan no longer produces",
     "a stale baseline entry means the plan's group structure changed "
     "(count or signature) without re-baselining — the remaining pins "
     "may be comparing unlike programs.",
     profiles=(("ci", ERROR),))
rule("budget/env-mismatch", WARNING,
     "budgets.json was recorded under a different environment",
     "cost envelopes depend on the smoke/full workload scale and the jax "
     "version that lowered them; comparing across environments would "
     "flag phantom drift, so budget checks are skipped (re-record with "
     "--update-budgets in this environment to re-arm them).")
rule("budget/unknown-dtype", WARNING,
     "HLO parser met a dtype with no known byte width",
     "collective-byte totals silently defaulting unknown dtypes to 4 "
     "bytes is exactly the wrong-total bug this rule surfaces; add the "
     "dtype to roofline.hlo._DTYPE_BYTES.",
     profiles=(("ci", ERROR),))


@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic from any lint layer."""

    rule: str               # a RULES key
    where: str              # "src/...py:123" | "fig12/group0" | plan name
    message: str
    severity: Optional[Severity] = None   # None: the rule's default

    @property
    def effective_severity(self) -> Severity:
        return self.severity_under(None)

    def severity_under(self, profile: Optional[str]) -> Severity:
        """Effective severity under a profile; an explicit per-finding
        severity (a downgrade a layer chose deliberately) always wins."""
        if self.severity is not None:
            return self.severity
        return severity_for(self.rule, profile)


def make_finding(rule_id: str, where: str, message: str,
                 severity: Optional[Severity] = None) -> Finding:
    if rule_id not in RULES:
        raise KeyError(f"unknown rule {rule_id!r}")
    return Finding(rule=rule_id, where=where, message=message,
                   severity=severity)


@dataclasses.dataclass
class AnalysisReport:
    """Findings from every layer plus the positive proofs per analyzed plan.

    ``profile`` selects the severity profile every aggregate view
    (``errors``/``warnings``/``ok``/``render``/``to_json``) resolves
    through; None keeps each rule's declared severity (== "bench").
    """

    findings: list[Finding] = dataclasses.field(default_factory=list)
    # plan/fixture name -> established facts, e.g. {"groups_predicted": 2,
    # "groups_traced": 2, "kernel_groups_proven": 1, "f64_ops": 0}
    proofs: dict = dataclasses.field(default_factory=dict)
    profile: Optional[str] = None

    def extend(self, findings) -> None:
        self.findings.extend(findings)

    def severity_of(self, f: Finding) -> Severity:
        return f.severity_under(self.profile)

    def errors(self) -> list[Finding]:
        return [f for f in self.findings if self.severity_of(f) == ERROR]

    def warnings(self) -> list[Finding]:
        return [f for f in self.findings if self.severity_of(f) == WARNING]

    def ok(self) -> bool:
        return not self.errors()

    def render(self, verbose: bool = False) -> str:
        lines = []
        shown = sorted(
            self.findings,
            key=lambda f: (_SEV_ORDER[self.severity_of(f)], f.rule, f.where))
        if not verbose:
            shown = [f for f in shown if self.severity_of(f) != INFO]
        for f in shown:
            lines.append(f"{self.severity_of(f).upper():7s} {f.rule:24s} "
                         f"{f.where}: {f.message}")
        for name in sorted(self.proofs):
            facts = self.proofs[name]
            body = ", ".join(f"{k}={v}" for k, v in facts.items())
            lines.append(f"PROOF   {name}: {body}")
        n_err, n_warn = len(self.errors()), len(self.warnings())
        n_info = len(self.findings) - n_err - n_warn
        prof = f" [profile={self.profile}]" if self.profile else ""
        lines.append(f"== {n_err} errors, {n_warn} warnings, {n_info} info; "
                     f"{'FAIL' if n_err else 'PASS'}{prof}")
        return "\n".join(lines)

    def to_json(self) -> dict:
        """JSON-serializable dump (the CI workflow-artifact surface)."""
        return {
            "profile": self.profile,
            "ok": self.ok(),
            "findings": [
                {"rule": f.rule, "where": f.where, "message": f.message,
                 "severity": self.severity_of(f)}
                for f in self.findings],
            "proofs": self.proofs,
            "counts": {"errors": len(self.errors()),
                       "warnings": len(self.warnings()),
                       "total": len(self.findings)},
        }
