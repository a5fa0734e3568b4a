"""HLO cost budgets (layer 5): pin every compile group's flop / byte /
memory / collective envelope against a schema-versioned baseline.

For each compile group `plan_lint` predicts, the group program is lowered
through the shared jit cache (`engine.lower_sweep` reuses the trace the IR
and kernel lints already paid for) and compiled once per session, then
XLA's `cost_analysis()` / `memory_analysis()` plus the roofline HLO-text
parser are folded into one envelope (`roofline.hlo.cost_envelope`).  The
envelope is compared leaf-by-leaf against `budgets.json` (committed next
to this module): a metric that drifts beyond its per-metric relative
tolerance raises ``budget/drift`` naming the plan, group signature and
metric, so a silent flop or HBM regression fails CI with an actionable
diff instead of a vague "slower".

Baseline discipline:

* the file records an *environment fingerprint* (REPRO_SMOKE/REPRO_FULL,
  jax version, kernel interpret mode).  jax is intentionally unpinned
  (pyproject: ``jax>=0.4.30``), and smoke mode changes n_ticks/K, so a
  mismatched environment downgrades every compare to one
  ``budget/env-mismatch`` warning rather than flagging phantom drift;
* groups present in the run but absent from the baseline raise
  ``budget/missing-baseline``; baseline groups no longer produced raise
  ``budget/stale-baseline`` — both WARNING by default, promoted to ERROR
  under the ci profile so the file can't rot;
* intentional cost changes re-record via
  ``python -m repro.analysis --ci --update-budgets`` (writes the file,
  never fails on drift).

Group identity is `experiment._group_signature` — the same string the
plan lint and benchmark health checks key on — qualified by the plan
label, so padded-group merges keep a stable identity across runs.
"""
from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Optional

from repro.analysis.findings import Finding, make_finding

__all__ = ["SCHEMA", "DEFAULT_PATH", "METRICS", "DEFAULT_TOLERANCES",
           "env_fingerprint", "measure_group", "check_envelope",
           "BudgetBook"]

SCHEMA = 1

# Committed next to the module so `python -m repro.analysis --ci` finds it
# from any cwd and the baseline travels with the code it describes.
DEFAULT_PATH = Path(__file__).with_name("budgets.json")

# The envelope leaves that get budget-checked, with per-metric relative
# tolerance: |new - base| <= tol * max(|base|, 1).
#   * flops/transcendentals are deterministic per program — tight;
#   * bytes_accessed includes XLA's fusion-dependent traffic model —
#     loose enough to absorb minor scheduling changes;
#   * argument/output bytes are exact interface contracts — zero;
#   * temp bytes swing with buffer assignment — loosest;
#   * collective bytes are an interface contract of the partitioner — zero.
DEFAULT_TOLERANCES = {
    "flops": 0.02,
    "transcendentals": 0.02,
    "bytes_accessed": 0.10,
    "argument_bytes": 0.0,
    "output_bytes": 0.0,
    "temp_bytes": 0.50,
    "peak_bytes": 0.25,
    "collective_bytes": 0.0,
}
METRICS = tuple(DEFAULT_TOLERANCES)


def env_fingerprint() -> dict:
    """What the recorded numbers depend on besides the code itself."""
    import jax

    from repro.kernels import ops

    return {
        "jax": jax.__version__,
        "repro_smoke": os.environ.get("REPRO_SMOKE", ""),
        "repro_full": os.environ.get("REPRO_FULL", ""),
        "kernel_interpret": ops.resolve_interpret(),
    }


def measure_group(cfg, sweep) -> dict:
    """Compile one group (via the shared jit/lowering cache) and return its
    cost envelope.  The `.compile()` is a real XLA run (~1 s/group on CPU);
    layer 5 is the only analysis layer that pays it."""
    from repro.netsim import engine
    from repro.roofline import hlo

    compiled = engine.lower_sweep(cfg, sweep).compile()
    return hlo.cost_envelope(compiled)


def check_envelope(base: dict, new: dict, tolerances: dict,
                   *, where: str) -> list[Finding]:
    """Leaf-level drift compare of one group's envelope vs its baseline."""
    findings = []
    for metric in METRICS:
        if metric not in base:
            continue                       # older baseline, fewer leaves
        tol = tolerances.get(metric, 0.0)
        b, n = float(base[metric]), float(new.get(metric, 0.0))
        if abs(n - b) > tol * max(abs(b), 1.0):
            pct = (n - b) / b * 100.0 if b else float("inf")
            findings.append(make_finding(
                "budget/drift", where,
                f"{metric}: measured {n:.6g} vs baseline {b:.6g} "
                f"({pct:+.1f}%, tolerance ±{tol * 100:.0f}%) — "
                f"re-record with --update-budgets if intentional"))
    return findings


@dataclasses.dataclass
class BudgetBook:
    """One analysis run's budget ledger: observe measured envelopes, then
    `finish()` into findings (check mode) or `save()` a new baseline
    (update mode)."""

    path: Path = DEFAULT_PATH
    update: bool = False
    tolerances: dict = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_TOLERANCES))

    def __post_init__(self):
        self.path = Path(self.path)
        self._measured: dict[str, dict[str, dict]] = {}   # plan -> sig -> env
        self._baseline: Optional[dict] = None
        self._load_error: Optional[str] = None
        if self.path.exists():
            try:
                data = json.loads(self.path.read_text())
                if data.get("schema") != SCHEMA:
                    self._load_error = (f"schema {data.get('schema')!r} "
                                        f"!= supported {SCHEMA}")
                else:
                    self._baseline = data
                    self.tolerances = dict(DEFAULT_TOLERANCES,
                                           **data.get("tolerances", {}))
            except (OSError, json.JSONDecodeError) as e:
                self._load_error = str(e)

    # -- recording --------------------------------------------------------

    def observe(self, plan: str, signature: str, envelope: dict) -> None:
        env = {m: envelope.get(m, 0.0) for m in METRICS}
        env["unknown_dtypes"] = list(envelope.get("unknown_dtypes", ()))
        self._measured.setdefault(plan, {})[signature] = env

    # -- check mode -------------------------------------------------------

    @property
    def env_matches(self) -> bool:
        if self._baseline is None:
            return False
        return self._baseline.get("env") == env_fingerprint()

    def finish(self) -> list[Finding]:
        """All budget findings for the observed run (check mode)."""
        findings: list[Finding] = []
        for plan, groups in self._measured.items():
            for sig, env in groups.items():
                for d in env.get("unknown_dtypes", ()):
                    findings.append(make_finding(
                        "budget/unknown-dtype", f"{plan} :: {sig}",
                        f"HLO collective result uses dtype {d!r} missing "
                        f"from roofline._DTYPE_BYTES (assumed 4 B/elem)"))
        if self._baseline is None:
            why = (f"cannot read {self.path} ({self._load_error})"
                   if self._load_error else f"{self.path} does not exist")
            findings.append(make_finding(
                "budget/missing-baseline", "budgets",
                f"no cost baseline: {why} — record one with "
                f"--update-budgets"))
            return findings
        if not self.env_matches:
            findings.append(make_finding(
                "budget/env-mismatch", "budgets",
                f"baseline recorded under {self._baseline.get('env')} but "
                f"running under {env_fingerprint()} — drift compares "
                f"skipped (re-record under the CI env to re-arm)"))
            return findings
        base_plans = self._baseline.get("plans", {})
        for plan, groups in self._measured.items():
            base_groups = {g["signature"]: g
                           for g in base_plans.get(plan, {}).get("groups", [])}
            for sig, env in groups.items():
                where = f"{plan} :: {sig}"
                if sig not in base_groups:
                    findings.append(make_finding(
                        "budget/missing-baseline", where,
                        "compile group has no recorded baseline — record "
                        "with --update-budgets"))
                    continue
                findings.extend(check_envelope(
                    base_groups[sig], env, self.tolerances, where=where))
            for sig in base_groups:
                if sig not in groups:
                    findings.append(make_finding(
                        "budget/stale-baseline", f"{plan} :: {sig}",
                        "baseline group no longer produced by this plan — "
                        "prune with --update-budgets"))
        return findings

    # -- update mode ------------------------------------------------------

    def save(self) -> Path:
        """Write the observed envelopes as the new baseline."""
        plans = {
            plan: {"groups": [
                dict(signature=sig,
                     **{m: env[m] for m in METRICS})
                for sig, env in groups.items()]}
            for plan, groups in sorted(self._measured.items())
        }
        data = {
            "schema": SCHEMA,
            "env": env_fingerprint(),
            "tolerances": self.tolerances,
            "plans": plans,
        }
        tmp = self.path.with_suffix(".json.tmp")
        tmp.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
        os.replace(tmp, self.path)
        return self.path

    # -- benchmark cross-check -------------------------------------------

    def baseline_for(self, plan: str, signature: str) -> Optional[dict]:
        """The recorded envelope of one group, or None (no baseline / env
        mismatch / unknown group)."""
        if self._baseline is None or not self.env_matches:
            return None
        for g in self._baseline.get("plans", {}).get(plan, {}) \
                               .get("groups", []):
            if g["signature"] == signature:
                return g
        return None

    def matches_any(self, signature: str, envelope: dict) -> Optional[bool]:
        """Cross-check a *measured* group profile against the prediction:
        does this envelope match (within tolerance) any recorded group
        with the same structural signature?  Benchmark plan labels differ
        from the analysis registry's, so candidates come from every
        recorded plan, keyed on the `_group_signature` tail of the stored
        ``"group<i>|<signature>"`` id.  Returns None when no baseline, the
        env mismatches, or no candidate shares the signature —
        `benchmarks.common` counts only a definite False as a mismatch."""
        if self._baseline is None or not self.env_matches:
            return None
        candidates = [
            g for plan in self._baseline.get("plans", {}).values()
            for g in plan.get("groups", [])
            if g["signature"].split("|", 1)[-1] == signature]
        if not candidates:
            return None
        return any(not check_envelope(g, envelope, self.tolerances,
                                      where="") for g in candidates)
