"""raymc check driver: DFS over schedules with sleep-set pruning.

``check(scenario_factory)`` owns the exploration loop: run one
execution, harvest backtrack points from every decision whose enabled
set had unchosen alternatives, push them (with sleep sets), pop and
replay until the stack drains or a budget trips. See explorer.py for
the execution machinery and the exhaustiveness contract.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

from tools.raymc.explorer import (Decision, Execution, ExecutionResult,
                                  ExplorerConfig)
from tools.raymc.minimize import _prop_names, build_counterexample
from tools.raymc.props import Finding
from tools.raymc.scenario import Scenario


@dataclasses.dataclass
class CheckResult:
    scenario: str
    executions: int = 0
    steps_total: int = 0
    pruned: int = 0
    truncated: int = 0
    divergences: int = 0
    exhausted: bool = False
    elapsed_s: float = 0.0
    conformance_checks: int = 0
    findings: List[Finding] = dataclasses.field(default_factory=list)
    # Union of every point name any execution crossed (pre-filter) —
    # the seam-coverage audit diffs this against the full catalog.
    points_crossed: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "scenario": self.scenario,
            "executions": self.executions,
            "steps_total": self.steps_total,
            "pruned": self.pruned,
            "truncated": self.truncated,
            "divergences": self.divergences,
            "exhausted": self.exhausted,
            "elapsed_s": round(self.elapsed_s, 3),
            "conformance_checks": self.conformance_checks,
            "findings": [f.to_dict() for f in self.findings],
            "points_crossed": list(self.points_crossed),
        }

    @classmethod
    def from_dict(cls, data: dict) -> "CheckResult":
        fields = {k: v for k, v in data.items() if k != "findings"}
        return cls(findings=[Finding.from_dict(f)
                             for f in data.get("findings", [])],
                   **fields)


def _independent(scn: Scenario, a: Decision, b: Decision) -> bool:
    """Independence is the scenario's call (see
    ``Scenario.independent``); a relation that lies loses soundness,
    so any doubt must answer "dependent"."""
    try:
        return bool(scn.independent(a, b))
    except Exception:
        return False


# How often a prefix is replayed before its divergence is believed.
_REPLAY_TRIES = 3


def check(scenario_factory: Callable[[], Scenario],
          cfg: Optional[ExplorerConfig] = None) -> CheckResult:
    cfg = cfg or ExplorerConfig()
    probe = scenario_factory()
    result = CheckResult(scenario=probe.name)
    # A scenario may declare the budget its exhaustive sweep needs
    # (scenario.max_schedules); the wall-clock budget still binds.
    schedule_cap = max(cfg.max_schedules,
                       getattr(probe, "max_schedules", 0) or 0)
    t0 = time.monotonic()
    deadline = t0 + cfg.time_budget_s

    # (prefix, sleep set at the state the prefix reaches)
    stack: List[Tuple[List[Decision], frozenset]] = [([], frozenset())]
    budget_hit = False

    while stack:
        if result.executions >= schedule_cap \
                or time.monotonic() > deadline:
            budget_hit = True
            break
        prefix, sleep = stack.pop()
        # A replay diverges when a thread of the prefix does not reach
        # its crossing in the explorer's wall-clock window: on a loaded
        # host that is the host's doing, and the same prefix replays
        # faithfully the next time. Only a prefix that diverges every
        # time is counted (and costs the sweep its exhaustiveness); the
        # counts are of the runs that stood.
        for _ in range(_REPLAY_TRIES):
            scn = scenario_factory()
            res = Execution(scn, list(prefix), cfg, sleep=sleep).run()
            if res.status != "divergence":
                break
        result.executions += 1
        result.steps_total += len(res.steps)
        result.pruned += res.sleep_leaves
        result.conformance_checks += res.conformance_checks
        result.points_crossed = sorted(
            set(result.points_crossed) | set(res.points_seen))
        if res.truncated:
            result.truncated += 1
        if res.status == "divergence":
            result.divergences += 1
            continue
        if res.status == "timeout":
            result.findings.append(Finding(
                scenario=scn.name, prop="execution-timeout",
                kind="deadlock",
                message=("an explored schedule wedged past the "
                         f"{cfg.exec_timeout_s:.0f}s execution bound; "
                         f"errors: {res.errors}")))
            if cfg.stop_on_first:
                break
            continue
        if res.status in ("violation", "deadlock") or res.errors:
            result.findings.extend(
                _findings_for(scenario_factory, cfg, prefix, res, scn))
            if cfg.stop_on_first:
                break
            continue

        _push_alternatives(stack, scn, cfg, prefix, sleep, res, result)

    result.elapsed_s = time.monotonic() - t0
    # Exhaustive = the DFS tree was fully drained with every execution
    # run to completion under full control and replayed faithfully.
    result.exhausted = (not stack and not budget_hit
                        and result.truncated == 0
                        and result.divergences == 0
                        and not result.findings)
    return result


def _push_alternatives(stack, scn: Scenario, cfg: ExplorerConfig,
                       prefix: List[Decision], sleep: frozenset,
                       res: ExecutionResult, result: CheckResult) -> None:
    """Backtrack points from one clean execution. Alternatives are
    pushed shallow-first so the LIFO stack explores deep branches (the
    chosen transition's subtree) before a sibling — the order sleep-set
    soundness assumes."""
    decisions = [s.chosen for s in res.steps]
    # `sleep` is the sleep set AT THE STATE THE PREFIX REACHES (it was
    # computed against the prefix's own last decision at push time) —
    # updating starts where the prefix ends.
    live = set(sleep)
    for i, step in enumerate(res.steps):
        if i < len(prefix):
            continue
        explored = [step.chosen]
        for alt in step.enabled:
            if alt == step.chosen:
                continue
            if cfg.dpor and alt in live:
                result.pruned += 1
                continue
            # Godefroid sleep sets: the child's sleep is everything
            # already explored from this state (plus the inherited
            # sleep) that commutes with the alternative being taken.
            child_sleep = frozenset(
                t for t in (set(live) | set(explored))
                if _independent(scn, t, alt))
            stack.append((decisions[:i] + [alt], child_sleep))
            explored.append(alt)
        live = {t for t in live if _independent(scn, t, step.chosen)}


def _findings_for(scenario_factory, cfg, prefix, res: ExecutionResult,
                  scn: Scenario) -> List[Finding]:
    decisions = [s.chosen for s in res.steps]
    out: List[Finding] = []
    if res.status == "deadlock":
        targets = {"deadlock"}
        ce = build_counterexample(scenario_factory, cfg, decisions,
                                  res, targets)
        out.append(Finding(
            scenario=scn.name, prop="no-deadlock", kind="deadlock",
            message=("explored schedule reached a state where no "
                     "thread could proceed"),
            counterexample=ce))
        return out
    if res.violations:
        targets = _prop_names(res.violations)
        ce = build_counterexample(scenario_factory, cfg, decisions,
                                  res, targets)
        kind = "invariant"
        for v in res.violations:
            prop = v.split(":", 1)[0]
            for live in scn.liveness():
                if live.name == prop:
                    kind = "liveness"
            out.append(Finding(
                scenario=scn.name, prop=prop, kind=kind,
                message=v.split(":", 1)[1].strip() if ":" in v else v,
                counterexample=ce))
    for err in res.errors:
        out.append(Finding(
            scenario=scn.name, prop="no-unhandled-exception",
            kind="exception", message=err,
            counterexample=None))
    return out
