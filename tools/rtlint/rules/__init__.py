"""Rule catalog for rtlint v3.

One module per concern; every rule subclasses :class:`Rule` from
``rules.base`` and is instantiated exactly once here, in id order.
``ALL_RULES`` is the engine's default rule set and the catalog printed
by ``--list-rules``; adding a rule means adding its instance here and a
section to RULES.md.
"""

from __future__ import annotations

from typing import List

from tools.rtlint.rules.base import (  # noqa: F401  (re-export for rules)
    Rule,
    _dotted,
    _is_jit_expr,
    _jit_call_sites,
    _traced_bodies,
)
from tools.rtlint.rules.jit import (
    DonatedReuseRule,
    HostSyncRule,
    RetraceRule,
)
from tools.rtlint.rules.blocking import ActorBlockingRule, AsyncBlockingRule
from tools.rtlint.rules.refs import RefLeakRule
from tools.rtlint.rules.collective import CollectiveFenceRule
from tools.rtlint.rules.threads import LockDisciplineRule, ThreadRaceRule
from tools.rtlint.rules.exceptions import SwallowRule
from tools.rtlint.rules.deadline import DeadlineTaintRule
from tools.rtlint.rules.clocks import ClockDomainRule
from tools.rtlint.rules.metrics import MetricsDisciplineRule
from tools.rtlint.rules.lifecycle import (
    BundleLifecycleRule,
    PageLifecycleRule,
    RefLockLifecycleRule,
)
from tools.rtlint.rules.protocol import ProtocolConformanceRule

ALL_RULES: List[Rule] = [
    HostSyncRule(),          # RT001
    RetraceRule(),           # RT002
    ActorBlockingRule(),     # RT003
    RefLeakRule(),           # RT004
    CollectiveFenceRule(),   # RT005
    ThreadRaceRule(),        # RT006
    SwallowRule(),           # RT007
    AsyncBlockingRule(),     # RT008
    DeadlineTaintRule(),     # RT009
    LockDisciplineRule(),    # RT010
    ClockDomainRule(),       # RT011
    DonatedReuseRule(),      # RT012
    MetricsDisciplineRule(),  # RT013
    PageLifecycleRule(),     # RT014
    BundleLifecycleRule(),   # RT015
    RefLockLifecycleRule(),  # RT016
    ProtocolConformanceRule(),  # RT017
]


def rule_by_id(rule_id: str) -> Rule:
    for r in ALL_RULES:
        if r.id == rule_id.upper():
            return r
    raise KeyError(rule_id)
