"""R006 static cost model roll-up.

Per-eqn FLOPs/bytes (analysis/cost.py) aggregated into a per-graph summary plus hotspot
diagnostics, so every other rule's findings can be read against "what
actually costs something". A single eqn above ``hot_flops`` is flagged
for sharding/fusion review — on a multi-chip mesh that eqn is the one
worth a parallel.shard hint or a Pallas kernel.
"""

from ..diagnostics import Diagnostic, WARNING, INFO
from ..engine import Rule, register_rule
from ..cost import fmt_flops, fmt_bytes


@register_rule
class CostModelRule(Rule):
    name = "cost-model"
    id = "R006"
    doc = ("per-eqn FLOPs/bytes roll-up, top hotspots, dominant-cost "
           "eqns above the hot_flops threshold")

    def __init__(self, hot_flops=1e9, report_top=3):
        self.hot_flops = hot_flops
        self.report_top = report_top

    def check(self, a):
        costs = a.costs
        total_f = max(costs.total_flops, 1.0)
        yield Diagnostic(
            self.name, INFO,
            "static cost: %s, %s touched (arithmetic intensity %.1f "
            "FLOP/byte) over %d eqn(s)"
            % (fmt_flops(costs.total_flops),
               fmt_bytes(costs.total_bytes),
               costs.total_flops / max(costs.total_bytes, 1.0),
               sum(len(v.jaxpr.eqns) for v in a.views)))
        ranked = sorted(
            ((view, eqn) for view, eqn in a.iter_eqns()),
            key=lambda ve: costs.flops(ve[1]), reverse=True)
        for view, eqn in ranked[:self.report_top]:
            f = costs.flops(eqn)
            if f <= 0:
                break
            share = 100.0 * f / total_f
            if f >= self.hot_flops:
                yield Diagnostic(
                    self.name, WARNING,
                    "dominant-cost eqn: %s (%.0f%% of the graph's "
                    "FLOPs)" % (fmt_flops(f), share),
                    path=view.eqn_path(eqn), cost_flops=f,
                    hint="first candidate for a parallel.shard hint, "
                         "a Pallas kernel, or recompute exclusion")
            else:
                yield Diagnostic(
                    self.name, INFO,
                    "hotspot: %s (%.0f%% of FLOPs)"
                    % (fmt_flops(f), share),
                    path=view.eqn_path(eqn), cost_flops=f)
