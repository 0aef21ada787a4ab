"""R003 sharding / transfer audit.

Walks shard_map and collective eqns (the lowering targets of
paddle_tpu/parallel/: psum from the Megatron tp hints, all_gather from
c_allgather, all_to_all from the MoE dispatch) and flags the patterns
that silently eat ICI/HBM bandwidth: large fully-replicated operands
entering a shard_map, implicit all-gathers, and host<->device transfers
inside the step.
"""

from ..diagnostics import Diagnostic, WARNING, INFO
from ..engine import Rule, register_rule, aval_nbytes
from ..cost import fmt_bytes

_COLLECTIVES = {"psum", "all_gather", "all_to_all", "ppermute",
                "psum_scatter", "pmax", "pmin", "all_gather_invariant"}


def _axis_names(eqn):
    ax = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(ax, (tuple, list)):
        return ",".join(str(x) for x in ax)
    return str(ax)


@register_rule
class ShardingTransferRule(Rule):
    name = "sharding-transfer"
    id = "R003"
    doc = ("replicated large shard_map operands, implicit all-gathers, "
           "host<->device transfers, collective roll-up")

    def __init__(self, replicated_min_bytes=1 << 20,
                 gather_warn_bytes=1 << 20):
        self.replicated_min_bytes = replicated_min_bytes
        self.gather_warn_bytes = gather_warn_bytes

    def check(self, a):
        n_coll = 0
        coll_bytes = 0.0
        for view, eqn in a.iter_eqns():
            prim = eqn.primitive.name
            if prim == "device_put":
                src = eqn.invars[0] if eqn.invars else None
                # placement of a trace-time constant (assign_value /
                # prior tables) happens once at compile, not per step
                if src is None or not hasattr(src, "aval") \
                        or src in view.jaxpr.constvars:
                    continue
                yield Diagnostic(
                    self.name, WARNING,
                    "device_put inside the traced step — a host<->"
                    "device transfer (or forced placement) on the hot "
                    "path",
                    path=view.eqn_path(eqn),
                    hint="move placement outside the step; let the "
                         "executor's donated state carry buffers")
                continue
            if prim == "shard_map":
                for var, spec in zip(eqn.invars, eqn.params["in_specs"]):
                    aval = getattr(var, "aval", None)
                    if aval is None:
                        continue
                    nb = aval_nbytes(aval)
                    if not any(spec) and nb >= self.replicated_min_bytes:
                        yield Diagnostic(
                            self.name, WARNING,
                            "fully-replicated operand (%s, %s) enters "
                            "shard_map over mesh %s — every device "
                            "holds a full copy"
                            % (list(aval.shape), fmt_bytes(nb),
                               getattr(eqn.params.get("mesh"),
                                       "shape", "?")),
                            path=view.eqn_path(eqn),
                            hint="shard the param dim over a mesh "
                                 "axis (parallel.shard hint) or mark "
                                 "it intentionally replicated")
                continue
            if prim in _COLLECTIVES:
                n_coll += 1
                out_nb = sum(aval_nbytes(v.aval) for v in eqn.outvars
                             if hasattr(v, "aval")) * view.weight
                coll_bytes += out_nb
                if prim == "all_gather":
                    sev = WARNING if out_nb >= self.gather_warn_bytes \
                        else INFO
                    yield Diagnostic(
                        self.name, sev,
                        "all_gather over axis %s materializes %s per "
                        "device" % (_axis_names(eqn),
                                    fmt_bytes(out_nb)),
                        path=view.eqn_path(eqn),
                        hint="prefer keeping the value sharded "
                             "(psum_scatter / ring schedules) if the "
                             "consumer can work on shards")
                elif prim in ("all_to_all", "ppermute"):
                    yield Diagnostic(
                        self.name, INFO,
                        "%s over axis %s moves %s"
                        % (prim, _axis_names(eqn), fmt_bytes(out_nb)),
                        path=view.eqn_path(eqn))
        if n_coll:
            yield Diagnostic(
                self.name, INFO,
                "collective roll-up: %d collective eqn(s), ~%s of "
                "outputs crossing the mesh per step"
                % (n_coll, fmt_bytes(coll_bytes)))
