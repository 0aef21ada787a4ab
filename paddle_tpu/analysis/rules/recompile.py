"""R002 recompilation-hazard detector.

The jit cache fragments on signature changes the caller never meant to
vary: weak-typed Python scalars (dtype follows the *value* context),
large arrays captured by closure (baked as jaxpr consts — re-traced per
object identity), and scalar floods (hundreds of 0-d args instead of
one stacked array). All three are visible in the traced signature
without running anything — the static analog of watching
jax.monitoring recompile counters in production.

Megastep awareness (ISSUE 7): a ``lax.scan`` body — the shape of
gradient accumulation, Executor.run_steps megasteps and the serving
engine's fused-K decode — is ONE compile unit whose trip count K is a
static trace constant. The rule surfaces each scanned unit with its K
so readers know a varying K (a K-sweep driven per run, a serving
engine rebuilt at a new ``serving_megastep``) recompiles the WHOLE
fused body, not just a wrapper.
"""

from ..diagnostics import Diagnostic, WARNING, INFO
from ..engine import Rule, register_rule, aval_nbytes


@register_rule
class RecompileHazardRule(Rule):
    name = "recompile-hazard"
    id = "R002"
    doc = ("weak-typed scalar args, large closure-captured constants, "
           "and 0-d argument floods that fragment the jit cache")

    def __init__(self, const_min_bytes=1 << 20, scalar_flood=32):
        self.const_min_bytes = const_min_bytes
        self.scalar_flood = scalar_flood

    def _check_fused_scopes(self, a):
        """Fused-op awareness (ISSUE 15): the transform tier's pattern
        fusion rewrites op chains into single ops whose lowerings run
        under ONE ``<fused_type>.<seq>`` named scope — when this rule
        reports an op path inside such a scope, the reader should
        attribute it to the fusion tier's output, not a mystery op.
        One INFO summarizes the fused scopes present."""
        from ...ops.fused import FUSED_OP_TYPES
        scopes = {}
        for view, eqn in a.iter_eqns():
            ns = str(eqn.source_info.name_stack)
            for part in ns.split("/"):
                base = part.rsplit(".", 1)[0]
                if base in FUSED_OP_TYPES:
                    scopes.setdefault(base, set()).add(part)
        if not scopes:
            return
        yield Diagnostic(
            self.name, INFO,
            "%d fused-op scope(s) from transform.fusion (%s) — each "
            "is ONE op-path/compile unit; op paths under them "
            "attribute to the fusion tier's rewrite, and their "
            "component chain can no longer fragment individually"
            % (sum(len(v) for v in scopes.values()),
               ", ".join("%s x%d" % (t, len(v))
                         for t, v in sorted(scopes.items()))))

    def _check_scanned_units(self, a):
        """Each lax.scan body is one compile unit keyed on its trip
        count K: megastep execution (Executor.run_steps, the serving
        engine's fused-K decode) and gradient accumulation both compile
        the WHOLE step body per distinct K, so a K that varies run to
        run is a recompile hazard worth flagging — the fused body is
        the most expensive trace in the program, not a thin wrapper."""
        for view, eqn in a.iter_eqns():
            if eqn.primitive.name != "scan":
                continue
            k = int(eqn.params.get("length", 1) or 1)
            if k < 2:
                continue
            yield Diagnostic(
                self.name, INFO,
                "scanned compile unit (K=%d trips) at %s — the body "
                "(megastep / grad-accum / fused decode) is ONE compile "
                "unit keyed on K: a K that varies across runs re-traces"
                " and recompiles the whole fused body"
                % (k, view.eqn_path(eqn)),
                hint="pin K per workload (flags serving_megastep / "
                     "run_steps k) instead of deriving it per batch")

    def check(self, a):
        jaxpr = a.closed_jaxpr.jaxpr
        n_scalar = 0
        for var in jaxpr.invars:
            aval = getattr(var, "aval", None)
            if aval is None:
                continue
            if getattr(aval, "weak_type", False):
                yield Diagnostic(
                    self.name, WARNING,
                    "weak-typed scalar argument %s — a bare Python "
                    "number; its dtype re-resolves per call context "
                    "and mixed uses split the jit cache"
                    % a.label(var),
                    hint="wrap with np.asarray(x, dtype) or jnp.* "
                         "so the signature dtype is pinned")
            if getattr(aval, "shape", None) == ():
                n_scalar += 1
        if n_scalar >= self.scalar_flood:
            yield Diagnostic(
                self.name, WARNING,
                "%d scalar (0-d) arguments in the jit signature — "
                "every distinct combination is a fresh cache entry "
                "and argument-handling overhead grows linearly"
                % n_scalar,
                hint="stack related scalars into one array argument")
        for const in a.closed_jaxpr.consts:
            nb = aval_nbytes(const)     # anything with shape + dtype
            if nb >= self.const_min_bytes:
                shape = getattr(const, "shape", ())
                yield Diagnostic(
                    self.name, WARNING,
                    "large constant baked into the graph (%s, %.1f "
                    "MiB) — captured by closure, so a new object "
                    "identity means a full re-trace and re-transfer"
                    % (list(shape), nb / (1 << 20)),
                    hint="pass it as a function argument (donated "
                         "state) instead of closing over it")
        for d in self._check_scanned_units(a):
            yield d
        for d in self._check_fused_scopes(a):
            yield d
        # informational: how much of the signature is traced state
        yield Diagnostic(
            self.name, INFO,
            "jit signature: %d args (%d scalar), %d baked consts"
            % (len(jaxpr.invars), n_scalar,
               len(a.closed_jaxpr.consts)))
