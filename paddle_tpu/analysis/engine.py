"""Jaxpr tracing + pluggable rule engine.

``check_program(fn, *args)`` stages ``fn`` to a jaxpr with
``jax.make_jaxpr`` (abstract — no device memory, no execution beyond
trace time) and runs every registered rule over the flattened graph.
This is the TPU-era analog of the reference's ProgramDesc validation
(operator attr checkers at InferShape time): catch dtype leaks,
recompilation hazards and numerically risky patterns before a graph
ever burns accelerator time.

Rules are pluggable: subclass ``Rule``, decorate with
``@register_rule``, and the CLI / CI gate pick it up. Each rule walks
an ``Analysis`` — the closed jaxpr plus per-subjaxpr ``GraphView``s
(producer/consumer maps), arg labels from the example-arg pytree, and a
lazily built static cost table.
"""

import numpy as np
import jax
from jax.extend.core import Jaxpr, ClosedJaxpr, Var, Literal
from jax.tree_util import tree_flatten_with_path, keystr

from .diagnostics import Diagnostic, Report, severity_rank

__all__ = ["Analysis", "GraphView", "Rule", "register_rule",
           "default_rules", "check_program", "sub_jaxprs",
           "Diagnostic", "Report"]


def sub_jaxprs(eqn):
    """Yield (param_name, Jaxpr) for every jaxpr nested in an eqn's
    params — scan/while bodies, cond branches, jit/shard_map/custom_*
    calls — whatever the primitive calls them."""
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield name, item.jaxpr
            elif isinstance(item, Jaxpr):
                yield name, item


def _eqn_weight(eqn):
    """Trip-count multiplier for costs inside this eqn's subjaxprs."""
    if eqn.primitive.name == "scan":
        return max(1, int(eqn.params.get("length", 1) or 1))
    return 1


class GraphView:
    """One jaxpr level: producer/consumer maps + a display path."""

    def __init__(self, jaxpr, path="", depth=0, weight=1.0,
                 parent=None):
        self.jaxpr = jaxpr
        self.path = path
        self.depth = depth
        self.weight = weight     # product of enclosing loop trip counts
        self.parent = parent     # (calling eqn, parent GraphView) | None
        self.producers = {}      # Var -> eqn that outputs it
        self.consumers = {}      # Var -> [eqns reading it]
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if isinstance(v, Var):
                    self.producers[v] = eqn
            for v in eqn.invars:
                if isinstance(v, Var):
                    self.consumers.setdefault(v, []).append(eqn)

    def producer(self, var):
        """Producing eqn, or None (invar / constvar / literal)."""
        if isinstance(var, Literal):
            return None
        return self.producers.get(var)

    def eqn_path(self, eqn):
        """Human path of an eqn: nesting path + named-scope stack +
        primitive name. The executor scopes every Program op as
        ``<op_type>.<seq>``, so this points back at the source op."""
        parts = [self.path] if self.path else []
        ns = str(eqn.source_info.name_stack)
        if ns:
            parts.append(ns)
        parts.append(eqn.primitive.name)
        return "/".join(parts)


class Analysis:
    """Everything a rule may inspect for one traced program."""

    def __init__(self, fn, example_args, name=""):
        self.name = name or getattr(fn, "__name__", "<fn>")
        self.example_args = example_args
        self.closed_jaxpr = jax.make_jaxpr(fn)(*example_args)
        self.views = []
        self._eqn_subviews = {}   # id(eqn) -> [GraphView of its jaxprs]
        self._collect(self.closed_jaxpr.jaxpr, "", 0, 1.0, None)
        self.root = self.views[0]
        # label root invars by their position in the example-arg pytree
        leaves, _ = tree_flatten_with_path(example_args)
        self.arg_labels = {}
        invars = self.closed_jaxpr.jaxpr.invars
        for (path, _), var in zip(leaves, invars):
            self.arg_labels[var] = "args" + keystr(path)
        self._costs = None

    def _collect(self, jaxpr, path, depth, weight, parent):
        if depth > 32:   # defensive: malformed recursive graphs
            return
        view = GraphView(jaxpr, path, depth, weight, parent)
        self.views.append(view)
        for i, eqn in enumerate(jaxpr.eqns):
            w = weight * _eqn_weight(eqn)
            sub_path_base = "%s[%d]" % (eqn.primitive.name, i)
            sub_path = "/".join([p for p in (path, sub_path_base) if p])
            subs = []
            for _, sub in sub_jaxprs(eqn):
                idx = len(self.views)
                self._collect(sub, sub_path, depth + 1, w, (eqn, view))
                if len(self.views) > idx:
                    subs.append(self.views[idx])
            if subs:
                self._eqn_subviews[id(eqn)] = subs

    # -- iteration helpers ------------------------------------------------
    def iter_eqns(self):
        for view in self.views:
            for eqn in view.jaxpr.eqns:
                yield view, eqn

    def label(self, var):
        return self.arg_labels.get(var, str(var))

    @property
    def costs(self):
        if self._costs is None:
            from .cost import CostTable
            self._costs = CostTable(self)
        return self._costs

    # -- dataflow helpers shared by rules ---------------------------------
    TRANSPARENT = frozenset({
        "broadcast_in_dim", "reshape", "transpose", "squeeze",
        "expand_dims", "convert_element_type", "copy", "slice",
        "stop_gradient", "rev"})

    # call-like eqns whose operands/results map 1:1 onto the inner
    # jaxpr's invars/outvars — the resolver walks through them (jnp
    # ufuncs, custom_jvp bodies etc. show up as jit wrappers)
    CALL_PRIMS = frozenset({
        "jit", "closed_call", "core_call", "custom_jvp_call",
        "custom_vjp_call", "remat2", "custom_lin"})

    def resolve_producer(self, view, var):
        """Walk back to the eqn that actually computes ``var``: through
        shape/dtype-only eqns, into call-like bodies (jit/custom_*),
        and back out through their invars. Returns (view, eqn) — eqn is
        None when the value is a program input / constant / literal."""
        for _ in range(256):
            if isinstance(var, Literal):
                return view, None
            eqn = view.producer(var)
            if eqn is None:
                # invar/constvar: map a call body's invar back onto the
                # calling eqn's operand and continue in the parent
                if view.parent is None:
                    return view, None
                call_eqn, pview = view.parent
                invars = list(view.jaxpr.invars)
                if call_eqn.primitive.name in self.CALL_PRIMS \
                        and var in invars:
                    idx = invars.index(var)
                    # call operands align to body invars from the END
                    # (leading operands may be hoisted consts)
                    off = len(call_eqn.invars) - len(invars)
                    if 0 <= idx + off < len(call_eqn.invars):
                        view, var = pview, call_eqn.invars[idx + off]
                        continue
                return view, None
            prim = eqn.primitive.name
            if prim in self.TRANSPARENT:
                var = eqn.invars[0]
                continue
            if prim in self.CALL_PRIMS:
                subs = self._eqn_subviews.get(id(eqn))
                if subs:
                    sub = subs[0]
                    try:
                        i = list(eqn.outvars).index(var)
                    except ValueError:
                        return view, eqn
                    out_v = sub.jaxpr.outvars[i] \
                        if i < len(sub.jaxpr.outvars) else None
                    if isinstance(out_v, Var):
                        view, var = sub, out_v
                        continue
                return view, eqn
            return view, eqn
        return view, eqn

    def real_producer(self, view, var):
        """Producing eqn only (see resolve_producer)."""
        return self.resolve_producer(view, var)[1]


class Rule:
    """Base class for lint rules. Subclass, set ``name``/``id``/``doc``,
    implement ``check(analysis) -> iterable[Diagnostic]``, and register
    with ``@register_rule``. Constructor kwargs are the rule's knobs, so
    callers can pass re-tuned instances to ``check_program``."""

    name = "base"
    id = "R000"
    doc = ""
    max_reports = 20      # per-rule cap so one bad graph stays readable

    def check(self, analysis):
        raise NotImplementedError

    def run(self, analysis):
        seen = {}    # (severity, path, message) -> Diagnostic (dedupe)
        dupes = {}
        for d in self.check(analysis):
            key = (d.severity, d.path, d.message)
            if key in seen:
                dupes[key] = dupes.get(key, 1) + 1
                continue
            seen[key] = d
        for key, n in dupes.items():
            seen[key].message += " (x%d identical sites)" % n
        # cap per rule, most severe FIRST: an error yielded after 20
        # warnings must never be suppressed — the CI gate keys on it
        ranked = sorted(seen.values(),
                        key=lambda d: -severity_rank(d.severity))
        out, cut = ranked[:self.max_reports], ranked[self.max_reports:]
        if cut:
            out.append(Diagnostic(
                self.name, max((d.severity for d in cut),
                               key=severity_rank),
                "... %d more %s finding(s) suppressed"
                % (len(cut), self.name),
                model=analysis.name))
        for d in out:
            if not d.model:
                d.model = analysis.name
        return out


_RULES = {}     # name -> Rule subclass


def register_rule(cls):
    """Class decorator: add a Rule to the global registry."""
    if not issubclass(cls, Rule):
        raise TypeError("register_rule expects a Rule subclass")
    if cls.name in _RULES and _RULES[cls.name] is not cls:
        raise ValueError("duplicate rule name %r" % cls.name)
    _RULES[cls.name] = cls
    return cls


def registered_rules():
    from . import rules as _builtin  # noqa: F401  (populate registry)
    return dict(_RULES)


def default_rules():
    return [cls() for _, cls in sorted(registered_rules().items(),
                                       key=lambda kv: kv[1].id)]


def resolve_rules(rules):
    """None -> all defaults; strings resolve through the registry;
    Rule instances pass through."""
    if rules is None:
        return default_rules()
    reg = registered_rules()
    out = []
    for r in rules:
        if isinstance(r, Rule):
            out.append(r)
        elif isinstance(r, str):
            if r not in reg:
                raise KeyError("unknown rule %r (have: %s)"
                               % (r, ", ".join(sorted(reg))))
            out.append(reg[r]())
        elif isinstance(r, type) and issubclass(r, Rule):
            out.append(r())
        else:
            raise TypeError("bad rule spec %r" % (r,))
    return out


def check_program(fn, *args, **kwargs):
    """Trace ``fn(*args)`` to a jaxpr and run the lint rules over it.

    kwargs: ``rules`` (list of names / Rule instances; default all),
    ``name`` (model label on diagnostics). Returns a ``Report``.
    Runs fully device-free: tracing is abstract, so this works under
    ``JAX_PLATFORMS=cpu`` with no accelerator attached.
    """
    rules = resolve_rules(kwargs.pop("rules", None))
    name = kwargs.pop("name", "")
    if kwargs:
        raise TypeError("unexpected kwargs %r" % sorted(kwargs))
    analysis = Analysis(fn, args, name=name)
    report = Report(model=analysis.name)
    for rule in rules:
        report.extend(rule.run(analysis))
    return report


def aval_nbytes(aval):
    try:
        return float(np.prod(aval.shape, dtype=np.float64)
                     * np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0.0
