"""Golden-diagnostic tests for paddle_tpu.analysis: one deliberately
broken toy fixture per rule (each must FAIL the lint), clean fixtures
that must pass, and the engine/CLI plumbing."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import analysis


def _hits(report, rule, severity=None):
    return [d for d in report
            if d.rule == rule and (severity is None
                                   or d.severity == severity)]


# ---------------------------------------------------------------- R001
def test_dtype_rule_flags_fp16_creep():
    def f(x):
        return x * 2.0

    rep = analysis.check_program(f, np.zeros((8, 8), np.float16))
    assert _hits(rep, "dtype-promotion", analysis.ERROR)


def test_dtype_rule_flags_bf16_softmax_normalizer():
    def f(x):
        e = jnp.exp(x)                     # bf16 exp -> bf16 sum
        return e / jnp.sum(e, -1, keepdims=True)

    rep = analysis.check_program(f, jnp.zeros((8, 128), jnp.bfloat16))
    assert _hits(rep, "dtype-promotion", analysis.ERROR)


def test_dtype_rule_flags_pointless_upcast():
    def f(x):
        y = x.astype(jnp.float32)          # feeds only elementwise ops
        return y * 2.0 + 1.0

    rep = analysis.check_program(f, jnp.zeros((64, 128), jnp.bfloat16))
    assert _hits(rep, "dtype-promotion", analysis.WARNING)


def test_dtype_rule_clean_on_f32_softmax_over_bf16():
    def f(x):
        return jax.nn.softmax(x.astype(jnp.float32), axis=-1)

    rep = analysis.check_program(f, jnp.zeros((8, 128), jnp.bfloat16))
    assert not _hits(rep, "dtype-promotion")


# ---------------------------------------------------------------- R002
def test_recompile_rule_flags_weak_scalar_arg():
    def f(x, scale):
        return x * scale

    rep = analysis.check_program(f, np.zeros((4, 4), np.float32), 3.0)
    found = _hits(rep, "recompile-hazard", analysis.WARNING)
    assert any("weak" in d.message for d in found)


def test_recompile_rule_flags_baked_constant():
    table = np.zeros((1 << 19,), np.float32)        # 2 MiB closure

    def f(idx):
        return jnp.take(jnp.asarray(table), idx)

    rep = analysis.check_program(f, np.zeros((4,), np.int32))
    found = _hits(rep, "recompile-hazard", analysis.WARNING)
    assert any("constant" in d.message for d in found)


# ---------------------------------------------------------------- R003
def test_sharding_rule_flags_replicated_param_and_all_gather():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def body(x, w):
        return jax.lax.psum(x @ w, "dp"), \
            jax.lax.all_gather(x, "dp", tiled=True)

    f = shard_map(body, mesh=mesh,
                  in_specs=(P("dp", None), P(None, None)),
                  out_specs=(P("dp", None), P(None, None)),
                  check_vma=False)
    rep = analysis.check_program(
        f, np.zeros((1024, 512), np.float32),          # 2 MiB act
        np.zeros((512, 512), np.float32))              # 1 MiB param
    found = _hits(rep, "sharding-transfer", analysis.WARNING)
    assert any("replicated" in d.message for d in found)
    assert any("all_gather" in d.message for d in found)


def test_sharding_rule_flags_device_put_of_traced_value():
    def f(x):
        return jax.device_put(x) + 1.0

    rep = analysis.check_program(f, np.zeros((8,), np.float32))
    assert _hits(rep, "sharding-transfer", analysis.WARNING)


# ---------------------------------------------------------------- R004
def test_numerics_rule_flags_unguarded_log_div_rsqrt():
    def f(x, y):
        return (jnp.log(x * y),            # log of a product, no eps
                x / (x * y),               # unguarded denominator
                jax.lax.rsqrt(x * y))      # unguarded rsqrt

    rep = analysis.check_program(f, np.ones((8,), np.float32),
                                 np.ones((8,), np.float32))
    msgs = [d.message for d in _hits(rep, "numerical-risk",
                                     analysis.WARNING)]
    assert any("log" in m for m in msgs)
    assert any("division" in m for m in msgs)
    assert any("rsqrt" in m for m in msgs)


def test_numerics_rule_flags_unshifted_softmax():
    def f(x):
        e = jnp.exp(x)                     # no max-subtraction
        return e / jnp.sum(e, -1, keepdims=True)

    rep = analysis.check_program(f, np.zeros((4, 16), np.float32))
    found = _hits(rep, "numerical-risk", analysis.WARNING)
    assert any("max-subtraction" in d.message for d in found)


def test_numerics_rule_sqrt_guard_depends_on_operand():
    """sqrt preserves zero: x/sqrt(var) is flagged, x/sqrt(var+eps)
    (the batch_norm denominator) is not."""
    def bad(x):
        var = jnp.sum((x - jnp.mean(x)) ** 2)
        return x / jnp.sqrt(var)

    def good(x):
        var = jnp.sum((x - jnp.mean(x)) ** 2)
        return x / jnp.sqrt(var + 1e-5)

    arg = np.ones((8,), np.float32)
    assert _hits(analysis.check_program(bad, arg), "numerical-risk")
    assert not _hits(analysis.check_program(good, arg),
                     "numerical-risk")


def test_numerics_rule_clean_on_guarded_idioms():
    def f(x, mask):
        a = jnp.log(jnp.clip(x, 1e-20))
        b = x / jnp.maximum(jnp.sum(mask), 1.0)
        c = jax.lax.rsqrt(jnp.var(x) + 1e-5)
        d = jax.nn.softmax(x)
        e = jax.nn.log_softmax(x)
        return a, b, c, d, e

    rep = analysis.check_program(f, np.ones((8,), np.float32),
                                 np.ones((8,), np.float32))
    assert not _hits(rep, "numerical-risk")


# ---------------------------------------------------------------- R005
def test_deadcode_rule_flags_unused_param_and_dead_compute():
    def f(params, x):
        wasted = x @ params["w"]           # 512^3 matmul, never used
        del wasted
        return jnp.sum(x), params["dead"]  # dead: pass-through only

    params = {"w": np.zeros((512, 512), np.float32),
              "dead": np.zeros((4,), np.float32)}
    rep = analysis.check_program(f, params, np.zeros((512, 512),
                                                     np.float32))
    found = _hits(rep, "dead-code", analysis.WARNING)
    assert any("dead" in d.message and "args[0]['dead']" in d.message
               for d in found)
    assert any("dead eqn" in d.message for d in found)


# ---------------------------------------------------------------- R006
def test_cost_rule_reports_hotspot_and_flags_dominant_eqn():
    def f(a, b):
        return a @ b                       # 2 * 1024^3 > hot_flops

    rep = analysis.check_program(f, np.zeros((1024, 1024), np.float32),
                                 np.zeros((1024, 1024), np.float32))
    hot = _hits(rep, "cost-model", analysis.WARNING)
    assert hot and hot[0].cost_flops == 2.0 * 1024 ** 3
    assert any("static cost" in d.message
               for d in _hits(rep, "cost-model", analysis.INFO))


def test_cost_rule_weights_scan_bodies_by_trip_count():
    def f(x):
        def body(c, _):
            return c @ x, ()
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    rep = analysis.check_program(
        f, np.zeros((128, 128), np.float32),
        rules=["cost-model"])
    summary = [d for d in rep if "static cost" in d.message][0]
    # 8 iterations x 2*128^3 FLOPs, reported in MFLOPs
    assert "33.55 MFLOP" in summary.message


# ------------------------------------------------------- engine / API
def test_op_paths_point_back_at_program_ops():
    """The executor scopes each op lowering as <op_type>.<seq>, so
    analyzer paths identify the source Program op."""
    from paddle_tpu.models import zoo_entry
    fn, args = zoo_entry("mlp")
    a = analysis.Analysis(fn, args, name="mlp")
    paths = {view.eqn_path(eqn) for view, eqn in a.iter_eqns()}
    assert any("mul." in p and "dot_general" in p for p in paths)
    assert any("adam." in p for p in paths)


def test_custom_rule_registration_and_selection():
    class NitRule(analysis.Rule):
        name = "nit"
        id = "R999"
        doc = "flags every add"

        def check(self, a):
            for view, eqn in a.iter_eqns():
                if eqn.primitive.name == "add":
                    yield analysis.Diagnostic(
                        self.name, analysis.INFO, "an add",
                        path=view.eqn_path(eqn))

    analysis.register_rule(NitRule)
    try:
        rep = analysis.check_program(
            lambda x: x + 1.0, np.zeros((2,), np.float32),
            rules=["nit"])
        assert _hits(rep, "nit")
        assert not _hits(rep, "cost-model")   # only requested rules ran
    finally:
        analysis.engine._RULES.pop("nit", None)
    with pytest.raises(KeyError):
        analysis.check_program(lambda x: x, np.zeros(1),
                               rules=["no-such-rule"])


def test_report_json_and_severity_filters():
    rep = analysis.check_program(
        lambda x: jnp.log(x * x), np.ones((4,), np.float32))
    import json
    blob = json.loads(rep.to_json())
    assert set(blob["counts"]) == {"error", "warning", "info"}
    assert blob["diagnostics"]
    assert len(rep.at_least("info")) == len(rep)
    assert all(d.severity == "warning"
               for d in rep.by_severity("warning"))


def test_cli_list_flags():
    from paddle_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    assert main(["--list-models"]) == 0
