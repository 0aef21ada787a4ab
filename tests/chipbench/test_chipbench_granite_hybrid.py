"""The cell ``granite4hmicro_train_T8k`` (ISSUE 64): the configuration
holds to its source, the built program counts the parameters the file
states, the arithmetic, the model against
``reference/granite_hybrid_lm.py`` (loss, logits, every parameter's
gradient), each published multiplier and each of the block's features
held by a case that fails without it, the fp8 control fails, the cell
rehearses through ``run.py``, the two new readers on a window written
out by hand, the entries in ``BENCHMARK.json`` (read off the file:
nothing here pins the END of a list) and PR 62's pin of the scan
readers' lists run against the lists as PR 62 left them."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, spans                     # noqa: E402
from chipbench.reference import compare, granite_hybrid_lm    # noqa: E402

CELL = "granite4hmicro_train_T8k"
CONFIG = "granite-4.0-h-micro-train-vp8"
REDUCED = ["num_hidden_layers", "vocab_size"]
NEW = ("ssd_gram_over_useful", "stream_scale_dev_share_pct")
# the accepted metrics' lists the cell is on (ISSUE 64)
LISTS = ("tokens_per_s", "flash_roof_pct", "flash_fwd_roof_pct",
         "flash_bwd_roof_pct", "matmul_roof_pct", "dense_matmul_roof_pct",
         "dense_matmul_fwd_roof_pct", "dense_matmul_bwd_roof_pct",
         "step_host_ms.train", "train_mfu_pct", "device_idle_pct.train",
         "optimizer_dev_share_pct", "unscoped_dev_share_pct",
         "exe_self_ms.train", "setup_trace_lower_s.train",
         "setup_compile_s.train", "step_interval_ms.train",
         "step_stall_pct.train", "exe_step_ms.train",
         "second_forward_dev_share_pct", "xent_dev_share_pct",
         "ssd_roof_pct", "ssd_glue_dev_share_pct")
ARCH = cells.load_arch("granite_hybrid")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tiny_cell():
    cell = cells.load_cell(ROOT, CELL)
    for part in ("config_file", "traffic_file"):
        cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
    return cell


def _built(cfg, seq):
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ARCH.build(cfg, seq)
    return main


def test_the_configuration_holds_to_its_source():
    """Every published key is in the file at its published value but
    the two cuts; every width, the four multipliers and all 40
    ``layer_types`` as published; ``cells.published_faults`` is empty;
    where the catalog is at hand, ``published`` is its row's ``config``
    key for key."""
    cell = cells.load_cell(ROOT, CELL)
    cfg = cell["config_file"]
    assert cells.published_faults(cfg) == []
    assert cfg["arch"] == "granite_hybrid" and cfg["reduced"] == REDUCED
    published = cfg["published"]
    assert {k for k in published if cfg[k] != published[k]} == set(REDUCED)
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert (published["num_hidden_layers"], published["vocab_size"]) == (
        40, 100352)
    assert published["vocab_size"] == 8 * cfg["vocab_size"]
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (
                12, 0.22, 0.015625, 8)
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
                2048, 8192, 64, 64, 1, 128, 4, 32, 8)
    assert len(cfg["layer_types"]) == 40
    kinds = granite_hybrid_lm.kinds(cfg)
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"].count("attention") == 4
    assert cfg["tie_word_embeddings"] is True
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["embedding_init_std"] == pytest.approx(1 / 12)
    for key in ("stream", "mlp", "mamba2", "in_proj_blocks",
                "mamba_chunk_size", "time_step", "a_init_max", "attention",
                "embedding_init_std", "initialisation", "seq_len"):
        assert cfg["assumed"][key], key
    assert "12,544 of 100,352" in cfg["deployment"]
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["check_rows"]) == (1, 8192, 4, 64)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "granite-4.0-h-micro"]
        assert published == row["config"]
        assert cfg["source"] == row["source_url"]


def test_the_entries_in_benchmark_json():
    """One configuration, one cell, two metrics, read off the file by
    name; the cell's name on the lists of the accepted metrics it
    reports and on no other; every new metric lists this cell and moves
    ``tokens_per_s``."""
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["file"] == "chipbench/configs/%s.json" % CONFIG
    assert config["reduced"] == REDUCED
    assert config["source"] == cells.load_json(
        os.path.join(ROOT, config["file"]))["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_T8k_b1", 1)
    for entry in (config, cell):
        assert 0 < len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    on = {name for name, m in by_name.items() if CELL in m.get(
        "workloads", ())}
    assert on == set(LISTS) | set(NEW)
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "tokens_per_s"
        reader = cells.load_metric(name)
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES)
    loaded = cells.load_cell(ROOT, CELL)
    assert {m["name"] for m in loaded["end_to_end"]} == {"tokens_per_s",
                                                         "setup_s"}


def test_pr_62s_pin_of_the_scan_readers_lists_as_pr_62_left_them(
        monkeypatch):
    """``test_chipbench_nemotron_h.py::test_the_entries_in_benchmark_json``
    holds ``ssd_roof_pct``'s and ``ssd_glue_dev_share_pct``'s lists to
    PR 62's cell ALONE; this PR appends its cell to both, as ISSUE 64
    asks (nine Mamba-2 mixers of ten layers: the readers are PR 62's
    and read this cell as they are), so the pin fails and is marked
    where the pins are (``tests/conftest.py``: that file is the
    benchmark's and not this PR's to edit). Here its assertions run
    against the lists as PR 62 left them: the later cells, read off
    ``BENCHMARK.json`` by what follows PR 62's, taken off every list.
    What a stripped copy cannot see is asserted first: this cell IS on
    both lists."""
    import test_chipbench_nemotron_h as theirs
    sound = cells.load_json
    bench = sound(os.path.join(ROOT, "BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in theirs.NEW:
        assert lists[name][:2] == [theirs.CELL, CELL], name
    names = [w["name"] for w in bench["workloads"]]
    later = names[names.index(theirs.CELL) + 1:]
    assert CELL in later

    def as_pr_62_left_it(path):
        bench = sound(path)
        if os.path.basename(path) != "BENCHMARK.json":
            return bench
        without = lambda m: {**m, "workloads": [
            w for w in m["workloads"] if w not in later]} \
            if "workloads" in m else m
        return {**bench,
                "workloads": [w for w in bench["workloads"]
                              if w["name"] not in later],
                "end_to_end": [without(m) for m in bench["end_to_end"]],
                "per_layer": [without(m) for m in bench["per_layer"]]}
    monkeypatch.setattr(cells, "load_json", as_pr_62_left_it)
    theirs.test_the_entries_in_benchmark_json()


def test_the_built_program_counts_the_parameters_the_file_states():
    """The program at the cell's own size, built and not run:
    772,160,448 parameters, by kind; ten regions; the scan at ONE
    group, the norm behind it over all channels, the scores at 1/64 and
    no rotation anywhere."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    main = _built(cfg, 8192)
    sizes = {p.name: math.prod(p.shape)
             for p in main.global_block().all_parameters()}
    of = lambda part: sum(n for name, n in sizes.items() if part in name)
    mlp = 2048 * 16384 + 8192 * 2048
    mamba = 2048 * 8512 + 4096 * 2048 + 5 * 4352 + 3 * 64 + 4096 + mlp \
        + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp + 2 * 2048
    for i, kind in enumerate(granite_hybrid_lm.kinds(cfg)):
        assert of("gh_l%d_" % i) == {"mamba": mamba,
                                     "attention": attention}[kind], i
    assert (mamba, attention) == (76182976, 60821504)
    assert sizes["gh_word_emb"] == 12544 * 2048 and "gh_head" not in sizes
    total = sum(sizes.values())
    assert total == 9 * mamba + attention + 12544 * 2048 + 2048 == 772160448
    assert "772,160,448" in cfg["parameters"] \
        and "12.35 GB" in cfg["parameters"]
    regions = [o for o in main.global_block().ops
               if o.type == "recompute_block"]
    assert len(regions) == 10
    inside = [o for r in regions for o in r.attr("sub_block").ops]
    scans = [o for o in inside if o.type == "ssd_scan"]
    assert len(scans) == 9 and {(o.attr("n_head"), o.attr("n_group"))
                                for o in scans} == {(64, 1)}
    assert {o.attr("groups") for o in inside
            if o.type == "gated_group_norm"} == {1}
    (attn,) = [o for o in inside if o.type == "causal_attention"]
    assert attn.attr("scale") == 0.015625 != 64 ** -0.5
    assert not {"rope", "qk_norm_rope"} & {o.type for o in inside}
    # the four multipliers, each an op of its own: the embedding's, two a
    # layer, the logits'
    scales = [o.attr("scale") for o in main.global_block().ops + inside
              if o.type == "scale"]
    assert sorted(scales) == [0.125] + [0.22] * 20 + [12.0]


def test_the_arithmetic():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    mlp = 3 * 2048 * 8192
    mamba = 2048 * 8512 + 4096 * 2048 + mlp
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp
    touched = 9 * mamba + attention + 2048 * 12544
    assert ARCH.touched_parameters(cfg) == touched
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    # 37.9 TFLOP of products a step, as ISSUE 64 counts
    assert 6 * touched * 8192 == pytest.approx(37.94e12, rel=1e-3)
    # the scan: 3.18 MFLOP a token and layer forward; ONE Gram product
    scan = 64 * (2 * 128 * 64 + 4 * 128 * 64) + 1 * 2 * 128 * 128
    assert ARCH.ssd_flops_per_token(cfg) == scan == 3178496
    scores = 4096.5 * 14 * 64 * 32                   # a token's share
    assert arith.train_flops_per_token(cfg, 8192) == pytest.approx(
        6 * touched + scores + 9 * 3 * scan)
    assert arith.flash_flops_per_step(cfg, 1, 8192) \
        == 14 * 64 * 32 * (8192 * 8193 // 2)
    # 0.94 TFLOP and 4.38 GB a step: the bytes bound the scans, 5.3 ms
    flops = ARCH.ssd_flops_per_step(cfg, 1, 8192)
    nbytes = ARCH.ssd_bytes_per_step(cfg, 1, 8192)
    assert flops == 9 * 8192 * 4 * scan == pytest.approx(0.937e12, rel=1e-3)
    assert nbytes == 9 * 8192 * 2 * (2 * (2 * 4096 + 256) + 3 * 4096
                                     + 2 * 256)
    assert nbytes == pytest.approx(4.38e9, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(5.35e-3, rel=1e-2) \
        and nbytes / 819e9 > flops / 197e12
    # the head is 3% of the parameters here for 6% in the whole model
    assert 2048 * 12544 / 772160448 == pytest.approx(0.033, abs=2e-3)
    whole = 36 * 76182976 + 4 * 60821504 + 100352 * 2048 + 2048
    assert whole == pytest.approx(3.191e9, rel=1e-3)
    assert 2048 * 100352 / whole == pytest.approx(0.064, abs=2e-3)
    assert arith.matmul_scopes(cfg) == ("mul",)


# -- the model against the reference, float32 ---------------------------------

B, T = 2, 48


def _feeds(cfg, seed=0):
    rng = np.random.RandomState(seed)
    v = cfg["vocab_size"]
    return {"src": rng.randint(0, v, (B, T)).astype(np.int64),
            "label": rng.randint(0, v, (B, T)).astype(np.int64),
            "mask": (rng.rand(B, T) > 0.2).astype(np.float32)}


@pytest.fixture(scope="module")
def program():
    """The rehearsal's configuration (3 layers, mamba / attention /
    mamba, 16 heads in ONE group, chunks of 32 rows over 48: one
    padded), float32, AMP off, with the gradient of every parameter:
    (cfg, main, its for_test clone, cost, logits, scope, executor). D,
    the convolution's bias and the norms' weights are drawn off their
    initial values, so that each is hit; the query's and the key's
    projections are six times their initial values, so that at heads of
    16 the softmax is not flat and its scale and a rotation show."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    cfg = _tiny_cell()["config_file"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, logits = ARCH.build(cfg, T)
        forward = main.clone(for_test=True)
        fluid.backward.append_backward(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    rng = np.random.RandomState(1)
    for p in main.global_block().all_parameters():
        if p.name.endswith(("norm", "_scan_d", "_b", "_dt_bias")):
            was = np.asarray(scope.find_var(p.name))
            scope.set(p.name, jnp.asarray(
                was + 0.3 * rng.randn(*was.shape).astype(np.float32)))
        elif p.name.endswith(("_wq", "_wk")):
            scope.set(p.name, 6.0 * jnp.asarray(scope.find_var(p.name)))
    return cfg, main, forward, cost, logits, scope, exe


def _params(program):
    import jax
    cfg, main, _, _, _, scope, _ = program
    return jax.tree.map(np.asarray, ARCH.params_of_program(main, scope, cfg))


class _Frozen(dict):
    """A configuration as a static argument of a jitted reference."""
    def __hash__(self):
        return id(self)


def test_the_program_is_the_reference(program):
    """Loss, every row's logits and the GRADIENT of every parameter,
    against ``jax.grad`` of the plain reference. The table's gradient
    is the sum of its two uses', the lookup's and the tied head's."""
    import jax
    import paddle_tpu as fluid
    cfg, main, forward, cost, logits, scope, exe = program
    feed, params = _feeds(cfg), _params(program)
    names = [p.name for p in main.global_block().all_parameters()]
    with fluid.scope_guard(scope):
        got_logits, = exe.run(forward, feed=feed, fetch_list=[logits])
        got = exe.run(main, feed=feed, fetch_list=[cost] + [
            n + "@GRAD" for n in names])
    loss = lambda p: granite_hybrid_lm.lm_loss(
        p, feed["src"], feed["label"], feed["mask"], _Frozen(cfg))
    want, grads = jax.value_and_grad(loss)(params)
    assert float(got[0]) == pytest.approx(float(want), rel=2e-6)
    for row in range(B):
        ref = ARCH.logits_at(params, feed["src"][row], 0, T, _Frozen(cfg))
        assert compare.logits_error(got_logits[row], ref) < 2e-5
    # the reference's tree, gradient by gradient, under the program's
    # names: the in_proj's five blocks, the filter's three and the
    # MLP's two side by side, as `params_of_program` lays them
    by_name = dict(zip(names, got[1:]))
    side = lambda at, fmt, parts: np.concatenate(
        [by_name[at + fmt % part] for part in parts], -1)
    checked = 0
    for i, (kind, g) in enumerate(zip(granite_hybrid_lm.kinds(cfg),
                                      grads["layers"])):
        at = "gh_l%d" % i
        mine = {"norm": by_name[at + "_norm"],
                "ffn_norm": by_name[at + "_ffn_norm"],
                "ffn_in": side(at, "_ffn_%s", ("gate", "up")),
                "ffn_out": by_name[at + "_ffn_down"]}
        if kind == "mamba":
            mine.update(
                w_in=side(at, "_in_%s", ("z", "x", "b", "c", "dt")),
                conv_w=side(at, "_conv_%s_w", ("x", "b", "c")),
                conv_b=side(at, "_conv_%s_b", ("x", "b", "c")),
                dt_bias=by_name[at + "_dt_bias"],
                a_log=by_name[at + "_scan_a_log"], d=by_name[at + "_scan_d"],
                norm_w=by_name[at + "_gnorm"], w_out=by_name[at + "_out"])
        else:
            mine.update({k: by_name["%s_%s" % (at, k)]
                         for k in ("wq", "wk", "wv", "wo")})
        assert set(mine) == set(g)
        for key, mine_g in mine.items():
            ref_g = np.asarray(g[key])
            assert np.abs(ref_g).max() > 0, (i, key)
            np.testing.assert_allclose(
                mine_g, ref_g, atol=1e-4 * np.abs(ref_g).max(),
                err_msg="layer %d %s" % (i, key))
            checked += mine_g.size
    for key, name in (("word_emb", "gh_word_emb"),
                      ("final_norm", "gh_final_norm")):
        ref_g = np.asarray(grads[key])
        np.testing.assert_allclose(by_name[name], ref_g,
                                   atol=1e-4 * np.abs(ref_g).max())
        checked += ref_g.size
    assert checked == sum(math.prod(p.shape) for p in
                          main.global_block().all_parameters())


# what the reference reads of the configuration, each moved off what the
# program was built with: the four multipliers (none is read by
# nothing), the score scale that is NOT head_dim^-0.5, the one group of
# B_t and C_t and the norm over all channels (at two groups of half the
# states the weights keep their shapes: heads 8-15 read other states,
# and the norm runs over halves)
MOVED = {
    "embedding_multiplier": {"embedding_multiplier": 6},
    "residual_multiplier": {"residual_multiplier": 0.3},
    "attention_multiplier": {"attention_multiplier": 0.05},
    "logits_scaling": {"logits_scaling": 6},
    "the_scores_at_head_dim_to_the_minus_half":
        {"attention_multiplier": 16 ** -0.5},
    "two_groups_of_heads_and_of_channels":
        {"mamba_n_groups": 2, "mamba_d_state": 8}}


@pytest.mark.parametrize("what", sorted(MOVED))
def test_a_term_moved_off_its_published_value_parts_the_logits(program,
                                                               what):
    """The program's logits against the reference's are within 2e-5
    (the test above); against the reference with ONE term moved they
    part by more than the cell's limit."""
    import paddle_tpu as fluid
    cfg, _, forward, _, logits, scope, exe = program
    feed, params = _feeds(cfg), _params(program)
    with fluid.scope_guard(scope):
        got, = exe.run(forward, feed=feed, fetch_list=[logits])
    moved = _Frozen({**cfg, **MOVED[what]})
    ref = ARCH.logits_at(params, feed["src"][0], 0, T, moved)
    assert compare.logits_error(got[0], ref) > ARCH.TRAIN_LOGITS_RTOL


@pytest.mark.parametrize("what", ["the_mlp_sublayer", "the_gate_first",
                                  "a_rotation"])
def test_a_part_of_the_block_left_out_parts_the_logits(program, what,
                                                       monkeypatch):
    """The second sublayer of a layer; the gate BEFORE the norm (the
    norm first reads otherwise); no rotation of q and k (a rotary
    embedding at ``rope_theta`` reads otherwise)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    cfg, _, forward, _, logits, scope, exe = program
    feed, params = _feeds(cfg), _params(program)
    with fluid.scope_guard(scope):
        got, = exe.run(forward, feed=feed, fetch_list=[logits])
    ref_mod = granite_hybrid_lm
    if what == "the_mlp_sublayer":
        monkeypatch.setattr(ref_mod, "mlp",
                            lambda p, h, mm: jnp.zeros_like(h))
    elif what == "the_gate_first":
        def norm_first(p, h, cfg, mm):
            d_inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            z = mm(h, p["w_in"])[:, :d_inner]
            return mm(_normed_scan(ref_mod, p, h, cfg, mm) * jax.nn.silu(z)
                      * p["norm_w"], p["w_out"])
        monkeypatch.setattr(ref_mod, "mamba2", norm_first)
    else:
        attention = ref_mod.attention

        def rotated(q, k, v, scale, mm):
            t, _, d = q.shape
            inv = cfg["rope_theta"] ** (-jnp.arange(0, d, 2) / d)
            ang = jnp.arange(t)[:, None] * inv[None, :]
            cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
            sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
            half = lambda x: jnp.concatenate(
                [-x[..., d // 2:], x[..., :d // 2]], -1)
            turn = lambda x: x * cos + half(x) * sin
            return attention(turn(q), turn(k), v, scale, mm)
        monkeypatch.setattr(ref_mod, "attention", rotated)
    ref = ARCH.logits_at(params, feed["src"][0], 0, T, _Frozen(cfg))
    assert compare.logits_error(got[0], ref) > ARCH.TRAIN_LOGITS_RTOL


def _normed_scan(ref_mod, p, h, cfg, mm):
    """``RMSNorm(y)`` of the mixer's scan over all its channels, no gate
    and no output projection, from the reference's own pieces."""
    import jax
    import jax.numpy as jnp
    t = h.shape[0]
    heads, p_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_inner, d_bc = heads * p_head, groups * n
    _, xbc, dt = jnp.split(mm(h, p["w_in"]),
                           [d_inner, 2 * d_inner + 2 * d_bc], axis=-1)
    xbc = jax.nn.silu(ref_mod.short_conv(xbc, p["conv_w"]) + p["conv_b"])
    x, b, c = jnp.split(xbc, [d_inner, d_inner + d_bc], axis=-1)
    y = ref_mod.recurrence(
        x.reshape(t, heads, p_head), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["a_log"]), b.reshape(t, groups, n),
        c.reshape(t, groups, n), p["d"]).reshape(t, d_inner)
    return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                             + cfg["rms_norm_eps"])


def test_the_fp8_control_fails_the_logits_limit(program):
    """The reference in fp8 e4m3 operands against itself in float32, at
    the rehearsal's widths: over ``TRAIN_LOGITS_RTOL``."""
    cfg = program[0]
    params = _params(program)
    tokens = _feeds(cfg, 3)["src"][0]
    ref = np.asarray(ARCH.logits_at(params, tokens, T - 32, 32,
                                    _Frozen(cfg)))
    low = np.asarray(ARCH.control_logits_at(params, tokens, T - 32, 32,
                                            _Frozen(cfg)))
    assert ref.shape == low.shape == (32, cfg["vocab_size"])
    assert compare.logits_error(low, ref) > 1.5 * ARCH.TRAIN_LOGITS_RTOL
    # the same control's loss: a number beside the reference's, parted
    # from it by fp8's rounding and by less than a dropped term would
    feed = _feeds(cfg, 3)
    args = (params, feed["src"], feed["label"], feed["mask"], _Frozen(cfg))
    import jax.numpy as jnp
    loss, low_loss = float(ARCH.lm_loss(*args)), float(
        granite_hybrid_lm.lm_loss(*args, operands=jnp.float8_e4m3fn))
    assert 1e-6 < compare.loss_error(low_loss, loss) < 1e-2


@pytest.mark.parametrize("seed", ["2200000029"])
def test_the_cell_rehearses_through_run_py(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         seed, "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}


def test_control_py_parts_the_program_from_the_fp8_control():
    """``chipbench/control.py`` on this cell at the rehearsal's sizes:
    the program's bf16-AMP reading under the limit and the fp8
    control's at least three times it (`separates`), which the first
    initialisation did not give on the chip: a tied head put a logit of
    20 on the input token and both errors were that one logit's. (The
    limit is the cell's own size's: a toy control may read under it.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "chipbench/control.py", "--workload", CELL,
         "--seeds", "11,12", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["separates"] is True and last["seeds"] == 2, last
    assert last["program_max"] < ARCH.TRAIN_LOGITS_RTOL == last["limit"]
    assert last["control_min"] > 5 * last["program_max"]


# -- the two new readers on a window written out by hand -----------------------

STEPS, BUSY = 2, 0.5
FWD, AGAIN, BWD = ("jit(step)/jvp()/checkpoint/",
                   "jit(step)/transpose(jvp())/checkpoint/"
                   "rematted_computation/",
                   "jit(step)/transpose(jvp())/checkpoint/")
# (the device row's name, op_name, seconds in each traced step)
OPS = [
    ("fusion.1", "jit(step)/jvp()/scale.4/mul:", 0.0004),
    ("fusion.2", FWD + "scale.31/mul:", 0.0010),
    ("fusion.3", AGAIN + "scale.31/mul:", 0.0010),
    ("fusion.4", BWD + "scale.31/mul:", 0.0012),
    ("fusion.5", "jit(step)/jvp()/scale.290/mul:", 0.0020),
    ("fusion.6", FWD + "mul.9/dot_general:", 0.02),
    ("fusion.7", FWD + "elementwise_add.32/add:", 0.003),
    ("fusion.8", "jit(step)/adam.300/mul:", 0.001)]
SCALED = 0.0004 + 0.0010 + 0.0010 + 0.0012 + 0.0020


def _run(cfg, ops=OPS, counters=None):
    window, t = {"host": [], "compiles": None, "ops": [], "modules": []}, 0.0
    for _ in range(STEPS):
        t0 = t
        for text, name, dur in ops:
            window["ops"].append(spans.device_op(text, t, dur, name))
            t += dur
        window["modules"].append({"program": "step", "start": t0,
                                  "dur": t - t0})
    return {"trace": {"busy_s": BUSY}, "spans": window, "config": cfg,
            "chips": 1, "peaks": {"flops_bf16": 197e12,
                                  "hbm_bytes_per_s": 819e9},
            "train": {"counters": counters or {}, "batch": 1,
                      "seq_len": 8192}}


def test_the_new_readers_on_a_window_by_hand(capsys):
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    # the kernels walk 8 of a group's 64 heads a grid step, each head
    # block making the group's Gram product again: eight a chunk
    apart = {"ssd_lowerings": {"pallas/fwd/128/64/8/8": 18,
                               "pallas/bwd/128/64/8/8": 9}}
    run = _run(cfg, counters=apart)
    read = lambda name, run=run: cells.load_metric(name).read(run)
    assert read("ssd_gram_over_useful") == 8.0
    assert read("stream_scale_dev_share_pct") == pytest.approx(
        100.0 * STEPS * SCALED / BUSY)
    out = capsys.readouterr().out
    assert "stream_scale_dev_share_pct: 0.011200 s under the scope " \
        "'scale' in 2 steps (fusion 0.011200)" in out
    assert "pallas/bwd/128/64/8/8 x 9, pallas/fwd/128/64/8/8 x 18" in out
    # a walk that shares the product across a group's head blocks, or
    # whose block is the group: 1; a row-by-row lowering (chunk 0) makes
    # none and is not counted
    shared = {"ssd_lowerings": {"pallas/fwd/128/64/8/1": 2,
                                "pallas/bwd/128/8/8/1": 1,
                                "steps/fwd/0/64/0/0": 5}}
    assert read("ssd_gram_over_useful", _run(cfg, counters=shared)) == 1.0
    mixed = {"ssd_lowerings": {"pallas/fwd/128/8/8/1": 3,
                               "pallas/fwd/128/64/8/8": 1}}
    assert read("ssd_gram_over_useful", _run(cfg, counters=mixed)) \
        == pytest.approx(11 / 4)
    # every multiplier fused into a neighbour: no op under the scope
    fused = [op for op in OPS if "scale." not in op[1]]
    assert read("stream_scale_dev_share_pct", _run(cfg, fused)) == 0.0


def test_the_new_readers_find_nothing_where_there_is_nothing():
    """A program whose counter lacks the labels (the parent of PR 64)
    or that ran no scan counts no ``ssd_lowerings``; an untraced run
    has no window. The readers return None and do not raise."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    read = lambda name, run: cells.load_metric(name).read(run)
    for counters in (None, {}, {"ssd_lowerings": {}},
                     {"ssd_lowerings": {"steps/fwd/0/64/0/0": 2}}):
        assert read("ssd_gram_over_useful", _run(cfg, counters=counters)) \
            is None
    run = _run(cfg)
    run["train"].pop("counters")
    assert read("ssd_gram_over_useful", run) is None
    run["spans"], run["trace"] = None, None             # an untraced run
    assert read("stream_scale_dev_share_pct", run) is None


def test_the_program_counts_its_scans_lowerings(monkeypatch):
    """``program_counters`` reads the scan's counter by the six labels
    the reader wants; a counter without them (the parent's) gives no
    tag and does not raise."""
    import jax.numpy as jnp
    from paddle_tpu.monitor import metrics
    from paddle_tpu.ops import ssd_scan as S
    counter = metrics.registry().get("ptpu_ssd_lowerings_total")
    counter.clear()
    S.ssd_scan(jnp.ones((1, 16, 2, 4)), jnp.ones((1, 16, 2)),
               -jnp.ones((2,)), jnp.ones((1, 16, 1, 8)),
               jnp.ones((1, 16, 1, 8)), jnp.ones((2,)), chunk=8,
               force="chunked")
    got = ARCH.program_counters(None, None)
    assert got["ssd_lowerings"] == {"chunked/fwd/8/2/2/1": 1}
    parents = metrics.Registry()
    parents.counter("ptpu_ssd_lowerings_total", "as before PR 64",
                    ("path", "direction", "chunk", "d_state")).inc(
                        path="pallas", direction="fwd", chunk="128",
                        d_state="128")
    monkeypatch.setattr(metrics, "registry", lambda: parents)
    assert ARCH.program_counters(None, None) == {"flash_lowerings": {},
                                                 "ssd_lowerings": {}}
