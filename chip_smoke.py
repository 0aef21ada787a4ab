"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of the transformer-large configuration (8 layers,
d1024, FFN 4096, T=1024, vocab 8192), weights random from ``--seed``:

  device  jax.devices() must be a TPU (no CPU fall-through)
  flash   the flash backward alone at the benchmark cell's shape
          ([4, 2048, 1024] bf16, 16 heads of 64, causal: one block
          holds all of T, so the ONE kernel flash_bwd): dq, dk, dv
          against dense float32 math on the same inputs
  gqa     the streamed kernels under grouped key/value heads and the
          block-granular mask at the block-diffusion cell's shape (one
          sequence of [4096, 32 x 128] reading [4096, 4 x 128], blocks
          of 4, both variants): dq, dk, dv against dense float32 math,
          the first block's wholly masked rows finite and weighed out;
          through the ONE streamed backward kernel (ISSUE 39; the line
          says which backward ran, from the lowering counter) and
          through flash_bwd_dq + flash_bwd_dkv, which a T over that
          kernel's byte bound still takes, with the device ms of each
  own_block  the rest of the gqa check (ISSUE 37; a phase of its own so
          that `--phases own_block` probes it alone): block diffusion's
          whole attention, [noised; clean] rows q [2, 8192, 32 x 128]
          and k/v [2, 8192, 4 x 128], as ONE call of each kernel (the
          own-block mask form) against dense float32 gradients, and its
          device ms forward and backward, the backward as ONE kernel and
          as the two, beside the form it replaced (two calls merged by
          lse, the own blocks as dense math)
  mla     the two-part score of latent attention through the streamed
          kernels at the shapes of the cells xing4_train_T4k and
          joyai_train_T8k (one sequence of [T, 32 x 128] with q_pe
          [T, 32 x 64] reading ONE k_pe [T, 64], values 128 wide, T 4096
          and 8192): dq_nope, dq_pe, dk_nope, dk_pe (summed over the 32
          heads) and dv against dense float32 math, the backward as ONE
          kernel (ISSUE 56) and as the two, both timed
  window  a window bound in the streamed kernels (ISSUE 38): q
          [2, 4096, 32 x 128] reading k/v [2, 4096, 4 x 128] under a
          window of 2048 keys: output, dq, dk, dv against dense float32
          math at `highest`; then the cell trinity_train_T16k's shape,
          one sequence of 16,384 rows, forward and backward in device
          ms under the window beside plain causal, the backward as ONE
          kernel and as the two
  scan    the selective scan's chunked kernel pair (ISSUE 40) at the
          cell phi4flash_train_T8k's shape, s and dt [1, 8192, 5120]
          bf16, 16 states: y and the gradients of all six inputs
          against the float32 lax.scan form at `highest`, both timed
  ssd     the state-space-dual scan's kernel pair (ISSUES 62, 64) at
          both Mamba-2 cells' shapes, [1, 8192, 64 x 64] bf16 with 128
          states: 8 groups of 8 heads (a grid step walks a group) and
          ONE group of 64 (a block of 8 a grid step, dB and dC summed
          over the blocks): y and all six gradients against the
          jax.numpy chunk walk, device ms by kernel; ONLY where
          `--phases ssd` asks for it
  conv    the causal depthwise convolution + SiLU in front of a scan or
          a delta rule (the Program op ssm_conv) as the kernel pair of
          ops/ssm_conv.py (ISSUE 65) at the four cells' shapes, x [1,
          8192, C] bf16 under 4 taps: C 4096 and 128 with a bias
          (Granite's; Nemotron's 4096), 1024, 5120, 1440 and 2880
          without: y, dx, dw and dbias against the jax.numpy path on
          the chip, device ms a call of each direction of both beside
          the bytes' time; ONLY where `--phases conv` asks for it
  diff    differential attention through the streamed kernels (ISSUE
          40): 40 query and 20 key/value heads of 64, values of 128,
          T 4096, full and under a window of 512: a1, a2, dq, dk, dv
          against the two softmaxes in dense float32 math; then forward
          and backward device ms at T 8192
  rotary  QK-norm and RoPE in the projections' own layout (the kernel
          pair of ops/rotary.py) at the block-diffusion cell's shapes,
          q [2, 8192, 32 x 128] and k [2, 8192, 4 x 128], and at
          lfm2_train_T32k's, [1, 32768, 32 x 64] and [.., 8 x 64], two
          heads to a lane tile (ISSUE 50): output, dx and dScale
          against float32 math, and a call's device ms forward and
          backward beside the HBM floor; heads of 64 beside the
          jax.numpy form they left
  experts the dropless expert layer at both routed cells' shapes
          (16,384 rows of 2048 over 16 held of 128 experts, softmax
          top-8; 4,096 rows of 3584 over 8 held of 64, sigmoid top-4):
          output and gradients against every held expert evaluated
          densely, nothing dropped when every row chooses held experts,
          a chunk's unread tail (NaN) harmless to the grouped matmuls,
          the kernels of ops/grouped_matmul.py and XLA's alike; then
          the phase `grouped`
  grouped the held experts' grouped matmuls ALONE (ISSUE 63), the probe
          by rows an expert: at the seven routed cells' chunks (256 to
          4,096 rows an expert, Nemotron's ungated experts among them)
          the three orientations of a pass's products, XLA's
          `ragged-dot` kernels against `grouped_matmul_rows` / `_rows_t`
          / `_by_expert` at row tiles of 128, 256 and 512: device ms
          and share of the bf16 peak, the kernels' results held to
          XLA's; `--phases grouped` runs it alone
  rows    the expert layer's row moves alone (ISSUE 35) at both
          cells' chunks, filled to the cells' share and filled whole:
          XLA's gather, and the scatter-add as XLA's op against the
          kernel of ops/moe_rows.py; device milliseconds and GB/s
  embed   the embedding's gradient alone (ISSUE 58) at five cells'
          shapes (T ids, a table [V, d]: 16384, 37984, 2560; 8192,
          12544, 3840; 4096, 16384, 3584; 32768, 8192, 2048; 8192,
          50272, 1024), ids drawn uniformly and Zipf-like (half the
          places on 1% of the ids): XLA's scatter-add against the
          kernel embedding_grad_rows of ops/embedding_grad.py with the
          sort and the gather round it, device ms; the kernel's table
          bit for bit the float32 sums in sorted stable order
  hc      the hyper-connections' stages alone (ISSUE 43) at the cell
          xing4_train_T4k's shape, a float32 stream [4096, 4 x 3584]:
          "mix" and "merge", forward and backward, the jax.numpy form
          against the kernels of ops/hyper_connection.py: device ms,
          passes over the stream, GB/s, and the largest difference in
          values and gradients
  delta   the gated delta rule's kernel pair (ISSUE 54) at the cell
          olmohybrid_train_T8k's shape, q and k [1, 8192, 15 x 96], v
          [.., 15 x 192] bf16, float32 gates: o and the gradients of
          all five inputs against the jax.numpy chunk walk on the same
          values, in bf16 and in float32; device ms forward and
          forward + backward at chunks of 64 and 128 beside the walk's
  train   T.transformer_lm -> Adam.minimize -> amp.enable_amp ->
          Executor(TPUPlace(0)); 5 steps on one batch; loss ~ ln(vocab)
          and falling; the flash kernel is in the compiled step
  serve   TransformerLMInfer + serving.Engine(defaults, 8 slots); 8
          requests; float32 (paged Pallas kernel) and bfloat16 (gather),
          each token-identical to serving.sequential_generate

    python chip_smoke.py                 one TPU chip (the driver's run)
    python chip_smoke.py --chips 4       ONLY the path across chips:
                                         ParallelExecutor on a dp2 x tp2
                                         mesh against the one-device run
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                         same phases, tiny size, kernels
                                         in interpret mode, on the CPU
                                         (--chips 4 --rehearse wants
                                         XLA_FLAGS=--xla_force_host_platform_device_count=4)

One process, no network, no children. Any failed phase raises, so the
exit code is non-zero and the result line is not printed. The last
line of stdout is one JSON object naming the device JAX reports; a
rehearsal says "platform": "cpu" there and can never be read as a
chip pass.
"""

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

import numpy as np

REAL = dict(n_layer=8, n_head=16, d_model=1024, d_inner=4096,
            max_len=1024, vocab=8192, batch=8, slots=8, requests=8,
            prompt=(32, 384), max_new=32)
TINY = dict(n_layer=2, n_head=4, d_model=64, d_inner=256, max_len=128,
            vocab=512, batch=4, slots=4, requests=4, prompt=(8, 40),
            max_new=8)


def log(msg):
    print(msg, flush=True)


def peak_hbm(phase):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log("[%s] peak_bytes_in_use: %s" % (
        phase, "%d (%.2f GiB)" % (peak, peak / 2 ** 30)
        if peak is not None else "not reported by this backend"))


def compiled_text(jitted, *args):
    """Optimized HLO of ``jitted`` for these arguments. Lowering
    neither runs nor donates; after the real call the compile is a
    persistent-cache hit."""
    return jitted.lower(*args).compile().as_text()


def kernels_in_interpret_mode():
    """Rehearsal only: steer both attention dispatchers to their
    Pallas kernels in interpret mode, so the CPU run walks the kernel
    code the chip will compile. Done here, in the script — the program
    has no such option."""
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops import rotary
    fa_resolve = fa._resolve_path
    fa._resolve_path = lambda q, scale, bq, bk, force: fa_resolve(
        q, scale, bq, bk, force or "interpret")
    pa._resolve_path = lambda q, force: force or "interpret"
    rotary_resolve = rotary._resolve_path
    rotary._resolve_path = lambda x, d, rows, rotate, force: rotary_resolve(
        x, d, rows, rotate, force or "interpret")
    from paddle_tpu.ops import moe_rows
    moe_rows._resolve_path = lambda shape, like, force: force or "interpret"
    from paddle_tpu.ops import grouped_matmul
    choose = grouped_matmul.choose
    grouped_matmul.choose = lambda rows, widths, like, force=None: choose(
        rows, widths, like, force or "interpret")
    from paddle_tpu.ops import embedding_grad
    embedding_grad._resolve_path = (
        lambda ids, shape, dtype, like, force: force or "interpret")


# --------------------------------------------------------------------------
def phase_device(rehearse, chips, cache_dir):
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    devs = jax.devices()
    dev = devs[0]
    log("[device] platform=%s kind=%s count=%d jax=%s jaxlib=%s "
        "libtpu=%s" % (dev.platform, dev.device_kind, len(devs),
                       jax.__version__, jaxlib.__version__,
                       libtpu_version))
    log("[device] compile cache: %s" % cache_dir)
    if not rehearse and dev.platform != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU — JAX reports platform %r (%s). This "
            "script does not fall back to the CPU; a CPU rehearsal is "
            "`JAX_PLATFORMS=cpu python chip_smoke.py --rehearse`."
            % (dev.platform, dev.device_kind))
    if len(devs) < chips:
        raise SystemExit(
            "chip_smoke: --chips %d but JAX reports %d %s device(s)"
            % (chips, len(devs), dev.platform))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _place(rehearse):
    import paddle_tpu as fluid
    return fluid.CPUPlace() if rehearse else fluid.TPUPlace(0)


def _persistables(program, scope):
    """The executors' state argument: the program's persistable
    variables that exist in ``scope``."""
    names = [v.name for v in program.global_block().vars.values()
             if v.persistable]
    return {n: scope.find_var(n) for n in names
            if scope.find_var(n) is not None}


def _lm_batch(cfg, seed):
    from paddle_tpu.models import transformer as T
    feeds = T.make_lm_batch(np.random.RandomState(seed), cfg["batch"],
                            cfg["max_len"], cfg["vocab"])
    feeds["mask"] = np.ones_like(feeds["mask"])     # packed sequences
    return feeds


# --------------------------------------------------------------------------
# bf16 gradients round at 2^-9 of their largest value; the cell's shape
# read 3.0e-3 to 3.9e-3 on the chip (PERF.md section 6, PR 31)
FLASH_GRAD_TOL = 2e-2
# the expert layer's bf16 matmuls (three in a row, float32 sums) against
# the float32 layer: readings are set beside the phase's log line in
# PERF.md section 6, PR 32
EXPERT_TOL = 3e-2


def phase_flash(seed, rehearse):
    """The backward the benchmark's step runs 24 times and nothing else
    compares: gradients of `flash_bthd` against the dense float32 math
    (`highest`), largest difference over largest value, each gradient."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    b, t, h, d = (1, 256, 4, 64) if rehearse else (4, 2048, 16, 64)
    rng = np.random.RandomState(seed)
    q, k, v, dy = (jnp.asarray(rng.randn(b, t, h * d) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    f32 = lambda x: x.astype(jnp.float32)

    def kernel(q, k, v):
        return (f32(fa.flash_bthd(q, k, v, h, causal=True)) * f32(dy)).sum()

    def dense(q, k, v):
        o = fa._dense(*(fa.heads_first(x, h) for x in (q, k, v)), True,
                      d ** -0.5)
        return (fa.heads_last(o) * f32(dy)).sum()

    t0 = time.perf_counter()
    grad = jax.jit(jax.grad(kernel, (0, 1, 2))).lower(q, k, v).compile()
    got, text = grad(q, k, v), grad.as_text()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(dense, (0, 1, 2)))(f32(q), f32(k), f32(v))
    errs = [float(jnp.max(jnp.abs(f32(a) - r)) / jnp.max(jnp.abs(r)))
            for a, r in zip(got, want)]
    log("[flash] q/k/v/dy [%d, %d, %d] bf16 causal: dq %.3e dk %.3e dv "
        "%.3e from the dense float32 gradients (%.1f s); "
        "tpu_custom_call sites %d" % (b, t, h * d, *errs,
                                     time.perf_counter() - t0,
                                     text.count("tpu_custom_call")))
    assert max(errs) <= FLASH_GRAD_TOL, errs
    if not rehearse:
        # the forward re-run and ONE backward kernel
        assert "flash_bwd" in text and "flash_bwd_dq" not in text, \
            "all of T in one block did not take the fused backward"


def _far(got, want):
    """Largest difference over largest value, each result from its
    float32 reference."""
    import jax.numpy as jnp
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - r))
                  / jnp.max(jnp.abs(r))) for a, r in zip(got, want)]


@contextlib.contextmanager
def _backwards_lowered(fa):
    """Yields a list that holds, once the block is left, the `backward`
    labels `ptpu_flash_lowerings_total` counted inside it: which
    backward what was traced there will run."""
    before, ran = fa._LOWERINGS.snapshot(), []
    yield ran
    at = fa._LOWERINGS.label_names.index("backward")
    ran += sorted({key[at] for key, n in fa._LOWERINGS.snapshot().items()
                   if n > before.get(key, 0)})


@contextlib.contextmanager
def _two_kernels(fa):
    """What is traced inside runs the streamed backward as it was,
    flash_bwd_dq + flash_bwd_dkv: no shape is within the bound of the
    ONE streamed kernel (ISSUE 39)."""
    was, fa._RESIDENT_DQ_BYTES = fa._RESIDENT_DQ_BYTES, 0
    try:
        yield
    finally:
        fa._RESIDENT_DQ_BYTES = was


def phase_gqa(seed, rehearse):
    """The kernels of the block-diffusion step (ISSUE 32): 32 query
    heads of 128 reading 4 key/value heads, T 4096 streamed, under
    `mask_block` 4 with and without `strict`; gradients against dense
    float32 math with the mask written out. Under `strict` the first
    block's rows see nothing: their output must be finite, their lse
    -1e30, and weighed out they must leave every gradient right."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    b, t, h, hkv, d = (2, 256, 4, 2, 128) if rehearse else (
        2, 4096, 32, 4, 128)
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(b, t, n * d) * 0.5, jnp.bfloat16)
    q, k, v, dy = mk(h), mk(hkv), mk(hkv), mk(h)
    f32 = lambda x: x.astype(jnp.float32)
    for strict in (False, True):
        kw = dict(causal=True, n_kv_head=hkv, mask_block=4, strict=strict)

        def loss(attend, dy, q, k, v):
            o, lse = attend(q, k, v)
            seen = (lse > -1e29).transpose(0, 2, 1)[..., None]   # [b,T,H,1]
            o = jnp.where(seen, f32(o).reshape(-1, t, h, d), 0.0)
            return (o * f32(dy).reshape(-1, t, h, d)).sum() \
                + jnp.where(lse > -1e29, lse, 0.0).sum()

        def dense(q, k, v):
            o, lse = fa._dense_lse(
                fa.heads_first(q, h), fa.heads_first(k, hkv),
                fa.heads_first(v, hkv), True, d ** -0.5, (2, int(strict)))
            return fa.heads_last(o), lse

        t0 = time.perf_counter()
        kernel = lambda q, k, v: fa.flash_bthd_lse(q, k, v, h, **kw)
        out, lse = jax.jit(kernel)(q, k, v)
        assert bool(jnp.isfinite(f32(out)).all())
        unseen = int((lse < -1e29).sum())
        assert unseen == (4 * h * b if strict else 0), unseen
        compile_grad = lambda: jax.jit(jax.grad(functools.partial(
            loss, lambda q, k, v: fa.flash_bthd_lse(q, k, v, h, **kw), dy),
            (0, 1, 2))).lower(q, k, v).compile()
        with _backwards_lowered(fa) as ran:
            grad = compile_grad()
        with _two_kernels(fa):
            grad_two = compile_grad()
        got, text = grad(q, k, v), grad.as_text()
        # the loss is a sum over sequences, so the dense gradients are
        # made a sequence at a time (32 heads of 4096^2 float32 scores)
        dense_grad = jax.jit(jax.grad(functools.partial(loss, dense),
                                      (1, 2, 3)))
        with jax.default_matmul_precision("highest"):
            rows = [dense_grad(dy[r:r + 1], f32(q[r:r + 1]),
                               f32(k[r:r + 1]), f32(v[r:r + 1]))
                    for r in range(b)]
        want = [jnp.concatenate(parts) for parts in zip(*rows)]
        errs, errs_two = _far(got, want), _far(grad_two(q, k, v), want)
        log("[gqa] q [%d, %d, %d] k/v [%d, %d, %d] bf16, blocks of 4%s, "
            "backward %s: dq %.3e dk %.3e dv %.3e from the dense float32 "
            "gradients (the two kernels: %.3e %.3e %.3e) (%.1f s); %d "
            "rows see nothing" % (
                b, t, h * d, b, t, hkv * d, ", strict" if strict else "",
                "+".join(ran), *errs, *errs_two, time.perf_counter() - t0,
                unseen))
        assert max(errs + errs_two) <= FLASH_GRAD_TOL, (errs, errs_two)
        if not rehearse:
            # T streamed: the ONE kernel, and the two it replaced where
            # no shape is within its bound
            assert ran == ["fused_streamed"], ran
            assert "flash_bwd" in text and "flash_bwd_dq" not in text
            two = grad_two.as_text()
            assert "flash_bwd_dq" in two and "flash_bwd_dkv" in two
            # the mask's variant moves no time: once is enough
            for label, call in () if strict else (
                    ("fused_streamed", grad), ("two_kernels", grad_two)):
                ms, _, ops = _device_ms(call, (q, k, v), 6, rehearse,
                                        "gqa_" + label)
                log("[gqa] %s: forward + backward %.3f ms a call on the "
                    "device (%s)" % (label, ms, ", ".join(
                        "%s %.3f" % kv for kv in ops.most_common(5))))


def _two_piece_attention(q, k, v, n_head, n_kv_head, block):
    """Block diffusion's attention as ops/block_diffusion.py had it
    until ISSUE 37, kept here as what the own-block form is timed
    against: two calls of the flash kernels (clean on clean; noised on
    the clean keys of earlier blocks, with its lse) and the noised
    rows' own blocks as dense math in XLA, merged by log-sum-exp, round
    slices of the halves and a concatenate."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    b, t2, hd = q.shape
    seq, d, group = t2 // 2, hd // n_head, n_head // n_kv_head
    scale = d ** -0.5
    kw = dict(causal=True, scale=scale, n_kv_head=n_kv_head,
              mask_block=block)
    q_n, k_n, v_n = q[:, :seq], k[:, :seq], v[:, :seq]
    k_c, v_c = k[:, seq:], v[:, seq:]
    clean = fa.flash_bthd(q[:, seq:], k_c, v_c, n_head, **kw)
    before, lse_before = fa.flash_bthd_lse(q_n, k_c, v_c, n_head,
                                           strict=True, **kw)
    blocks = seq // block
    qb = q_n.reshape(b, blocks, block, n_kv_head, group, d)
    kb = k_n.reshape(b, blocks, block, n_kv_head, d)
    vb = v_n.reshape(b, blocks, block, n_kv_head, d)
    s = jnp.einsum("bnqhgd,bnkhd->bnqhgk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    lse_own = jax.nn.logsumexp(s, axis=-1)
    own = jnp.einsum("bnqhgk,bnkhd->bnqhgd",
                     jnp.exp(s - lse_own[..., None]).astype(v.dtype), vb,
                     preferred_element_type=jnp.float32)
    lse_own = lse_own.reshape(b, seq, n_head)
    lse_before = lse_before.transpose(0, 2, 1)
    top = jnp.maximum(lse_own, lse_before)
    w_own = jnp.exp(lse_own - top)[..., None]
    w_before = jnp.exp(lse_before - top)[..., None]
    noised = (w_own * own.reshape(b, seq, n_head, d) + w_before
              * before.reshape(b, seq, n_head, d).astype(jnp.float32)
              ) / (w_own + w_before)
    return jnp.concatenate(
        [noised.reshape(b, seq, hd).astype(q.dtype), clean], axis=1)


def _device_ms(call, args, calls, rehearse, name, carried=False):
    """(device ms a call of the jitted `call`: the median of its runs
    under the profiler; the last result; {op kind: ms a call}); in a
    rehearsal the host's clock and no ops. `carried`: each call takes
    the one before's result as its first argument (a donated
    accumulator)."""
    import collections
    import jax
    from chipbench import tracing
    trace_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "chiprun_out", name)
    out = jax.block_until_ready(call(*args))
    tracing.start(trace_dir)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = call(*((out,) + tuple(args[1:]) if carried else args))
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) * 1e3 / calls
    tracing.stop()
    rows = [row for row in tracing.load_rows(trace_dir)
            if row["plane"].startswith("/device:")]
    runs = [row["dur"] * 1e3 for row in rows
            if row["line"] == tracing.MODULE_LINE]
    assert rehearse or len(runs) == calls, len(runs)
    ops = collections.Counter()
    for row in rows:
        if row["line"] == tracing.OP_LINE:
            ops[tracing.op_name(row["name"])] += row["dur"] * 1e3 / calls
    return (float(np.median(runs)) if runs else wall), out, ops


def phase_own_block(seed, rehearse):
    """Block diffusion's attention inside the kernels (ISSUE 37; the
    `gqa` phase's third mask form): the own-block form at the cell's shape, out, dq,
    dk and dv of both halves against dense float32 math with the
    2L x 2L mask written out (a sequence and a key/value head at a
    time: 8 heads of 8192^2 float32 scores), then a layer's attention
    forward and backward under the profiler, this form and the
    two-piece form it replaced: whole, the kernels, and the rest."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import block_diffusion as bd
    from paddle_tpu.ops import flash_attention as fa
    b, seq, h, hkv, d = (2, 128, 4, 2, 128) if rehearse else (
        2, 4096, 32, 4, 128)
    group = h // hkv
    rng = np.random.RandomState(seed + 37)
    mk = lambda n: jnp.asarray(rng.randn(b, 2 * seq, n * d) * 0.5,
                               jnp.bfloat16)
    q, k, v, dy = mk(h), mk(hkv), mk(hkv), mk(h)
    f32 = lambda x: x.astype(jnp.float32)

    def both(attend):
        def call(q, k, v, dy):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(dy)
        return jax.jit(call)

    def dense(q, k, v):         # one sequence, one key/value head
        o, _ = fa._dense_lse(
            fa.heads_first(q, group), fa.heads_first(k, 1),
            fa.heads_first(v, 1), True, d ** -0.5, (2, fa._OWN))
        return fa.heads_last(o)

    t0 = time.perf_counter()
    own_block = lambda q, k, v: bd.attention(q, k, v, h, hkv, 4)
    new, new_two = both(own_block), both(own_block)
    old = both(lambda q, k, v: _two_piece_attention(q, k, v, h, hkv, 4))
    with _backwards_lowered(fa) as ran:
        got = new(q, k, v, dy)
    text = new.lower(q, k, v, dy).compile().as_text()
    with _two_kernels(fa):      # traced here, with the backward as it was
        got_two = new_two(q, k, v, dy)
    dense_both = both(dense)
    parts = []
    with jax.default_matmul_precision("highest"):
        for r in range(b):
            heads = [dense_both(*(f32(x[r:r + 1, :, a * n * d:(a + 1) * n * d])
                                  for x, n in ((q, group), (k, 1), (v, 1),
                                               (dy, group))))
                     for a in range(hkv)]
            parts.append([jnp.concatenate(x, 2) for x in zip(*heads)])
    want = [jnp.concatenate(x) for x in zip(*parts)]
    errs, errs_two, was = (_far(x, want) for x in (
        got, got_two, old(q, k, v, dy)))
    log("[own_block] own-block form, q [%d, %d, %d] k/v [%d, %d, %d] bf16, "
        "[noised; clean] rows, blocks of 4, backward %s: out %.3e dq %.3e "
        "dk %.3e dv %.3e from dense float32 math (flash_bwd_dq + "
        "flash_bwd_dkv: %.3e %.3e %.3e %.3e; the two-piece form: %.3e "
        "%.3e %.3e %.3e) (%.1f s); tpu_custom_call sites %d" % (
            b, 2 * seq, h * d, b, 2 * seq, hkv * d, "+".join(ran), *errs,
            *errs_two, *was, time.perf_counter() - t0,
            text.count("tpu_custom_call")))
    assert max(errs + errs_two) <= FLASH_GRAD_TOL, (errs, errs_two)
    if not rehearse:
        # the forward and ONE backward kernel (ISSUE 39)
        assert ran == ["fused_streamed"], ran
        assert text.count("tpu_custom_call") == 2, "not one call a kernel"
    kernels = ("flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv")
    for label, call in (("own-block", new), ("own-block-two-kernels", new_two),
                        ("two-piece", old)):
        ms, _, ops = _device_ms(call, (q, k, v, dy), 2 if rehearse else 10,
                                rehearse, "bd_attention_" + label)
        inside = {n: ops.pop(n, 0.0) for n in kernels}
        log("[own_block] %s form, a layer's attention forward and backward: "
            "%.3f ms a call on the device; kernels %s = %.3f; outside "
            "them %.3f: %s" % (
                label, ms, " ".join("%s %.3f" % x for x in inside.items()),
                sum(inside.values()), sum(ops.values()),
                " ".join("%s %.3f" % x for x in ops.most_common(12))))
    if rehearse:
        log("[own_block] (REHEARSAL: a CPU's times, no device number)")


def phase_mla(seed, rehearse):
    """The kernels of the latent-attention step (ISSUEs 34 and 56): a
    score of two parts, q_nope k_nope^T over 128 lanes a head plus
    q_pe k_pe^T over 64 lanes against ONE key that all 32 heads read,
    values 128 wide, causal, streamed, at the cells' two lengths
    (`xing4_train_T4k`'s 4096 and `joyai_train_T8k`'s 8192); every
    gradient against dense float32 math with the shared key broadcast
    in the einsum alone (eight heads at a time at T 8192), through the
    ONE backward kernel `flash_bwd` and, with no shape within its byte
    bound, through `flash_bwd_dq` + `flash_bwd_dkv`; then both timed
    under the profiler."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    b, h, d, d2 = (1, 4, 128, 64) if rehearse else (1, 32, 128, 64)
    f32 = lambda x: x.astype(jnp.float32)
    scale = (d + d2) ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    wrt = (0, 1, 2, 3, 4)
    for t in (256, 512) if rehearse else (4096, 8192):
        rng = np.random.RandomState(seed + t)
        mk = lambda lanes: jnp.asarray(rng.randn(b, t, lanes) * 0.5,
                                       jnp.bfloat16)
        q, k, v, q2, k2, dy = mk(h * d), mk(h * d), mk(h * d), \
            mk(h * d2), mk(d2), mk(h * d)

        def kernel(q, k, v, q2, k2):
            out = fa.flash_bthd(q, k, v, h, causal=True, scale=scale, q2=q2,
                                k2=k2)
            return (f32(out) * f32(dy)).sum()

        # the dense side `part` heads at a time (32 heads of 4096^2
        # float32 scores are what the device holds beside them): q's, k's,
        # v's and q_pe's gradients are the parts side by side, k_pe's
        # their sum
        part = max(1, min(h, h * 4096 ** 2 // t ** 2))

        def dense(q, k, v, q2, k2, dy):
            o, _ = fa._dense_lse(
                *(fa.heads_first(x, part) for x in (q, k, v)), True, scale,
                (0, 0), fa.heads_first(q2, part), k2)
            return (fa.heads_last(o) * dy).sum()

        t0 = time.perf_counter()
        compile_grad = lambda: jax.jit(jax.grad(kernel, wrt)).lower(
            q, k, v, q2, k2).compile()
        with _backwards_lowered(fa) as ran:
            grad = compile_grad()
        with _two_kernels(fa):
            grad_two = compile_grad()
        got, text = grad(q, k, v, q2, k2), grad.as_text()
        dense_grad = jax.jit(jax.grad(dense, wrt))
        heads = lambda x, a, w: f32(x[..., a * w:(a + part) * w])
        with jax.default_matmul_precision("highest"):
            parts = [dense_grad(heads(q, a, d), heads(k, a, d),
                                heads(v, a, d), heads(q2, a, d2), f32(k2),
                                heads(dy, a, d)) for a in range(0, h, part)]
        want = [jnp.concatenate(xs, -1) for xs in list(zip(*parts))[:4]] + [
            sum(x[4] for x in parts)]
        errs = _far(got, want)
        errs_two = _far(grad_two(q, k, v, q2, k2), want)
        log("[mla] q/k/v [%d, %d, %d] q_pe [.., %d] k_pe [.., %d] bf16 "
            "causal, backward %s: dq_nope %.3e dk_nope %.3e dv %.3e dq_pe "
            "%.3e dk_pe %.3e from the dense float32 gradients (the two "
            "kernels: %.3e %.3e %.3e %.3e %.3e) (%.1f s); tpu_custom_call "
            "sites %d" % (b, t, h * d, h * d2, d2, "+".join(ran), *errs,
                          *errs_two, time.perf_counter() - t0,
                          text.count("tpu_custom_call")))
        assert max(errs + errs_two) <= FLASH_GRAD_TOL, (errs, errs_two)
        if rehearse:
            continue
        # the ONE kernel within its byte bound, the two it replaced where
        # no shape is
        assert ran == ["fused_streamed"], ran
        assert "flash_bwd" in text and "flash_bwd_dq" not in text \
            and "flash_bwd_dkv" not in text
        two = grad_two.as_text()
        assert "flash_bwd_dq" in two and "flash_bwd_dkv" in two
        # nothing of k_pe's size times the heads, and no operand padded
        # to 256 lanes a head, is made round the kernels
        assert "%d,%d]" % (t, h * 2 * d) not in text
        for label, call in (("fused_streamed", grad),
                            ("two_kernels", grad_two)):
            ms, _, ops = _device_ms(call, (q, k, v, q2, k2), 6, rehearse,
                                    "mla_%s_T%d" % (label, t))
            log("[mla] T %d %s: forward + backward %.3f ms a layer on the "
                "device (%s)" % (t, label, ms, ", ".join(
                    "%s %.3f" % kv for kv in ops.most_common(6))))


def phase_window(seed, rehearse):
    """The kernels of a sliding-window layer (ISSUE 38): 32 query heads
    of 128 reading 4 key/value heads under a window of 2048 keys at
    T 4096, streamed (a q block's band is three key blocks of 1024: its
    own, cut on the diagonal, one whole, and one that the lower edge
    crosses); output and the three gradients against dense float32 math
    at `highest` with the band written out. Then what the bound saves,
    at the cell's own shape (one sequence of 16,384 rows): device ms of
    forward and backward under the window beside plain causal."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    b, t, h, hkv, d, window = (2, 512, 4, 1, 128, 200) if rehearse else (
        2, 4096, 32, 4, 128, 2048)
    rng = np.random.RandomState(seed)
    mk = lambda rows, n: jnp.asarray(rng.randn(1, rows, n * d) * 0.5,
                                     jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)

    def attend(w):
        def loss(dy, q, k, v):
            out = fa.flash_bthd(q, k, v, h, causal=True, n_kv_head=hkv,
                                window=w)
            return (f32(out) * f32(dy)).sum(), out
        return loss

    def dense(dy, q, k, v):
        o, _ = fa._dense_lse(
            fa.heads_first(q, h), fa.heads_first(k, hkv),
            fa.heads_first(v, hkv), True, d ** -0.5, (0, fa._WIN, window))
        return (fa.heads_last(o) * f32(dy)).sum(), fa.heads_last(o)

    t0 = time.perf_counter()
    kernel = jax.jit(jax.value_and_grad(attend(window), (1, 2, 3),
                                        has_aux=True))
    dense_grad = jax.jit(jax.value_and_grad(dense, (1, 2, 3), has_aux=True))
    errs, text = [], ""
    for row in range(b):        # a sequence at a time: 32 x 4096^2 scores
        dy, q, k, v = mk(t, h), mk(t, h), mk(t, hkv), mk(t, hkv)
        if not text:
            text = compiled_text(kernel, dy, q, k, v)
        (_, out), got = kernel(dy, q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, ref), want = dense_grad(dy, f32(q), f32(k), f32(v))
        errs.append([
            float(jnp.max(jnp.abs(f32(a) - r)) / jnp.max(jnp.abs(r)))
            for a, r in zip((out,) + got, (ref,) + want)])
    errs = np.max(errs, axis=0)
    log("[window] q [%d, %d, %d] k/v [.., %d] bf16, window %d: out %.3e "
        "dq %.3e dk %.3e dv %.3e from dense float32 math (%.1f s)" % (
            b, t, h * d, hkv * d, window, *errs, time.perf_counter() - t0))
    assert max(errs) <= FLASH_GRAD_TOL, errs
    if not rehearse:
        assert "flash_bwd" in text and "flash_bwd_dq" not in text
    t = 1024 if rehearse else 16384
    dy, q, k, v = mk(t, h), mk(t, h), mk(t, hkv), mk(t, hkv)
    for w in (window, None):
        for two in (False, True):
            with _backwards_lowered(fa) as ran, (
                    _two_kernels(fa) if two else contextlib.nullcontext()):
                call = jax.jit(jax.grad(attend(w), (1, 2, 3), has_aux=True))
                ms, (got, _), ops = _device_ms(call, (dy, q, k, v), 4,
                                               rehearse, "window")
            if not two:
                one = [f32(x) for x in got]
            # the same sums in another order: bf16's last bit
            apart = max(_far(got, one))
            log("[window] T %d, window %s, backward %s: forward + backward "
                "%.3f ms a call on the device (%s); %.3e from the ONE "
                "kernel's gradients" % (
                    t, w, "+".join(ran), ms,
                    ", ".join("%s %.3f" % kv for kv in ops.most_common(6)),
                    apart))
            assert apart <= FLASH_GRAD_TOL, apart


def phase_scan(seed, rehearse):
    """The selective scan's chunked kernel pair (ISSUE 40) at the cell
    phi4flash_train_T8k's shape: s and dt [1, 8192, 5120] bf16, 16
    states a channel: y and the gradients of all six inputs against the
    float32 ``lax.scan`` form at ``highest`` on the same values, and
    the device ms of both, forward and forward + backward, beside the
    bytes the kernels must move at the HBM peak."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import selective_scan as ss
    b, t, c, n = (1, 64, 256, 16) if rehearse else (1, 8192, 5120, 16)
    force = "interpret" if rehearse else None
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = lambda x: x.astype(jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)
    ops = (bf(jax.random.normal(ks[0], (b, t, c))),
           bf(jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 3.0)),
           -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n))),
           bf(jax.random.normal(ks[3], (b, t, n))),
           bf(jax.random.normal(ks[4], (b, t, n))),
           jax.random.normal(ks[5], (c,)))
    dy = bf(jax.random.normal(ks[6], (b, t, c)))

    def both(scan, *a):
        y, vjp = jax.vjp(scan, *a)
        return (y,) + vjp(dy.astype(y.dtype))

    kernels = jax.jit(functools.partial(both, functools.partial(
        ss.selective_scan, force=force)))
    steps = jax.jit(functools.partial(both, ss.scan_steps))
    t0 = time.perf_counter()
    text = "" if rehearse else compiled_text(kernels, *ops)
    ms, got, kinds = _device_ms(kernels, ops, 4, rehearse, "scan")
    with jax.default_matmul_precision("highest"):
        ms_steps, want, _ = _device_ms(steps, tuple(f32(x) for x in ops), 1,
                                       rehearse, "scan_steps")
    errs = _far(got, want)
    # the state is float32: the step form on the same values with its
    # state HELD in bfloat16 between steps lies well further from the
    # float32 one than the kernels on the same float32 values (their
    # y on bf16 operands is rounded once, at 2^-9 of its value)
    ops32 = tuple(f32(x) for x in ops)
    held_low = _far([jax.jit(functools.partial(
        ss.scan_steps, state_dtype=jnp.bfloat16))(*ops32)], want[:1])[0]
    exact = _far([jax.jit(functools.partial(
        ss.selective_scan, force=force))(*ops32)], want[:1])[0]
    # one forward and one backward: 8 [T, C] and 6 [T, N] bf16 values
    need = (8 * c + 6 * n) * t * b * 2
    log("[scan] s/dt [%d, %d, %d] bf16, %d states: y %.3e ds %.3e ddt %.3e "
        "dA %.3e dB %.3e dC %.3e dD %.3e from the float32 step form "
        "(%.1f s); forward + backward %.3f ms a call on the device (%s; "
        "floor %.3f ms at the HBM peak), the step form %.3f ms; y of the "
        "kernels on the same values in float32 %.3e, of the step form "
        "with a bfloat16 state %.3e" % (
            b, t, c, n, *errs, time.perf_counter() - t0, ms,
            ", ".join("%s %.3f" % kv for kv in kinds.most_common(4)),
            need / 819e9 * 1e3, ms_steps, exact, held_low))
    assert max(errs) <= FLASH_GRAD_TOL, errs
    assert exact <= 1e-4 and held_low >= 10 * exact, (exact, held_low)
    if not rehearse:
        assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
        assert " while(" not in text


def phase_ssd(seed, rehearse):
    """The state-space-dual scan's kernel pair (ISSUES 62, 64) at both
    cells' shapes, 64 heads of 64 with 128 states over 8,192 rows: 8
    groups of 8 heads (a grid step walks a group) and ONE group of 64
    (a grid step walks a block of 8, dB and dC summed over the group's
    eight blocks in VMEM): y and the gradients of all six inputs against
    the jax.numpy chunk walk on the same bfloat16 values, and the
    device ms a call by kernel. Runs where asked for by name."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssd_scan as ssd
    bsz, t, h, p, n = (1, 160, 16, 64, 16) if rehearse \
        else (1, 8192, 64, 64, 128)
    chunk = 128                # the kernels walk whole lane tiles
    bf = lambda v: v.astype(jnp.bfloat16)
    for g in ((2, 1) if rehearse else (8, 1)):
        ks = jax.random.split(jax.random.PRNGKey(seed + g), 7)
        ops = (bf(jax.random.normal(ks[0], (bsz, t, h, p))),
               jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, h)) - 4.0),
               -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.7)),
               bf(0.3 * jax.random.normal(ks[3], (bsz, t, g, n))),
               bf(0.3 * jax.random.normal(ks[4], (bsz, t, g, n))),
               jnp.ones((h,)))
        dy = bf(jax.random.normal(ks[5], (bsz, t, h, p)))

        def both(force, *a):
            y, vjp = jax.vjp(functools.partial(
                ssd.ssd_scan, chunk=chunk, force=force), *a)
            return (y,) + vjp(dy.astype(y.dtype))

        kernels = jax.jit(functools.partial(
            both, "interpret" if rehearse else "pallas"))
        t0 = time.perf_counter()
        text = "" if rehearse else compiled_text(kernels, *ops)
        ms, got, kinds = _device_ms(kernels, ops, 4, rehearse, "ssd")
        want = jax.jit(functools.partial(both, "chunked"))(*ops)
        errs = _far(got, [w.astype(jnp.float32) for w in want])
        log("[ssd] x [%d, %d, %d, %d] bf16, %d states, %d group(s) of %d "
            "heads, %d a grid step: y %.3e dx %.3e ddt %.3e dA %.3e dB "
            "%.3e dC %.3e dD %.3e from the jax.numpy chunk walk (%.1f s); "
            "forward + backward %.3f ms a call on the device (%s)" % (
                bsz, t, h, p, n, g, h // g, ssd._block_heads(h // g, p),
                *errs, time.perf_counter() - t0, ms,
                ", ".join("%s %.3f" % kv for kv in kinds.most_common(4))))
        assert max(errs) <= FLASH_GRAD_TOL, errs
        if not rehearse:
            assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


def conv_times(c, biased, seed, rehearse, t=8192, calls=8):
    """One shape of `phase_conv`: x [1, t, c] bf16 under 4 taps. ({"fwd
    pallas", "bwd pallas", "fwd taps", "bwd taps": device ms a call},
    the largest differences of y, dx, dw, dbias from the jax.numpy
    path's as shares of the largest value). The backward is forward +
    backward in one executable less the forward's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import selective_scan as SS
    from paddle_tpu.ops import ssm_conv
    ks = jax.random.split(jax.random.PRNGKey(seed + c), 4)
    x = jax.random.normal(ks[0], (1, t, c)).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[1], (1, t, c)).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[2], (4, c), minval=-0.5, maxval=0.5)
    args = (x, w) + ((jax.random.uniform(ks[3], (c,), minval=-0.5,
                                         maxval=0.5),) if biased else ())

    def taps(x, w, bias=None):          # the module's own path off a TPU
        was, SS._on_tpu = SS._on_tpu, lambda x: False
        try:
            return SS.causal_conv_silu(x, w, bias)
        finally:
            SS._on_tpu = was

    def pallas(x, w, bias=None):
        return ssm_conv.conv_silu(x, w, bias, interpret=rehearse)

    programs = {}
    for path, fn in (("pallas", pallas), ("taps", taps)):
        def fwd(*a, fn=fn):
            return fn(*a)

        def both(dy, *a, fn=fn):
            y, pull = jax.vjp(fn, *a)
            return (y,) + pull(dy)

        fwd.__name__ = "conv_fwd_%s_%d" % (path, c)
        both.__name__ = "conv_both_%s_%d" % (path, c)
        programs[fwd.__name__] = (jax.jit(fwd), args)
        programs[both.__name__] = (jax.jit(both), (dy,) + args)
    ms = _device_ms_each(programs, calls, rehearse, "conv")
    out = {}
    for path in ("pallas", "taps"):
        f, fb = (ms["conv_%s_%s_%d" % (kind, path, c)]
                 for kind in ("fwd", "both"))
        out["fwd " + path], out["bwd " + path] = f, fb - f
    got, want = (programs["conv_both_%s_%d" % (path, c)]
                 for path in ("pallas", "taps"))
    errs = _far(got[0](*got[1]),
                [v.astype(jnp.float32) for v in want[0](*want[1])])
    return out, errs


def phase_conv(seed, rehearse):
    """The Program op ssm_conv's kernel pair (ISSUE 65) against the
    jax.numpy path at the shapes of the four cells that run it: one
    sequence of 8,192 rows under 4 taps, bfloat16; 4,096 and 128
    channels with a bias (granite4hmicro_train_T8k's x and its B_t or
    C_t; nemotron3nano_train_T8k's x), 1,024 (Nemotron's B_t or C_t),
    5,120 (phi4flash_train_T8k), 1,440 and 2,880 (olmohybrid_train_T8k's
    q or k and v: not whole lane tiles, the last block of 512 lanes partly
    outside the array) without.
    Device ms a call of each direction of both paths beside the time of
    the bytes at 819 GB/s (forward: x read and y written; backward: x
    and dy read, dx written), and the largest difference of y, dx, dw
    and dbias. Runs where asked for by name."""
    shapes = ((256, True, 80), (96, False, 80)) if rehearse else (
        (4096, True, 8192), (128, True, 8192), (1024, False, 8192),
        (5120, False, 8192), (1440, False, 8192), (2880, False, 8192))
    for c, biased, t in shapes:
        ms, errs = conv_times(c, biased, seed, rehearse, t,
                              2 if rehearse else 8)
        array = t * c * 2 / 819e6       # ms to move one bf16 array
        log("[conv] x [1, %d, %d] bf16, 4 taps, %s: forward %.3f ms "
            "(jax.numpy %.3f; the bytes %.3f), backward %.3f ms (jax.numpy "
            "%.3f; the bytes %.3f): %.0f and %.0f GB/s; y %.2e dx %.2e dw "
            "%.2e%s from the jax.numpy path's" % (
                t, c, "a bias" if biased else "no bias", ms["fwd pallas"],
                ms["fwd taps"], 2 * array, ms["bwd pallas"], ms["bwd taps"],
                3 * array, 2 * array * 819 / ms["fwd pallas"],
                3 * array * 819 / ms["bwd pallas"], *errs[:3],
                " dbias %.2e" % errs[3] if biased else ""))
        # a bfloat16 result may round the other way: 2^-8 of a value
        assert max(errs[:2]) <= 2 ** -7 and max(errs[2:]) <= 1e-4, errs
    if rehearse:
        log("[conv] (REHEARSAL: a CPU's times, no device number)")


def phase_delta(seed, rehearse):
    """The gated delta rule's kernel pair (ISSUE 54) at the cell
    olmohybrid_train_T8k's shape: o and the five gradients of
    ``delta_rule_fwd`` / ``delta_rule_bwd`` against the jax.numpy chunk
    walk and its autodiff on the same values, bf16 operands and
    float32 ones (where the two must agree to float32 rounding: the
    state, the solve and the decays are float32 in both), and the
    device ms of both, forward and forward + backward, at chunks of 64
    and of 128 rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_rule as dr
    b, t, h, d_k, d_v = (1, 96, 2, 8, 16) if rehearse \
        else (1, 8192, 15, 96, 192)
    force = "interpret" if rehearse else "pallas"
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
    ops32 = (unit(jax.random.normal(ks[0], (b, t, h, d_k))) * d_k ** -0.5,
             unit(jax.random.normal(ks[1], (b, t, h, d_k))),
             jax.random.normal(ks[2], (b, t, h, d_v)),
             -0.1 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h))),
             2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))
    ops16 = tuple(x.astype(jnp.bfloat16) for x in ops32[:3]) + ops32[3:]
    do = jax.random.normal(ks[5], (b, t, h, d_v))

    def both(rule, *a):
        o, vjp = jax.vjp(rule, *a)
        return (o,) + vjp(do.astype(o.dtype))

    def forms(chunk, path):
        rule = functools.partial(dr.gated_delta_rule, chunk=chunk,
                                 force=path)
        return jax.jit(rule), jax.jit(functools.partial(both, rule))

    f32 = lambda xs: [x.astype(jnp.float32) for x in xs]
    for chunk in (16, 32) if rehearse else (64, 128):
        t0 = time.perf_counter()
        fwd, fb = forms(chunk, force)
        walk_fwd, walk_fb = forms(chunk, "chunked")
        text = "" if rehearse else compiled_text(fb, *ops16)
        ms_f, _, _ = _device_ms(fwd, ops16, 4, rehearse, "delta_f")
        ms, got, kinds = _device_ms(fb, ops16, 4, rehearse, "delta_fb")
        ms_wf, _, _ = _device_ms(walk_fwd, ops16, 2, rehearse, "delta_wf")
        ms_w, want, _ = _device_ms(walk_fb, ops16, 2, rehearse, "delta_w")
        errs = _far(got, f32(want))
        exact = _far(fb(*ops32), f32(walk_fb(*ops32)))
        log("[delta] q/k [%d, %d, %d x %d] v [.. x %d] chunks of %d: bf16 "
            "operands o %.3e dq %.3e dk %.3e dv %.3e dg %.3e dbeta %.3e "
            "from the jax.numpy walk, float32 operands %s (%.1f s); "
            "forward %.3f ms, forward + backward %.3f ms a call on the "
            "device (%s); the walk %.3f and %.3f ms" % (
                b, t, h, d_k, d_v, chunk, *errs,
                " ".join("%.2e" % e for e in exact),
                time.perf_counter() - t0, ms_f, ms,
                ", ".join("%s %.3f" % kv for kv in kinds.most_common(5)),
                ms_wf, ms_w))
        assert max(errs) <= FLASH_GRAD_TOL, errs
        assert max(exact) <= 2e-5, exact
        if not rehearse:
            assert "delta_rule_fwd" in text and "delta_rule_bwd" in text
            assert " while(" not in text


def phase_diff(seed, rehearse):
    """Differential attention through the streamed kernels (ISSUE 40)
    at the cell phi4flash_train_T8k's heads: 40 query and 20 key/value
    heads of 64, values of 128, T 4096, full and under a window of 512:
    a1, a2 and dq, dk, dv against the two softmaxes written out in
    dense float32 math, a differential head at a time; then forward +
    backward device ms at the cell's T 8192."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import flash_attention as fa
    t, h, hkv, d, window = (256, 8, 4, 64, 100) if rehearse else (
        4096, 40, 20, 64, 512)
    rng = np.random.RandomState(seed)
    mk = lambda rows, n: jnp.asarray(rng.randn(1, rows, n * d) * 0.5,
                                     jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)

    def attend(w):
        def loss(dy, q, k, v):
            a = jnp.stack(fa.flash_diff_bthd(q, k, v, h, hkv, window=w))
            return (f32(a) * f32(dy)).sum(), a
        return loss

    def written_out(w):
        def loss(dy, q, k, v):
            rows = q.shape[1]
            ahead = jnp.arange(rows)[:, None] - jnp.arange(rows)[None, :]
            seen = (ahead >= 0) if w is None else (ahead >= 0) & (ahead < w)
            qh, kh, vh = (x.reshape(rows, -1, d) for x in (q[0], k[0], v[0]))

            def head(p):
                r = p // (h // hkv)
                value = jnp.concatenate([vh[:, 2 * r], vh[:, 2 * r + 1]], -1)
                return jnp.stack([jax.nn.softmax(jnp.where(
                    seen, qh[:, 2 * p + turn] @ kh[:, 2 * r + turn].T
                    * d ** -0.5, -jnp.inf), -1) @ value
                    for turn in (0, 1)])            # [2, T, 2D]
            a = jax.lax.map(head, jnp.arange(h // 2))     # [P, 2, T, 2D]
            a = a.transpose(1, 2, 0, 3).reshape(2, 1, rows, h * d)
            return (a * dy).sum(), a
        return loss

    t0 = time.perf_counter()
    for w in (None, window):
        dy = jnp.asarray(rng.randn(2, 1, t, h * d) * 0.5, jnp.bfloat16)
        q, k, v = mk(t, h), mk(t, hkv), mk(t, hkv)
        before = sum(n for key, n in fa._LOWERINGS.snapshot().items()
                     if "dense" in key)
        kernel = jax.jit(jax.value_and_grad(attend(w), (1, 2, 3),
                                            has_aux=True))
        (_, out), got = kernel(dy, q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, ref), want = jax.jit(jax.value_and_grad(
                written_out(w), (1, 2, 3), has_aux=True))(
                    f32(dy), f32(q), f32(k), f32(v))
        errs = _far((out,) + got, (ref,) + want)
        dense = sum(n for key, n in fa._LOWERINGS.snapshot().items()
                    if "dense" in key) - before
        log("[diff] q [1, %d, %d] k/v [.., %d] bf16, window %s: a1/a2 "
            "%.3e dq %.3e dk %.3e dv %.3e from the two softmaxes in dense "
            "float32 math (%.1f s); dense lowerings %d" % (
                t, h * d, hkv * d, w, *errs, time.perf_counter() - t0,
                dense))
        assert max(errs) <= FLASH_GRAD_TOL, errs
        assert rehearse or not dense
    t = 512 if rehearse else 8192
    dy = jnp.asarray(rng.randn(2, 1, t, h * d) * 0.5, jnp.bfloat16)
    q, k, v = mk(t, h), mk(t, hkv), mk(t, hkv)
    for w in (None, window):
        call = jax.jit(jax.grad(attend(w), (1, 2, 3), has_aux=True))
        ms, _, kinds = _device_ms(call, (dy, q, k, v), 4, rehearse, "diff")
        log("[diff] T %d, window %s: forward + backward %.3f ms a call on "
            "the device (%s)" % (t, w, ms, ", ".join(
                "%s %.3f" % kv for kv in kinds.most_common(6))))


def phase_rotary(seed, rehearse):
    """The kernel pair that norms and turns q and k, at the block-
    diffusion cell's shapes (ISSUE 33: heads of 128, wrap L) and at
    `lfm2_train_T32k`'s (ISSUE 50: 32 and 8 heads of 64, two to a lane
    tile, T 32,768): output, dx and dScale against the same math in
    float32 on the heads' view, and a call's device ms under the
    profiler, forward and backward, beside the bytes it must move at
    the HBM peak (x in and out forward; dy and x in, dx out backward).
    Heads of 64 time the jax.numpy form too, the one they left."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import rotary
    f32 = lambda x: x.astype(jnp.float32)
    rng = np.random.RandomState(seed)

    def both(turn, x, w, dy):
        out, vjp = jax.vjp(turn, x, w)
        return (out,) + vjp(dy)

    shapes = ((2, 64, 4, 128, 32), (2, 64, 2, 128, 32), (1, 128, 4, 64, 0),
              (1, 128, 2, 64, 0)) if rehearse else (
        (2, 8192, 32, 128, 4096), (2, 8192, 4, 128, 4096),
        (1, 32768, 32, 64, 0), (1, 32768, 8, 64, 0))
    for b, t, n_head, d, wrap in shapes:
        x, dy = (jnp.asarray(rng.randn(b, t, n_head * d) * 0.5, jnp.bfloat16)
                 for _ in range(2))
        w = jnp.asarray(1.0 + 0.1 * rng.randn(d), jnp.float32)
        turn = lambda x, w, force=None: rotary.norm_rope(
            x, w, n_head, 1e6, wrap, 1e-6, force=force)
        want = jax.jit(functools.partial(both, functools.partial(
            turn, force="xla")))(f32(x), w, f32(dy))
        floor = [k * x.size * 2 / 819e9 * 1e3 for k in (2, 3)]
        for force in (None, "xla") if d < 128 else (None,):
            forward = jax.jit(functools.partial(turn, force=force))
            step = jax.jit(functools.partial(both, functools.partial(
                turn, force=force))).lower(x, w, dy).compile()
            errs = [float(jnp.max(jnp.abs(f32(a) - r)) / jnp.max(jnp.abs(r)))
                    for a, r in zip(step(x, w, dy), want)]
            ms = [_device_ms(fn, args, 8, rehearse, "rotary")[0]
                  for fn, args in ((forward, (x, w)), (step, (x, w, dy)))]
            log("[rotary] x [%d, %d, %d x %d] bf16, wrap %d, %s: out %.3e "
                "dx %.3e dScale %.3e from float32 math; a call %.3f ms "
                "forward (floor %.3f), %.3f ms backward (floor %.3f)%s" % (
                    b, t, n_head, d, wrap,
                    "the jax.numpy form" if force else "the kernels", *errs,
                    ms[0], floor[0], ms[1] - ms[0], floor[1],
                    "  (REHEARSAL: a CPU's time, no device number)"
                    if rehearse else ""))
            assert max(errs) <= FLASH_GRAD_TOL, errs
            if not rehearse:
                text = step.as_text()
                assert ("qk_norm_rope_fwd" in text) == (force is None)
                assert ("qk_norm_rope_bwd" in text) == (force is None)


def phase_experts(seed, rehearse):
    """The dropless expert layer against every held expert evaluated
    densely (float32, `highest`), output and gradients, under the
    router's own choices and with every row sent to held experts, at
    the shapes of the two routed cells (ISSUE 35: the rows go back to
    their tokens by the kernel of ops/moe_rows.py); and a chunk's
    forward and written backward (ISSUE 47) with NaN in the rows, the
    cotangents and the weights of the tail past its pairs, which must
    reach nothing."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    shapes = [(256, 256, 128, 16, 4, 4, "softmax", 1.0)] if rehearse else [
        (16384, 2048, 768, 128, 16, 8, "softmax", 1.0),
        (4096, 3584, 1024, 64, 8, 4, "sigmoid", 2.0)]
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    bf16 = lambda a: a.astype(jnp.bfloat16)
    rel = lambda a, r: float(jnp.max(jnp.abs(a.astype(jnp.float32) - r))
                             / jnp.max(jnp.abs(r)))
    for n, d, f, e, held, k, score, scaling in shapes:
        x = mk(n, d)
        wr = mk(d, e) * 0.02
        wg, wu, wd = (mk(held, d, f) * d ** -0.5, mk(held, d, f) * d ** -0.5,
                      mk(held, f, d) * f ** -0.5)
        # the series' label `rows`: what adds a chunk's rows
        rows_at = moe._LOWERINGS.label_names.index("rows")
        by_kernel = lambda: sum(
            v for key, v in moe._LOWERINGS.snapshot().items()
            if key[rows_at] == ("interpret" if rehearse else "pallas"))
        was = by_kernel()

        def layer(x, wr, wg, wu, wd):
            return moe.routed_experts(x, wr, bf16(wg), bf16(wu), bf16(wd), e,
                                      0, k, score=score, scaling=scaling)

        def dense(x, wr, wg, wu, wd):
            _, w, idx = moe.route(x, wr, k, True, score=score,
                                  scaling=scaling)
            out = 0.0
            for i in range(held):
                w_i = jnp.sum(jnp.where(idx == i, w, 0.0), 1)[:, None]
                out = out + w_i * (
                    (jax.nn.silu(x @ wg[i]) * (x @ wu[i])) @ wd[i])
            return out

        for routing in ("the router's own", "every row on held experts"):
            if routing != "the router's own":
                x = x.at[:, 0].set(8.0)
                wr = (wr * 0.01).at[0, :k].set(5.0)
            t0 = time.perf_counter()
            out, _, counts, _ = jax.jit(layer)(x, wr, wg, wu, wd)
            pairs = int(counts[:held].sum())
            sq = lambda fn: lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()
            got = jax.jit(jax.grad(sq(lambda *a: layer(*a)[0]),
                                   (0, 2, 3, 4)))(x, wr, wg, wu, wd)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(dense)(x, wr, wg, wu, wd)
                want = jax.jit(jax.grad(sq(dense), (0, 2, 3, 4)))(
                    x, wr, wg, wu, wd)
            errs = [rel(out, ref)] + [rel(a, r) for a, r in zip(got, want)]
            log("[experts] %d rows of %d, %d of %d experts held, %s top-%d, "
                "%s: %d pairs on held experts; out %.3e dx %.3e dgate %.3e "
                "dup %.3e ddown %.3e from the dense float32 layer (%.1f s)"
                % (n, d, held, e, score, k, routing, pairs, *errs,
                   time.perf_counter() - t0))
            assert int(counts.sum()) == n * k
            if routing != "the router's own":
                assert pairs == n * k, "a held pair was dropped"
            assert max(errs) <= EXPERT_TOL, errs
        assert by_kernel() > was, "the layer did not take the row kernel"

        # a chunk's tail past its pairs holds other experts' rows, their
        # cotangents and their weights, gathered as they are (ISSUE 47:
        # the written backward masks none of them), and might hold
        # anything: NaN there must reach no row under the pairs and no
        # gradient of the grouped matmuls
        cap = min(4096, n)
        sizes = jnp.full((held,), cap // (2 * held), jnp.int32)
        live = int(sizes.sum())
        xs, dy, w = bf16(mk(cap, d)), bf16(mk(cap, d)), mk(cap)
        w_gu = jnp.concatenate([bf16(wg), bf16(wu)], axis=2)

        from paddle_tpu.ops import grouped_matmul
        kernels = grouped_matmul.choose(
            cap, (d, f), xs, "interpret" if rehearse else None)
        assert kernels[0] != "xla", kernels
        for matmuls in (kernels, ("xla", 0)):
            def pull(xs, dy, w):
                y = moe._swiglu_experts(xs, w_gu, bf16(wd), sizes,
                                        matmuls=matmuls)
                dw, dxs, *dws = moe._swiglu_experts_bwd(
                    xs, dy, w, w_gu, bf16(wd), sizes, matmuls=matmuls)
                return (y[:live], dw[:live], dxs[:live]) + tuple(dws)

            clean = jax.jit(pull)(xs, dy, w)
            dirty = jax.jit(pull)(*(a.at[live:].set(jnp.nan)
                                    for a in (xs, dy, w)))
            worst = max(float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(clean, dirty))
            log("[experts] NaN in the %d places past %d pairs of a chunk "
                "(rows, cotangents, weights), the grouped matmuls by %s: "
                "their output and the written backward's six results move "
                "by %.1e" % (cap - live, live, matmuls, worst))
            assert worst == 0.0, worst
    phase_grouped(seed, rehearse)


# The seven routed cells' expert layers as their steps run them (ISSUE
# 63): rows a step, d, the hidden width (an ungated expert's padded,
# `moe.hidden_width`), whether a gate stands beside the up projection,
# experts held, experts routed over, top-k.
GROUPED_CELLS = [
    ("nemotron3nano_train_T8k", 8192, 2688, 2048, False, 8, 128, 6),
    ("xing4_train_T4k", 4096, 3584, 1024, True, 8, 64, 4),
    ("joyai_train_T8k", 8192, 2048, 768, True, 16, 256, 8),
    ("sdar_train_bd4k", 16384, 2048, 768, True, 16, 128, 8),
    ("trinity_train_T16k", 16384, 2048, 1024, True, 8, 128, 8),
    ("smallthinker_train_T16k", 16384, 2560, 768, True, 16, 64, 6),
    ("lfm2_train_T32k", 32768, 2048, 1792, True, 8, 32, 4)]


def _device_ms_each(programs, calls, rehearse, name):
    """{label: device ms a call} of jitted `programs` {label: (fn,
    args)}: every program run `calls` times under ONE trace and told
    apart by its module's name, the median of its runs; in a rehearsal
    the host's clock."""
    import jax
    from chipbench import tracing
    trace_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "chiprun_out", name)
    for fn, args in programs.values():
        jax.block_until_ready(fn(*args))
    wall = {}
    tracing.start(trace_dir)
    for label, (fn, args) in programs.items():
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        wall[label] = (time.perf_counter() - t0) * 1e3 / calls
    tracing.stop()
    if rehearse:
        return wall
    runs = {}
    for row in tracing.load_rows(trace_dir):
        if row["plane"].startswith("/device:") \
                and row["line"] == tracing.MODULE_LINE:
            runs.setdefault(tracing.module_name(row["name"]), []).append(
                row["dur"] * 1e3)
    assert all(len(runs.get(label, ())) == calls for label in programs), {
        k: len(v) for k, v in runs.items()}
    return {label: float(np.median(runs[label])) for label in programs}


def phase_grouped(seed, rehearse, row_tiles=(128, 256, 512)):
    """The probe by rows an expert (ISSUE 63, ROADMAP queue 1 S6): the
    held experts' grouped matmuls ALONE at each routed cell's chunk,
    filled as uniform routing fills it (half, the rows drawn to the
    experts as a multinomial), the three orientations of a pass's seven
    products, two products each as the layer makes them: `rows` (up,
    down), `rows_t` (dh, dxs against the weights as they lie) and
    `by_expert` (dW_up, dW_down); XLA's `ragged-dot` kernels against
    the kernels of ops/grouped_matmul.py at row tiles of 128, 256 and
    512, device ms of the jitted pair under the profiler, and the
    share of the bf16 peak that the live rows' FLOPs make of it. Each
    kernel's results are held to XLA's on the rows that hold pairs."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.ops import grouped_matmul as gm
    from paddle_tpu.parallel import moe
    cells = [("tiny", 256, 128, 128, True, 4, 16, 4)] if rehearse \
        else GROUPED_CELLS
    path = "interpret" if rehearse else "pallas"
    calls = 1 if rehearse else 5
    rng = np.random.RandomState(seed % 2 ** 31)
    for cell, n, d, f, gated, held, e, k in cells:
        pairs = n * k
        cap = 2 * pairs * held // e
        expected = pairs // e
        sizes = jnp.asarray(rng.multinomial(
            expected * held, [1.0 / held] * held), jnp.int32)
        live = expected * held
        wide = 2 * f if gated else f
        keys = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 6)
        mk = lambda i, *shape: jax.random.normal(keys[i], shape,
                                                 jnp.bfloat16)
        xs, dy, h, dgu = mk(0, cap, d), mk(1, cap, d), mk(2, cap, f), \
            mk(3, cap, wide)
        w_gu, w_down = mk(4, held, d, wide), mk(5, held, f, d)

        operands = (sizes, xs, dy, h, dgu, w_gu, w_down)

        def pair(matmuls, which):
            # (the operands as arguments: a jitted closure would carry
            # them as constants of every program)
            def fn(sizes, xs, dy, h, dgu, w_gu, w_down):
                rd, rd_t, by_expert = moe._grouped(sizes, matmuls)
                if which == "rows":
                    return rd(xs, w_gu), rd(h, w_down)
                if which == "rows_t":
                    return rd_t(dy, w_down), rd_t(dgu, w_gu, jnp.bfloat16)
                return by_expert(xs, dgu), by_expert(h, dy)
            fn.__name__ = "%s_%s_%d" % (which, *matmuls)
            return jax.jit(fn)

        hows = [("xla", 0)] + [(path, tm) for tm in row_tiles
                               if cap % tm == 0]
        programs = {"%s_%s_%d" % (which, *m): (pair(m, which), operands)
                    for which in ("rows", "rows_t", "by_expert")
                    for m in hows}
        ms = _device_ms_each(programs, calls, rehearse, "grouped_trace")
        # the live rows' FLOPs of the two products of an orientation
        flops = 2 * live * d * (wide + f)
        chosen = gm.choose(cap, (d, f), xs,
                           "interpret" if rehearse else None)
        for which in ("rows", "rows_t", "by_expert"):
            want = programs["%s_xla_0" % which][0](*operands)
            for m in hows[1:]:
                got = programs["%s_%s_%d" % (which, *m)][0](*operands)
                for a, b in zip(got, want):
                    if which != "by_expert":
                        a, b = a[:live], b[:live]
                    err = float(jnp.max(jnp.abs(
                        a.astype(jnp.float32) - b.astype(jnp.float32)))
                        / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    assert err <= 1e-2, (cell, which, m, err)
            log("[grouped] %s: %d rows an expert (%d held, chunk %d, d %d, "
                "f %d%s) %s: XLA %.3f ms (%.1f%% of the peak)%s"
                % (cell, expected, held, cap, d, f,
                   "" if gated else " ungated", which,
                   ms["%s_xla_0" % which],
                   flops / 197e12 * 1e5 / ms["%s_xla_0" % which],
                   "".join("; tile %d %.3f ms (%.1f%%)" % (
                       m[1], ms["%s_%s_%d" % (which, *m)],
                       flops / 197e12 * 1e5 / ms["%s_%s_%d" % (which, *m)])
                       for m in hows[1:])))
        log("[grouped] %s: the layer takes %s; the six products (a pass "
            "makes the up projection's twice): XLA %.3f ms%s"
            % (cell, chosen, sum(ms["%s_xla_0" % w] for w in
                                 ("rows", "rows_t", "by_expert")),
               "".join("; tile %d %.3f ms" % (m[1], sum(
                   ms["%s_%s_%d" % (w, *m)]
                   for w in ("rows", "rows_t", "by_expert")))
                   for m in hows[1:])))
        # no pair at all (a chunk 0 that runs regardless): by_expert's
        # exact zeros, and nothing hangs on a grid of no visit
        if chosen[0] != "xla":
            def no_pair(sizes, xs, dy, h, w_gu, w_down):
                rd, rd_t, by_expert = moe._grouped(sizes * 0, chosen)
                return rd(xs, w_gu), rd_t(dy, w_down), by_expert(h, dy)

            none = jax.jit(no_pair)(sizes, xs, dy, h, w_gu, w_down)
            assert float(jnp.max(jnp.abs(none[2]))) == 0.0
    if rehearse:
        log("[grouped] (REHEARSAL: a CPU's times, no device number)")


def phase_rows(seed, rehearse):
    """Each row move of the expert layer alone (ISSUE 35), at the two
    routed cells' shapes with the chunk filled to the cell's share and
    filled whole: the gather into a chunk (bfloat16 rows; XLA's op on
    every path) and the scatter-add out of it (float32 rows times a
    weight), XLA's op over the whole chunk against the kernel of
    ops/moe_rows.py, which visits the places that hold pairs. The rows
    are sorted runs as the layer's are: ascending within an expert, a
    token coming again across experts. Times are device times of the
    jitted call under the profiler (`calls` dispatches; the
    accumulator donated, as the layer's loop carries it). GB/s counts
    the bytes of the places that hold pairs: a row read and written by
    the gather; a row of y read, a row of the accumulator read and
    written by the scatter-add."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_rows as mr
    cells = [("tiny", 64, 256, 128, 4, 70)] if rehearse else [
        ("sdar_train_bd4k", 16384, 2048, 32768, 16, 16000),
        ("xing4_train_T4k", 4096, 3584, 4096, 8, 2200)]
    path = "interpret" if rehearse else "pallas"
    calls = 2 if rehearse else 10
    rng = np.random.RandomState(seed)

    def timed(fn, *args, carried=False):
        """(ms a call, the last result): _device_ms's first two."""
        return _device_ms(fn, args, calls, rehearse, "rows_trace",
                          carried)[:2]

    for cell, n, d, cap, held, share in cells:
        x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
        y = jnp.asarray(rng.randn(cap, d), jnp.float32)
        scale = jnp.asarray(rng.rand(cap), jnp.float32)
        for count in (share, cap):
            run = count // held
            rows = np.concatenate(
                [np.sort(rng.choice(n, run, replace=False))
                 for _ in range(held)]
                + [rng.randint(0, n, cap - run * held)]).astype(np.int32)
            rows, count = jnp.asarray(rows), run * held
            cnt = jnp.asarray(count, jnp.int32)
            ms, _ = timed(jax.jit(lambda x, rows: x[rows]), x, rows)
            log("[rows] %s gather bf16 [%d, %d] -> %d places, %d hold "
                "pairs: XLA's op %.3f ms (%.0f GB/s on the pairs' bytes)"
                % (cell, n, d, cap, count, ms,
                   2 * count * d * 2 / 1e6 / ms))

            def xla_add(acc, y, rows, scale, c):
                return mr.scatter_add(acc, (n, d), y, rows, scale, c, "xla")

            def kernel_add(acc, y, rows, scale, c):
                return mr.scatter_add(acc, (n, d), y, rows, scale, c, path)

            xla_ms, want = timed(
                jax.jit(xla_add, donate_argnums=0),
                mr.zeros((n, d), "xla"), y, rows, scale, cnt, carried=True)
            ker_ms, got = timed(
                jax.jit(kernel_add, donate_argnums=0),
                mr.zeros((n, d), path), y, rows, scale, cnt, carried=True)
            leave = jax.jit(lambda a: mr.result(a, (n, d), jnp.float32, path))
            back_ms, got = timed(leave, got)
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            add_gb = 3 * count * d * 4 / 1e6
            log("[rows] %s scatter-add f32 %d places, %d hold pairs -> "
                "[%d, %d]: XLA's op %.3f ms (%.0f GB/s), the kernel %.3f ms "
                "(%.0f GB/s) + %.3f ms to leave the slab, once a pass; "
                "%d sums apart by %.1e" % (
                    cell, cap, count, n, d, xla_ms, add_gb / xla_ms, ker_ms,
                    add_gb / ker_ms, back_ms, calls + 1, err))
            assert err <= 1e-5, err
    if rehearse:
        log("[rows] (REHEARSAL: a CPU's times, no device number)")


def phase_embed(seed, rehearse):
    """The embedding's gradient alone (ISSUE 58): T float32 rows of d
    summed into a table [V, d] at their ids, at the shapes of five
    cells, the ids drawn uniformly (the benchmark's traffic: most ids
    distinct, the most rows to write) and Zipf-like (half the places on
    1% of the ids: a corpus's long runs). XLA's scatter-add (what
    `jnp.take`'s gradient is) against ops/embedding_grad.py's path: the
    sort of the ids, XLA's gather of dy by the order, and the kernel
    `embedding_grad_rows`; device times of the jitted call under the
    profiler, with the ops of the kernel's path apart. The kernel's
    table must equal, bit for bit, float32 additions in sorted stable
    order (numpy's, on the host); XLA's promises no order, so its
    distance is only reported."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import embedding_grad as eg
    cells = [("tiny", 200, 150, 256)] if rehearse else [
        ("smallthinker_train_T16k", 16384, 37984, 2560),
        ("olmohybrid_train_T8k", 8192, 12544, 3840),
        ("xing4_train_T4k", 4096, 16384, 3584),
        ("lfm2_train_T32k", 32768, 8192, 2048),
        ("opt350m_train", 8192, 50272, 1024)]
    path = "interpret" if rehearse else "pallas"
    calls = 2 if rehearse else 10
    rng = np.random.RandomState(seed + 58)
    for cell, t, v, d in cells:
        dy = rng.randn(t, d).astype(np.float32)
        draws = {"uniform": rng.randint(0, v, t),
                 "zipf-like": np.where(rng.rand(t) < 0.5,
                                       rng.randint(0, max(v // 100, 1), t),
                                       rng.randint(0, v, t))}
        for draw, ids in draws.items():
            ids = ids.astype(np.int32)
            args = (jnp.asarray(ids), jnp.asarray(dy))
            timed = lambda how: _device_ms(
                jax.jit(lambda ids, dy: eg.embedding_grad(ids, dy, v, how)),
                args, calls, rehearse, "embed_trace")
            xla_ms, xla, _ = timed("xla")
            ker_ms, got, ops = timed(path)
            order = np.argsort(ids, kind="stable")
            want = np.zeros((v, d), np.float32)
            np.add.at(want, ids[order], dy[order])
            apart = float(jnp.max(jnp.abs(xla - want)))
            log("[embed] %s %s: %d ids (%d distinct) into [%d, %d]: XLA's "
                "scatter-add %.3f ms, the sorted segment sum %.3f ms (%s); "
                "%.0f MB at 819 GB/s is %.3f ms; XLA's sums from the sorted "
                "order's by at most %.1e" % (
                    cell, draw, t, len(np.unique(ids)), v, d, xla_ms, ker_ms,
                    ", ".join("%s %.3f" % kv for kv in ops.most_common(5)),
                    (t + v) * d * 4 / 1e6, (t + v) * d * 4 / 819e6, apart))
            assert np.array_equal(np.asarray(got), want), (
                "the kernel's table is not the sorted stable sums")
    if rehearse:
        log("[embed] (REHEARSAL: a CPU's times, no device number)")


# --------------------------------------------------------------------------
def phase_train(cfg, seed, rehearse):
    """benchmarks/transformer.py's build, 5 steps on one batch."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import _normalize_feeds
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        avg_cost, _ = T.transformer_lm(
            vocab_size=cfg["vocab"], max_len=cfg["max_len"],
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_model=cfg["d_model"], d_inner=cfg["d_inner"], packed=True)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        fluid.amp.enable_amp()
        exe = fluid.Executor(_place(rehearse))
        exe.run(startup)
        feeds = _lm_batch(cfg, seed)
        loader = iter(fluid.reader.DeviceLoader(
            fluid.reader.repeat_feed(feeds, 5)))
        losses, block_ms, fetch_ms = [], [], []
        for step in range(5):
            feed = next(loader)
            t0 = time.perf_counter()
            loss, = exe.run(main, feed=feed, fetch_list=[avg_cost],
                            return_numpy=False)
            t_dispatch = time.perf_counter()
            jax.block_until_ready(loss)
            t_block = time.perf_counter()
            losses.append(float(np.asarray(loss)))  # device->host
            t_fetch = time.perf_counter()
            if step == 0:
                log("[train] first step (compile + run): %.1f s"
                    % (t_block - t0))
            else:
                block_ms.append(1e3 * (t_block - t0))
                fetch_ms.append(1e3 * (t_fetch - t0))
                log("[train] step %d: dispatch returned %.2f ms, "
                    "block_until_ready %.2f ms, +device->host "
                    "fetch %.2f ms" % (
                        step, 1e3 * (t_dispatch - t0), block_ms[-1],
                        fetch_ms[-1]))
        log("[train] losses: %s" % " ".join("%.4f" % x
                                            for x in losses))
        log("[train] step ms (smoke timing, steps 1-4): "
            "block_until_ready median %.2f, with fetch median %.2f "
            "-> block_until_ready %s" % (
                np.median(block_ms), np.median(fetch_ms),
                "BLOCKS (the fetch adds nothing to wait for)"
                if np.median(block_ms) > 0.9 * np.median(fetch_ms)
                else "DOES NOT BLOCK (the fetch did the waiting)"))
        want = math.log(cfg["vocab"])
        assert all(math.isfinite(x) for x in losses), losses
        assert abs(losses[0] - want) <= 0.05 * want, \
            "first loss %.4f is not within 5%% of ln(%d)=%.4f" % (
                losses[0], cfg["vocab"], want)
        assert losses[-1] < losses[0], \
            "loss did not fall: %r" % (losses,)
        (entry,) = [fn for key, fn in exe._cache.items()
                    if key[0] is main]
        with jax.default_device(exe.place.jax_device()):
            text = compiled_text(entry, _persistables(main, scope),
                                 _normalize_feeds(feeds)[0],
                                 jax.random.key(0))
        n_kernels = text.count("tpu_custom_call")
        log("[train] tpu_custom_call sites in the compiled step: %d"
            % n_kernels)
        if not rehearse:
            assert n_kernels > 0, \
                "no tpu_custom_call in the train step: the flash " \
                "kernel did not run (dense branch taken)"
        fluid.amp.enable_amp(False)
    peak_hbm("train")


# --------------------------------------------------------------------------
def _requests(cfg, seed):
    rng = np.random.RandomState(seed)
    lo, hi = cfg["prompt"]
    reqs = []
    for _ in range(cfg["requests"]):
        plen = int(rng.randint(lo, hi + 1))
        prompt = [1] + rng.randint(3, cfg["vocab"], plen - 1).tolist()
        reqs.append((prompt, cfg["max_new"]))
    return reqs


def _reference_logits(step, infer, prompt, toks):
    """The sequential path's logits for its NEXT token after emitting
    ``toks``: ``step`` is ``jit(model._step_logits)`` at batch 1 — the
    step ``sequential_generate`` jits — teacher-forced along ``prompt +
    toks``. Only used to judge a divergence."""
    import jax.numpy as jnp
    state = infer._init_state(1)
    for t, tok in enumerate(list(prompt) + list(toks)):
        logits, state = step(jnp.full((1,), tok, jnp.int32), state,
                             np.int32(t))
    return np.asarray(logits[0], np.float32)


# How far below the reference's top logit (relative to it) the engine's
# token may sit where the two streams part. The engine batches 8 rows
# where the baseline runs 1, so XLA may tile and fuse the same math
# differently and the last bits differ: float32 accumulation order, or
# one or two bfloat16 steps (2**-7 each). A random-weight model's top
# logits are bunched that closely — in bfloat16 they TIE exactly — and
# argmax then parts the streams. Anything further off is a fault.
NEAR_TIE = {"float32": 1e-5, "bfloat16": 2 * 2.0 ** -7}


def compare_with_sequential(label, infer, step, reqs, want, got):
    """Engine tokens must equal ``sequential_generate``'s. Where a
    request's streams part, the engine's token must be a near-tie of
    the reference's own choice there (NEAR_TIE, judged on ``step`` =
    ``jit(infer._step_logits)``); what follows a parting has another
    context and is not compared. Returns the count of exactly
    identical requests."""
    exact = 0
    for i, ((wt, _), (gt, _)) in enumerate(zip(want, got)):
        if gt == wt:
            exact += 1
            continue
        j = next(k for k, (a, b) in enumerate(zip(gt, wt)) if a != b)
        logits = _reference_logits(step, infer, reqs[i][0], wt[:j])
        top = float(logits.max())
        gap = top - float(logits[gt[j]])
        tol = NEAR_TIE[label] * abs(top)
        log("[serve %s] request %d parts from sequential at token %d: "
            "engine %d, sequential %d; the engine's token is %.3g below "
            "the reference's top logit %.4g (near-tie tolerance %.3g)"
            % (label, i, j, gt[j], wt[j], gap, top, tol))
        assert gap <= tol, \
            "%s engine request %d token %d is no near-tie of the " \
            "sequential baseline: a real divergence" % (label, i, j)
    return exact


def phase_serve(cfg, seed, rehearse):
    """The same model through TransformerLMInfer + serving.Engine with
    default options: float32 (block kernel) then bfloat16 (gather)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.transformer_infer import TransformerLMInfer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        T.transformer_lm(
            vocab_size=cfg["vocab"], max_len=cfg["max_len"],
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_model=cfg["d_model"], d_inner=cfg["d_inner"])
        fluid.Executor(_place(rehearse)).run(startup)
    reqs = _requests(cfg, seed)
    log("[serve] %d requests, prompt lengths %s, %d new tokens each"
        % (len(reqs), [len(p) for p, _ in reqs], cfg["max_new"]))

    for label, dtype in (("float32", None), ("bfloat16", jnp.bfloat16)):
        # float32 is checked at float32 matmul precision. The TPU's
        # default multiplies float32 operands in bfloat16 passes, and
        # that rounding depends on the batch shape: measured on the
        # chip (PERF.md, PR 21), at the default the float32 engine
        # parts from the batch-1 baseline at top-2 margins of 4e-4 to
        # 2e-3 with the block kernel AND with gather alike; at
        # "highest" both are token-identical. Set process-wide, not as
        # a context: the engine traces on its own thread.
        jax.config.update("jax_default_matmul_precision",
                          "highest" if dtype is None else None)
        # end_id past the vocab: a random model must not stop early
        infer = TransformerLMInfer(
            main, scope, cfg["n_layer"], cfg["n_head"], cfg["d_model"],
            cfg["max_len"], dtype=dtype, end_id=cfg["vocab"])
        t0 = time.perf_counter()
        want = serving.sequential_generate(infer, reqs)
        seq_s = time.perf_counter() - t0
        eng = serving.Engine(infer, slots=cfg["slots"],
                             name="smoke-" + label)
        try:
            t0 = time.perf_counter()
            eng.warmup()
            warm_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            handles = [eng.submit(p, m) for p, m in reqs]
            got = [h.result(timeout=900) for h in handles]
            wall_s = time.perf_counter() - t0
            # the decode step's LOWERED text, not its compiled one:
            # the engine's programs close over the weights, so their
            # executables (0.5-1.3 GB here) are refused by a bounded
            # persistent cache and compiling again costs 12-30 s. A
            # Pallas kernel is a custom call already at this level.
            avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                eng._state)
            n_kernels = eng._step_fn.lower(
                avals, eng._btab_all(), False).as_text().count(
                    "tpu_custom_call")
            path = ("gather" if not eng._block_kernel else
                    "block kernel (%s)" % (
                        "pallas, %d tpu_custom_call sites" % n_kernels
                        if n_kernels else "pallas interpret" if rehearse
                        else "lax"))
            log("[serve %s] attention path: %s" % (label, path))
            for i, (gt, _) in enumerate(got):
                log("[serve %s] request %d tokens: %s" % (label, i, gt))
            log("[serve %s] engine wall %.2f s (first use: prefill and "
                "activate compile inside), decode-step warmup compile "
                "%.2f s, sequential baseline %.2f s; ttft s: %s" % (
                    label, wall_s, warm_s, seq_s,
                    ["%.2f" % h.ttft for h in handles]))
            log("[serve %s] eng.stats: %s" % (label, json.dumps(
                eng.stats, sort_keys=True)))
            assert all(len(gt) == cfg["max_new"] for gt, _ in got)
            ref_step = jax.jit(infer._step_logits)
            exact = compare_with_sequential(label, infer, ref_step,
                                            reqs, want, got)
            log("[serve %s] identical to sequential: %s" % (
                label, "True (%d/%d requests)" % (exact, len(reqs))
                if exact == len(reqs) else
                "%d/%d requests exactly; the rest part at a near-tie "
                "of the reference's own logits" % (exact, len(reqs))))
            # once more, now that every program is compiled and the
            # radix cache holds the prompts: a wall with no compile in
            # it, and the prefix-cache path on the device
            hits0 = eng.stats["prefix_hit_tokens"]
            t0 = time.perf_counter()
            handles = [eng.submit(p, m) for p, m in reqs]
            again = [h.result(timeout=900) for h in handles]
            again_s = time.perf_counter() - t0
            exact = compare_with_sequential(label, infer, ref_step,
                                            reqs, want, again)
            log("[serve %s] second pass (compiled; %d prompt tokens "
                "from the prefix cache): wall %.2f s for %d tokens, "
                "ttft median %.3f s, time per output token median "
                "%.1f ms (smoke timings); %d/%d requests identical "
                "to sequential" % (
                    label, eng.stats["prefix_hit_tokens"] - hits0,
                    again_s, sum(len(t) for t, _ in again),
                    np.median([h.ttft for h in handles]),
                    1e3 * np.median([h.tpot for h in handles]),
                    exact, len(reqs)))
            if dtype is None:
                assert eng._block_kernel, \
                    "float32 engine did not default to the block kernel"
                if not rehearse:
                    assert n_kernels > 0, "no tpu_custom_call in the " \
                        "float32 decode step: paged kernel did not run"
            else:
                assert not eng._block_kernel, \
                    "bfloat16 un-quantized engine left the gather default"
        finally:
            eng.close()
            jax.config.update("jax_default_matmul_precision", None)
        peak_hbm("serve " + label)


# --------------------------------------------------------------------------
def phase_multichip(cfg, seed, rehearse):
    """transformer-large through ParallelExecutor on a dp2 x tp2 mesh,
    3 steps, against the one-device Executor run of the same program
    from the same initial parameters."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.core.executor import _normalize_feeds
    from paddle_tpu.models import transformer as T

    axes = {"dp": 2, "tp": 2}
    mesh = parallel.make_mesh(axes)
    strategy = parallel.DistributedStrategy(**axes)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    feeds = _lm_batch(cfg, seed)
    steps = 3
    with fluid.program_guard(main, startup):
        avg_cost, _ = T.transformer_lm_parallel(
            vocab_size=cfg["vocab"], max_len=cfg["max_len"],
            n_layer=cfg["n_layer"], n_head=cfg["n_head"],
            d_model=cfg["d_model"], d_inner=cfg["d_inner"],
            strategy=strategy)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
        fluid.amp.enable_amp()
        one, four = fluid.Scope(), fluid.Scope()
        exe = fluid.Executor(_place(rehearse))
        with fluid.scope_guard(one):
            exe.run(startup)
        for n, v in _persistables(main, one).items():
            four.set(n, np.array(np.asarray(v)))
        singles = []
        with fluid.scope_guard(one):
            for _ in range(steps):
                loss, = exe.run(main, feed=feeds,
                                fetch_list=[avg_cost])
                singles.append(float(np.asarray(loss)))
        peak_hbm("chips4 one-device baseline")
        pexe = fluid.ParallelExecutor(
            loss_name=avg_cost.name, main_program=main, mesh=mesh,
            scope=four)
        shardeds, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss, = pexe.run([avg_cost], feed=feeds)
            shardeds.append(float(np.asarray(loss)))
            step_s.append(time.perf_counter() - t0)
        log("[chips4] one-device losses: %s" % singles)
        log("[chips4] dp2 x tp2 losses:  %s" % shardeds)
        log("[chips4] step wall s (first compiles): %s"
            % ["%.2f" % s for s in step_s])
        # __graft_entry__.dryrun_multichip's loss tolerance for a
        # reduced-precision composition
        tol = 2e-3
        for i, (ls, lp) in enumerate(zip(singles, shardeds)):
            assert math.isfinite(lp), lp
            assert abs(lp - ls) <= tol * max(1.0, abs(ls)), \
                "step %d: sharded loss %r != one-device %r " \
                "(tol %g)" % (i, lp, ls, tol)
        # tp-sharded weights really occupy four devices, each
        # holding the shard its hint describes
        hints = sorted(main._sharding_hints.items())
        assert hints, "program carries no tp sharding hints"
        for name, spec in hints:
            arr = four.find_var(name)
            shards = arr.addressable_shards
            devs = {s.device for s in shards}
            assert len(devs) == 4, \
                "%s lives on %d device(s): %s" % (name, len(devs),
                                                  devs)
            want = tuple(dim // (mesh.shape[ax] if ax else 1)
                         for dim, ax in zip(arr.shape, spec))
            assert {s.data.shape for s in shards} == {want}, \
                (name, spec, [s.data.shape for s in shards])
        log("[chips4] %d tp-sharded weights, each on 4 distinct "
            "devices with the expected shard shapes" % len(hints))
        (entry,) = pexe._cache.values()
        feeds_dev = {k: jax.device_put(v, pexe._data_sharding())
                     for k, v in _normalize_feeds(feeds)[0].items()}
        text = compiled_text(entry, _persistables(main, four),
                             feeds_dev, jax.random.key(0))
        found = {c: text.count(c) for c in (
            "all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all", "tpu_custom_call")}
        log("[chips4] compiled step: %s" % found)
        assert found["all-reduce"] or found["reduce-scatter"], \
            "no collective in the compiled dp2 x tp2 step"
        if not rehearse:
            assert found["tpu_custom_call"], \
                "flash kernel missing from the sharded step"
        fluid.amp.enable_amp(False)
    peak_hbm("chips4")


def phase_hc(seed, rehearse):
    """The hyper-connections' stages alone (ISSUE 43) at the cell
    xing4_train_T4k's shape, a float32 stream [4096, 4 x 3584] and a
    bfloat16 y: "mix" and "merge", forward and backward, as the
    jax.numpy form and as the kernels of ops/hyper_connection.py, both
    on the chip: device ms under the profiler (the backward is forward
    and backward in one executable less the forward's), the passes over
    the stream that time is at the HBM's 819 GB/s beside the passes the
    stage must make (its bytes over the stream's 234.9 MB), GB/s on
    those bytes, and the largest difference of the kernels' values and
    gradients from the jax.numpy form's, as a share of the largest
    value. XLA's `copy` ops are left out of the times: a backward kernel
    overwrites the cotangent it is handed, which in a step is dead by
    then and here is still the probe's, so XLA copies it first."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import hyper_connection as hc
    n, d, rows, calls = (4, 128, 64, 2) if rehearse else (4, 3584, 4096, 8)
    c = n * (n + 2)
    rng = np.random.RandomState(seed)
    f32 = lambda *shape, scale=1.0: jnp.asarray(rng.randn(*shape) * scale,
                                                jnp.float32)
    x, g = f32(rows, n * d), f32(rows, n * d)
    proj, alpha = f32(n * d, c, scale=0.02), jnp.asarray([0.5, 0.7, 0.9])
    bias = f32(c, scale=0.3)
    y = f32(rows, d).astype(jnp.bfloat16)
    stream = x.size * 4
    kernels = "interpret" if rehearse else "pallas"

    def mix(path, x, proj, alpha, bias):
        return hc.mix_stage(x, proj, alpha, bias, n, 20, 1e-6,
                            (-30.0, 30.0), force=path)

    def merge(path, x, post, res, y):
        return hc.merge_stage(x, post, res, y, n, force=path)

    def forward(stage, *args):
        out = stage(*args)      # the stream "mix" hands through is x
        return out[:3] if stage.func is mix else out

    def both(stage, *args_and_cotangents):
        out, pull = jax.vjp(stage, *args_and_cotangents[:4])
        cots = args_and_cotangents[4:]
        return (out[:3], pull(cots)) if stage.func is mix \
            else (out, pull(cots[0]))

    _, post, res, _ = jax.jit(functools.partial(mix, "xla"))(
        x, proj, alpha, bias)
    dh, dpost, dres = f32(rows, d), f32(rows, n), f32(n, n, rows)
    # stage: its arguments, its cotangents, the passes over the stream
    # it must make forward and backward
    stages = {"mix": (mix, (x, proj, alpha, bias), (dh, dpost, dres, g),
                      1.25, 3.25),
              "merge": (merge, (x, post, res, y), (g,), 2.125, 3.25)}
    for name, (fn, args, cots, need_f, need_b) in stages.items():
        got = {}
        for path in ("xla", kernels):
            stage = functools.partial(fn, path)
            f_ms, _, f_ops = _device_ms(
                jax.jit(functools.partial(forward, stage)), args, calls,
                rehearse, "hc_trace")
            fb_ms, got[path], fb_ops = _device_ms(
                jax.jit(functools.partial(both, stage)), args + cots, calls,
                rehearse, "hc_trace")
            f_ms, fb_ms = f_ms - f_ops["copy"], fb_ms - fb_ops["copy"]
            for way, ms, need in (("forward", f_ms, need_f),
                                  ("backward", fb_ms - f_ms, need_b)):
                log("[hc] %s %s, %s: %.3f ms = %.2f passes over the %.1f MB "
                    "stream at 819 GB/s, where %.3f are needed (%.0f GB/s "
                    "on those bytes)" % (
                        name, way, path, ms, ms * 819e6 / stream,
                        stream / 1e6, need, need * stream / 1e6 / ms))
            top = lambda ops: ", ".join(
                "%s %.3f" % kv for kv in ops.most_common(6))
            log("[hc] %s %s ops, ms a call: forward %s; both %s" % (
                name, path, top(f_ops), top(fb_ops)))
        wide = lambda a: np.asarray(a, np.float32)
        leaves = jax.tree_util.tree_leaves
        errs = [(float(np.abs(wide(a) - wide(b)).max()
                       / (np.abs(wide(b)).max() + 1e-30)), str(b.dtype))
                for a, b in zip(leaves(got[kernels]), leaves(got["xla"]))]
        log("[hc] %s: the kernels' values and gradients from the jax.numpy "
            "form's, largest first: %s" % (name, " ".join(
                "%.1e (%s)" % e for e in sorted(errs)[::-1])))
        # a bfloat16 result (dy) may round the other way: 2^-8 of a value
        assert all(e <= (2e-5 if dtype == "float32" else 2 ** -7)
                   for e, dtype in errs), errs
    if rehearse:
        log("[hc] (REHEARSAL: a CPU's times, no device number)")


# --------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the ParallelExecutor phase on a "
                         "dp2 x tp2 mesh and its one-device baseline")
    ap.add_argument("--phases", default="",
                    help="comma separated: only these one-chip phases "
                         "(flash, gqa, own_block, mla, window, scan, ssd, "
                         "conv, delta, "
                         "diff, "
                         "rotary, "
                         "experts, grouped, "
                         "rows, embed, hc, "
                         "train, serve); all of them if not given")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny size, Pallas kernels in "
                         "interpret mode, no tpu_custom_call "
                         "assertions; the last line says platform cpu")
    args = ap.parse_args()

    from paddle_tpu import compile_cache
    cache_dir = compile_cache.configure()
    log("[cache] %d entries in %s at start"
        % (compile_cache.entries(cache_dir), cache_dir))
    t0 = time.perf_counter()
    device = phase_device(args.rehearse, args.chips, cache_dir)
    cfg = TINY if args.rehearse else REAL
    if args.rehearse:
        kernels_in_interpret_mode()
    log("[config] %s%s" % (json.dumps(cfg), "  (REHEARSAL, tiny)"
                           if args.rehearse else ""))
    if args.chips == 4:
        phase_multichip(cfg, args.seed, args.rehearse)
    else:
        phases = {"flash": phase_flash, "gqa": phase_gqa,
                  "own_block": phase_own_block, "mla": phase_mla,
                  "window": phase_window, "scan": phase_scan,
                  "ssd": phase_ssd, "conv": phase_conv,
                  "delta": phase_delta,
                  "diff": phase_diff, "rotary": phase_rotary,
                  "experts": phase_experts, "grouped": phase_grouped,
                  "rows": phase_rows, "embed": phase_embed,
                  "hc": phase_hc,
                  "train": functools.partial(phase_train, cfg),
                  "serve": functools.partial(phase_serve, cfg)}
        # (`experts` ends with `grouped`: not twice where all run; `ssd`
        # and `conv` are a cell's own checks, `tests/test_ssd_scan.py`
        # and `tests/test_ssm_conv_kernel.py` the CPU's)
        for name in (args.phases.split(",") if args.phases
                     else [name for name in phases
                           if name not in ("grouped", "ssd", "conv")]):
            phases[name](args.seed, args.rehearse)
    log("[cache] %d entries in %s at end"
        % (compile_cache.entries(cache_dir), cache_dir))
    log("[done] all phases passed in %.1f s" % (time.perf_counter() - t0))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
