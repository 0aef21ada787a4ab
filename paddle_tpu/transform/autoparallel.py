"""Automatic parallelism planner: dp/tp/pp/sp/ep search over a cost
model calibrated against PERF.md's measurements.

The five-axis ``DistributedStrategy`` composition has been "user picks"
since the parallel subsystem landed; systems like GSPMD and Alpa showed
a cost-model-driven search over parallelism assignments beats
hand-tuning on real topologies. We own both halves of the input
already: the static per-step FLOPs/bytes roll-up
(``analysis/cost.step_costs``) prices compute, and PERF.md's measured
numbers calibrate the analytic comm/bubble terms:

  * pipeline bubble — the GPipe useful fraction U(M) = M/(S+M-1);
    PERF.md round 3 measured throughput ratios tracking it within a few
    points across M in {1,2,4,8,16} (pp=4, 8-device virtual mesh).
  * DCN wire — the pserver tier pushes dense params at ~0.8 GB/s and
    pulls at ~0.9 GB/s (round-3 scatter-gather numbers); the sparse
    path ships only touched rows (131 KB vs 105 MB for the [200k x 64]
    benchmark table) and measured 7046 vs 335 samples/s.
  * ICI — mesh collectives (grad all-reduce on dp, Megatron per-layer
    all-reduces on tp, ring passes on sp, all-to-all on ep) price at an
    assumed per-link ICI bandwidth. The absolute constant is a
    placeholder until a chip round measures it; every ranking the tests
    pin is ordinal, and orderings are stable across plausible values.

API:  candidates(spec, devices)       valid strategy assignments
      rank(spec, devices)             -> [Plan] cheapest first;
                                      hbm_bytes= REJECTS candidates
                                      over per-chip capacity (params +
                                      optimizer state + paged-KV pool
                                      via kvpool.bytes_per_block —
                                      flag autoparallel_hbm_gb)
      plan_hbm_bytes(spec, axes)      the capacity term itself
      recommend(model, devices)       zoo surface (traces + prices)
      apply(plan, ...)                top plan -> configured
                                      ParallelExecutor + built program
      recommend_embedding_placement   sparse-vs-dense pserver wire call
CLI:  python -m paddle_tpu.transform --plan transformer 8
"""

import os

import numpy as np

# -- calibration constants (provenance: PERF.md) ---------------------------
# GPipe bubble: U(M) = M/(S+M-1), measured round 3 (pipeline bench table)
DCN_DENSE_PUSH_BPS = 0.8e9     # round 3: RPC push 52 MB at 0.8 GB/s
DCN_DENSE_PULL_BPS = 0.9e9     # round 3: RPC pull 52 MB at 0.9 GB/s
DCN_SPARSE_ROW_OVERHEAD = 8.0  # bytes per shipped row id (int64 index)
ICI_BPS = 45e9                 # assumed per-link ICI; ordinal use only
PEAK_FLOPS = 180e12            # per-chip peak for the compute term;
                               # cancels out of every same-device-count
                               # comparison, kept for readable seconds
# HBM capacity term (ISSUE 10): weights + grads + Adam m/v alongside
# the parameter shard — 4x the shard bytes total (1 + this multiplier)
OPTIMIZER_STATE_MULT = 3.0
KV_BLOCK_SIZE = 16             # pool granule priced per plan (matches
                               # the serving_block_size flag default)


_CALIB_CACHE = {}          # path -> (mtime, record)
_CALIB_WARNED = set()


def calibration():
    """(peak_flops, ici_bps, source) for the cost model. The
    ``autoparallel_calib`` flag names a ``calibrate.write_calibration``
    record; unset / unreadable falls back to the documented
    placeholders (a bad record warns once per path, never raises —
    rankings are ordinal either way)."""
    from .. import flags
    path = flags.get_flag("autoparallel_calib") or ""
    if not path:
        return PEAK_FLOPS, ICI_BPS, "placeholder"
    try:
        mtime = os.path.getmtime(path)
        cached = _CALIB_CACHE.get(path)
        if cached is None or cached[0] != mtime:
            from .calibrate import load_calibration
            _CALIB_CACHE[path] = (mtime, load_calibration(path))
        rec = _CALIB_CACHE[path][1]
    except Exception as e:
        if path not in _CALIB_WARNED:
            _CALIB_WARNED.add(path)
            import sys
            print("autoparallel_calib %r unusable (%s); using "
                  "placeholder constants" % (path, e), file=sys.stderr)
        return PEAK_FLOPS, ICI_BPS, "placeholder"
    peak = float(rec["peak_flops"])
    ici = rec.get("ici_bps")
    if ici:
        return peak, float(ici), "measured:%s" % path
    # single-device records carry no ring measurement: the comm terms
    # still price at the placeholder, and the provenance must say so
    return peak, ICI_BPS, "measured:%s (ici placeholder)" % path


def pipeline_utilization(m, s):
    """GPipe useful fraction U(M) = M/(S+M-1) — PERF.md round 3
    measured throughput ratios track this within a few points."""
    m, s = max(1, int(m)), max(1, int(s))
    return m / float(s + m - 1)


class ModelSpec:
    """Everything the cost model needs to price one model, detached
    from tracing so unit tests pin orderings with pure math.

    flops/bytes are per GLOBAL step (the analysis cost model's
    accounting); param_bytes the dense parameter footprint;
    act_bytes the per-layer boundary activation size (batch * seq *
    d_model * dtype) that tp all-reduces, sp ring-passes, ep
    all-to-alls and pp ships between stages."""

    def __init__(self, name, flops, bytes, param_bytes, batch, seq,
                 d_model, n_layer, n_head, num_experts=0,
                 dtype_bytes=4):
        self.name = name
        self.flops = float(flops)
        self.bytes = float(bytes)
        self.param_bytes = float(param_bytes)
        self.batch = int(batch)
        self.seq = int(seq)
        self.d_model = int(d_model)
        self.n_layer = int(n_layer)
        self.n_head = int(n_head)
        self.num_experts = int(num_experts)
        self.dtype_bytes = int(dtype_bytes)

    @property
    def act_bytes(self):
        return (self.batch * self.seq * self.d_model
                * float(self.dtype_bytes))


class Plan:
    """One priced strategy assignment, cheapest-first sortable."""

    def __init__(self, axes, microbatches, cost, breakdown,
                 hbm_bytes=None):
        self.axes = dict(axes)              # dp/tp/pp/sp/ep
        self.microbatches = int(microbatches)
        self.cost = float(cost)             # modeled seconds per step
        self.breakdown = dict(breakdown)
        self.hbm_bytes = hbm_bytes          # modeled per-chip bytes

    def strategy(self):
        from ..parallel import DistributedStrategy
        return DistributedStrategy(**self.axes)

    def mesh_axes(self):
        return {k: v for k, v in
                (("dp", self.axes["dp"]), ("pp", self.axes["pp"]),
                 ("sp", self.axes["sp"]), ("ep", self.axes["ep"]),
                 ("tp", self.axes["tp"]))
                if v > 1 or k == "dp"}

    def describe(self):
        ax = "x".join("%s%d" % (k, self.axes[k])
                      for k in ("dp", "tp", "pp", "sp", "ep")
                      if self.axes[k] > 1) or "dp1"
        mb = " M=%d" % self.microbatches if self.axes["pp"] > 1 else ""
        return "%s%s" % (ax, mb)

    def to_dict(self):
        out = {"axes": dict(self.axes),
               "microbatches": self.microbatches,
               "cost_s": self.cost,
               "breakdown": dict(self.breakdown),
               "describe": self.describe()}
        if self.hbm_bytes is not None:
            out["hbm_bytes"] = self.hbm_bytes
        return out

    def __repr__(self):
        return "Plan(%s, cost=%.3es)" % (self.describe(), self.cost)


def _factorizations(n, k):
    """All ordered k-tuples of positive ints whose product is n."""
    if k == 1:
        yield (n,)
        return
    for d in sorted(set(
            d for d in range(1, n + 1) if n % d == 0)):
        for rest in _factorizations(n // d, k - 1):
            yield (d,) + rest


def candidates(spec, devices):
    """Valid (strategy axes, microbatches) assignments for this model
    on ``devices`` chips. Validity mirrors what the model builders /
    mesh actually accept: every axis must divide its dimension (dp the
    batch, tp the head count and model dim, pp the layer count, sp the
    sequence, ep the expert count), and a pipeline schedule needs at
    least one microbatch per per-dp batch row."""
    devices = int(devices)
    out = []
    seen = set()
    for dp, tp, pp, sp, ep in _factorizations(devices, 5):
        if (dp, tp, pp, sp, ep) in seen:
            continue
        seen.add((dp, tp, pp, sp, ep))
        if spec.batch % dp:
            continue
        if tp > 1 and (spec.n_head % tp or spec.d_model % tp):
            continue
        if pp > 1 and spec.n_layer % pp:
            continue
        if sp > 1 and spec.seq % sp:
            continue
        if ep > 1 and (not spec.num_experts
                       or spec.num_experts % ep):
            continue
        axes = {"dp": dp, "tp": tp, "pp": pp, "sp": sp, "ep": ep}
        if pp > 1:
            per_dp = spec.batch // dp
            ms = [m for m in (1, 2, 4, 8, 16, 32)
                  if m <= per_dp and per_dp % m == 0]
            for m in ms or [1]:
                out.append((axes, m))
        else:
            out.append((axes, 1))
    return out


def plan_cost(spec, axes, microbatches=1,
              peak_flops=None, ici_bps=None):
    """Analytic per-step cost (seconds) of one strategy assignment:
    compute spread over every chip, inflated by the pipeline bubble
    1/U(M), plus the per-axis collective traffic at ICI rate. Each
    comm term uses the standard ring-collective volume for its
    collective (all-reduce 2(n-1)/n, all-to-all / ring pass (n-1)/n).
    Constants default to ``calibration()`` — a measured calib record
    when the ``autoparallel_calib`` flag names one, the documented
    placeholders otherwise."""
    if peak_flops is None or ici_bps is None:
        cal_peak, cal_ici, _ = calibration()
        peak_flops = cal_peak if peak_flops is None else peak_flops
        ici_bps = cal_ici if ici_bps is None else ici_bps
    dp, tp, pp, sp, ep = (axes["dp"], axes["tp"], axes["pp"],
                          axes["sp"], axes["ep"])
    n = dp * tp * pp * sp * ep
    util = pipeline_utilization(microbatches, pp) if pp > 1 else 1.0
    compute = spec.flops / (peak_flops * n) / util

    # per-chip shard of the dense params that dp replicates (tp/pp/ep
    # already shard them); ring all-reduce moves 2(dp-1)/dp of it
    dp_comm = 0.0
    if dp > 1:
        shard = spec.param_bytes / (tp * pp * max(1, ep))
        dp_comm = 2.0 * (dp - 1) / dp * shard / ici_bps
    # Megatron tp: one all-reduce per sublayer (2 per layer) of the
    # boundary activation, on each chip's dp/sp shard of the batch
    tp_comm = 0.0
    if tp > 1:
        act = spec.act_bytes / (dp * sp)
        tp_comm = (2.0 * spec.n_layer
                   * 2.0 * (tp - 1) / tp * act / ici_bps)
    # ring attention: K/V blocks circulate the sp ring once per layer
    sp_comm = 0.0
    if sp > 1:
        act = spec.act_bytes / (dp * tp)
        sp_comm = spec.n_layer * 2.0 * (sp - 1) / sp * act / ici_bps
    # MoE all-to-all: tokens scatter+gather across ep once per layer
    ep_comm = 0.0
    if ep > 1:
        act = spec.act_bytes / (dp * tp * sp)
        ep_comm = spec.n_layer * 2.0 * (ep - 1) / ep * act / ici_bps
    # pipeline point-to-point: each microbatch's activation crosses
    # every stage boundary (forward + backward)
    pp_comm = 0.0
    if pp > 1:
        act = spec.act_bytes / (dp * sp) / max(1, microbatches)
        pp_comm = (2.0 * (pp - 1) * microbatches * act / ici_bps)

    comm = dp_comm + tp_comm + sp_comm + ep_comm + pp_comm
    return compute + comm, {
        "compute_s": compute,
        "pipeline_util": util,
        "dp_comm_s": dp_comm, "tp_comm_s": tp_comm,
        "sp_comm_s": sp_comm, "ep_comm_s": ep_comm,
        "pp_comm_s": pp_comm,
    }


def plan_hbm_bytes(spec, axes, block_size=KV_BLOCK_SIZE,
                   optimizer_mult=OPTIMIZER_STATE_MULT,
                   kv_quant=None):
    """Modeled PER-CHIP HBM bytes of one assignment — the capacity
    term PR 9 left open (ISSUE 10): the dense parameter shard dp
    replicates (tp/pp/ep shard it) times (1 + optimizer_mult) for
    grads + Adam moments, plus the paged-KV pool a decode tier of the
    same shape reserves, priced with ``serving.kvpool.bytes_per_block``
    (each per-chip batch row keeps ceil(seq_shard / block_size) blocks
    of its layer/head shard). ``kv_quant`` (or ``spec.kv_quant`` when
    the caller leaves it None) prices an int8/fp8-quantized pool —
    the capacity filter then admits plans the dense pool would
    reject. Returns (total, breakdown)."""
    from ..serving.kvpool import bytes_per_block
    dp, tp, pp, sp, ep = (axes["dp"], axes["tp"], axes["pp"],
                          axes["sp"], axes["ep"])
    shard = spec.param_bytes / (tp * pp * max(1, ep))
    params = shard * (1.0 + float(optimizer_mult))
    dk = max(1, spec.d_model // max(1, spec.n_head))
    rows = max(1, spec.batch // dp)
    seq_shard = -(-spec.seq // sp)
    blocks = rows * (-(-seq_shard // int(block_size)))
    if kv_quant is None:
        kv_quant = getattr(spec, "kv_quant", None)
    kv = blocks * bytes_per_block(
        max(1, spec.n_layer // pp), max(1, spec.n_head // tp),
        block_size, dk, dtype_bytes=spec.dtype_bytes,
        kv_quant=kv_quant)
    return params + kv, {"hbm_param_bytes": params, "hbm_kv_bytes": kv}


def rank(spec, devices, peak_flops=None, ici_bps=None,
         hbm_bytes=None):
    """All valid plans for (spec, devices), cheapest first. Ties break
    on the axes tuple so the ranking is deterministic. ``hbm_bytes``
    (per-chip capacity) REJECTS over-capacity candidates instead of
    ranking them — an HBM-infeasible plan is not a slow plan, it is
    not a plan."""
    plans, rejected = [], 0
    for axes, m in candidates(spec, devices):
        hbm, hbm_bd = plan_hbm_bytes(spec, axes)
        if hbm_bytes is not None and hbm_bytes > 0 and hbm > hbm_bytes:
            rejected += 1
            continue
        cost, breakdown = plan_cost(spec, axes, m,
                                    peak_flops=peak_flops,
                                    ici_bps=ici_bps)
        breakdown.update(hbm_bd)
        plans.append(Plan(axes, m, cost, breakdown, hbm_bytes=hbm))
    plans.sort(key=lambda p: (p.cost,
                              tuple(sorted(p.axes.items())),
                              -p.microbatches))
    if not plans:
        if rejected:
            raise ValueError(
                "every valid assignment for %r on %d devices exceeds "
                "the %.2f GB per-chip HBM capacity (%d candidate(s) "
                "rejected) — raise autoparallel_hbm_gb or shard more"
                % (spec.name, devices, hbm_bytes / 1e9, rejected))
        raise ValueError(
            "no valid dp/tp/pp/sp/ep assignment for %r on %d devices "
            "(batch=%d heads=%d layers=%d seq=%d experts=%d)"
            % (spec.name, devices, spec.batch, spec.n_head,
               spec.n_layer, spec.seq, spec.num_experts))
    return plans


# -- zoo surface -----------------------------------------------------------

# models with a strategy-aware builder the planner can price AND apply
PLANNABLE = ("transformer",)


def model_spec(model, entry=None):
    """Trace + price one plannable zoo model into a ModelSpec: FLOPs
    and bytes from the analysis cost model over the real single-device
    train step, parameter bytes from the built Program."""
    ent = entry if entry is not None else _plan_entry(model)
    from ..analysis.cost import step_costs
    from ..models.harness import program_entry
    fn, args = program_entry(ent["build"], ent["feeds"])
    flops, nbytes = step_costs(fn, args)
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ent["build"]()
    param_bytes = 0.0
    for p in main.all_parameters():
        param_bytes += float(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
    return ModelSpec(
        model, flops=flops, bytes=nbytes, param_bytes=param_bytes,
        batch=ent["batch"], seq=ent["seq"], d_model=ent["d_model"],
        n_layer=ent["n_layer"], n_head=ent["n_head"],
        num_experts=ent.get("num_experts", 0))


def _plan_entry(model):
    if model not in PLANNABLE:
        raise KeyError(
            "model %r is not plannable (strategy-aware builders exist "
            "for: %s)" % (model, ", ".join(PLANNABLE)))
    import importlib
    mod = importlib.import_module("paddle_tpu.models.%s" % model)
    return mod.plan_entry()


def recommend(model, devices, top=None, spec=None, hbm_gb=None):
    """Ranked plans for a zoo model at a device count. ``spec`` skips
    the trace (tests / repeated calls). ``hbm_gb`` (default: the
    ``autoparallel_hbm_gb`` flag; 0 = off) rejects candidates whose
    modeled per-chip bytes (params + optimizer state + paged-KV pool)
    exceed the capacity."""
    if hbm_gb is None:
        from .. import flags
        hbm_gb = flags.get_flag("autoparallel_hbm_gb")
    spec = spec or model_spec(model)
    plans = rank(spec, devices,
                 hbm_bytes=hbm_gb * 1e9 if hbm_gb else None)
    return plans[:top] if top else plans


class AppliedPlan:
    """A plan instantiated for real: built program (strategy-aware),
    configured ParallelExecutor over the plan's mesh, startup already
    run. ``run(feed)`` executes one step and returns the fetches."""

    def __init__(self, plan, pexe, main, startup, fetch_vars, feed_fn,
                 scope):
        self.plan = plan
        self.pexe = pexe
        self.main = main
        self.startup = startup
        self.fetch_vars = fetch_vars
        self.feed_fn = feed_fn
        self.scope = scope

    def run(self, feed):
        return self.pexe.run(fetch_list=list(self.fetch_vars),
                             feed=feed)


def apply(plan, model, devices=None):
    """Instantiate a plan: build the model WITH the plan's strategy
    (fresh programs), make the mesh, init params, and hand back a
    configured ParallelExecutor — "framework solves" made executable.
    ``devices`` optionally restricts the jax device list."""
    import jax
    import paddle_tpu as fluid
    from ..parallel import make_mesh, ParallelExecutor

    ent = _plan_entry(model)
    strategy = plan.strategy()
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fetch_vars = ent["build"](strategy)
        if not isinstance(fetch_vars, (tuple, list)):
            fetch_vars = (fetch_vars,)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    devs = list(devices if devices is not None else jax.devices())
    mesh = make_mesh(plan.mesh_axes(), devs)
    pexe = ParallelExecutor(loss_name=fetch_vars[0].name, mesh=mesh,
                            scope=scope, main_program=main,
                            strategy=strategy)
    return AppliedPlan(plan, pexe, main, startup, fetch_vars,
                       ent["feeds"], scope)


# -- pserver embedding placement (DCN tier) --------------------------------

def embedding_wire_costs(rows, dim, touched_rows, dtype_bytes=4,
                         measured_sparse_row_s=None):
    """Per-step DCN wire seconds for a pserver-sharded embedding,
    dense vs sparse. Dense ships the WHOLE table both ways every step
    (grad push + param pull — PERF.md round 3 measured ~105 MB
    wire/step for the 52 MB table); sparse ships only the touched rows
    plus their int64 ids (the measured 131 KB/step shape).

    ``measured_sparse_row_s`` (ISSUE 12 placement pricing hook): a
    LIVE per-row miss-path measurement —
    ``serving.sparse.SparseClient.miss_row_seconds()`` — overrides the
    modeled sparse wire term, so a serving deployment prices placement
    with ITS wire (loopback, DCN) instead of the
    PERF.md round-3 constants. The cost carries a
    ``sparse_measured`` marker so rankings say which model priced
    them."""
    rows, dim = int(rows), int(dim)
    touched = min(int(touched_rows), rows)
    dense_bytes = float(rows) * dim * dtype_bytes
    sparse_bytes = float(touched) * (dim * dtype_bytes
                                     + DCN_SPARSE_ROW_OVERHEAD)
    sparse_s = (sparse_bytes / DCN_DENSE_PUSH_BPS
                + sparse_bytes / DCN_DENSE_PULL_BPS)
    measured = measured_sparse_row_s is not None
    if measured:
        sparse_s = float(touched) * float(measured_sparse_row_s)
    return {
        "dense": (dense_bytes / DCN_DENSE_PUSH_BPS
                  + dense_bytes / DCN_DENSE_PULL_BPS),
        "sparse": sparse_s,
        "sparse_measured": measured,
        "dense_wire_bytes": 2.0 * dense_bytes,
        "sparse_wire_bytes": 2.0 * sparse_bytes,
    }


def recommend_embedding_placement(rows, dim, touched_rows,
                                  dtype_bytes=4,
                                  measured_sparse_row_s=None):
    """[(mode, cost_seconds)] cheapest first for a pserver-sharded
    embedding shape. Pinned against PERF.md: the [200k x 64] table with
    a few hundred touched rows/step ranks sparse over dense (measured
    7046 vs 335 samples/s). Pass a serving SparseClient's
    ``miss_row_seconds()`` as ``measured_sparse_row_s`` to rank with
    the deployment's own measured miss path instead of the modeled
    wire."""
    costs = embedding_wire_costs(
        rows, dim, touched_rows, dtype_bytes,
        measured_sparse_row_s=measured_sparse_row_s)
    ranked = sorted([("sparse", costs["sparse"]),
                     ("dense", costs["dense"])], key=lambda kv: kv[1])
    return ranked
