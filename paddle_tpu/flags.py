"""Flag / env bootstrap layer.

Reference parity: the gflags system (utils/Flags.h; fluid's
``__bootstrap__`` in python/paddle/fluid/__init__.py reads selected
FLAGS_* env vars at import). Here every runtime flag is registered in one
table with type, default, and docs; values come from ``PADDLE_TPU_*``
environment variables (gflags semantics for booleans: 0/false/off/no =
off) and can be read or overridden programmatically via get_flag/set_flag.

Registered flags:
  check_nan_inf   bool  per-op NaN/Inf guards in the compiled step
                        (FLAGS_check_nan_inf parity, executor.cc:27-94)
  lod_bucketing   bool  bucket flat LoD totals to powers of two so text
                        batches share compiled steps (SURVEY §7)
  segment_compile bool  jit the compute runs between host (IO) ops in
                        host-op programs instead of interpreting op-by-op
  debug_nans      bool  jax_debug_nans — XLA-level NaN tracer (heavier
                        than check_nan_inf; locates the primitive)
  data_home       str   dataset cache directory
  monitor*        —     paddle_tpu.monitor runtime telemetry knobs (arm
                        at import, flight-recorder path, stall watchdog,
                        console reporter, MFU peak/cost-model)
  faults*         —     paddle_tpu.resilience fault-injection plan
                        (JSON spec or @path) + decision seed
  trace*          —     paddle_tpu.trace cross-process distributed
                        tracing (sampling rate, span-log path, lane
                        label, clock-probe interval)
  rpc_retry*      —     transparent reconnect/retry of idempotent RPC
                        verbs (bounded backoff + total deadline)
  feed_plan_cache bool  cache _normalize_feeds plans + committed device
                        feed buffers across same-signature run() calls
  transform*      —     paddle_tpu.transform optimizing IR passes (arm
                        at the compile path, pass selection) + the
                        autoparallel planner's default device count
  serving*        —     paddle_tpu.serving continuous-batching engine
                        knobs (prefill chunk length, admission window,
                        fused decode megastep K, paged-KV layout /
                        block size / pool size / prefix cache,
                        speculative decode: on/off, draft length
                        gamma, drafter tier) and serving.fleet router
                        knobs (per-replica in-flight window, global
                        shed bound, stall-watchdog deadline)
  megastep_inflight int Executor.run_steps async dispatch window depth
                        (2 = double buffering)
  telemetry*      —     monitor.collector scrape-only TelemetryServer
                        (arm at import, port, membership KV endpoint
                        to self-register with for fleet discovery)
  slo_spec        str   default SLO spec JSON for python -m
                        paddle_tpu.slo and the live verdict line of
                        python -m paddle_tpu.monitor watch
  signals_spec    str   default spec for python -m paddle_tpu.monitor
                        alerts (burn-rate objectives + sustained-rule
                        overrides; falls back to slo_spec)
  trace_tail_*    —     tail-based trace retention (in-memory span
                        ring trace window; slow-root promotion
                        threshold in ms)
  forensics_dir   str   incident-bundle output directory for
                        monitor.forensics black-box DUMP captures

Distributed bootstrap envs (read by distributed.launch, not here):
  PADDLE_COORDINATOR, PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ID.
"""

import os

_TRUTHY_OFF = ("0", "false", "off", "no")


class _Flag:
    def __init__(self, name, type_, default, help_):
        self.name = name
        self.type = type_
        self.default = default
        self.help = help_
        self.env = "PADDLE_TPU_" + name.upper()
        self._override = None

    def value(self):
        if self._override is not None:
            return self._override
        raw = os.environ.get(self.env)
        if raw is None or not raw.strip():
            return self.default
        raw = raw.strip()
        if self.type is bool:
            return raw.lower() not in _TRUTHY_OFF
        return self.type(raw)


_FLAGS = {}


def _register(name, type_, default, help_):
    _FLAGS[name] = _Flag(name, type_, default, help_)


_register("check_nan_inf", bool, False,
          "scan every op output for NaN/Inf inside the compiled step")
_register("lod_bucketing", bool, True,
          "bucket flat LoD feed totals to the next power of two")
_register("segment_compile", bool, True,
          "jit-compile the compute runs between host (IO) ops instead of "
          "interpreting the whole program op-by-op")
_register("debug_nans", bool, False,
          "enable jax_debug_nans (XLA-level NaN localization)")
_register("profile_memory", bool, False,
          "record device live/peak bytes on every profiler event "
          "(FLAGS_benchmark memory-logging parity, operator.cc:576-578)")
_register("data_home", str,
          os.path.expanduser("~/.cache/paddle_tpu/dataset"),
          "dataset cache directory")
_register("gather_sharded_fetches", bool, False,
          "fetch-time all-gather of cross-process SHARDED values: every "
          "process receives the merged global array (the reference "
          "ParallelExecutor merged fetched tensors across devices, "
          "parallel_executor.cc:190-197). Default OFF: the gather "
          "crosses DCN on every fetch, so the default stays the loud "
          "NotImplementedError telling you to fetch replicated values")
_register("monitor", bool, False,
          "arm paddle_tpu.monitor at import: step/compile telemetry into "
          "the process-wide metrics registry (near-zero overhead; see "
          "monitor_log / monitor_stall_timeout for the recorder/watchdog)")
_register("monitor_log", str, "",
          "flight-recorder JSONL path (with the monitor flag on); empty "
          "= metrics only, no event log")
_register("monitor_stall_timeout", float, 0.0,
          "seconds without a completed step/compile before the monitor "
          "watchdog dumps all thread stacks + a metrics snapshot "
          "(0 = watchdog off)")
_register("monitor_report_interval", float, 0.0,
          "seconds between one-line monitor console reports to stderr "
          "(0 = no reporter thread)")
_register("monitor_peak_flops", float, 0.0,
          "device peak FLOP/s for the MFU gauge (0 = auto-detect by TPU "
          "device kind; stays unset on CPU, disabling the gauge)")
_register("monitor_sync_every", int, 1,
          "sync (block_until_ready) every Nth monitored step. 1 = every "
          "step: exact latency, but serializes JAX async dispatch — fine "
          "on CPU and for debugging. N>1: async TPU pipelines keep "
          "dispatch pipelining; the monitor syncs once per N steps and "
          "reports the window-average as that step's latency "
          "(intermediate steps log dispatch time, flagged synced=false, "
          "and are excluded from the latency histogram/MFU)")
_register("monitor_cost_model", bool, True,
          "price each compiled step with the paddle_tpu.analysis static "
          "cost model (one extra trace per COMPILE, nothing per step) so "
          "the monitor can derive MFU")
_register("faults", str, "",
          "arm a paddle_tpu.resilience fault-injection plan at import: "
          "a JSON spec, or @/path/to/plan.json (see resilience/faults.py "
          "for the spec schema). Empty = no injection, zero-cost hooks")
_register("faults_seed", int, 0,
          "decision seed for the armed fault plan — a fixed seed gives "
          "a reproducible chaos run")
_register("trace", str, "",
          "arm paddle_tpu.trace cross-process distributed tracing at "
          "import: '1'/'true' records every root span, a float in "
          "(0, 1] head-samples that fraction of roots "
          "(PADDLE_TPU_TRACE=0.01 for fleets). Span context propagates "
          "through RPC frames; arm the WHOLE fleet together. Empty/0 = "
          "off, zero-cost hooks (one is-None check per site)")
_register("trace_log", str, "",
          "span-log JSONL path ('{pid}' substitutes the process id — "
          "each process needs its own file). Empty = "
          "ptpu_trace_<pid>.jsonl in the cwd. Merge the fleet's logs: "
          "python -m paddle_tpu.trace merge *.jsonl -o timeline.json")
_register("trace_proc", str, "",
          "process label for the merged fleet-timeline lane (default: "
          "the executable basename) — e.g. trainer0, pserver1")
_register("trace_clock_interval", float, 15.0,
          "seconds between NTP-style clock-offset probes per peer "
          "(midpoint method over an idle RPC round trip; the merge CLI "
          "uses the min-RTT sample to skew-correct timestamps). <=0 "
          "probes at every opportunity")
_register("trace_tail_window", int, 256,
          "tail-based trace retention: number of recent traces the "
          "always-on in-memory span ring buffers per process (ALL "
          "spans, sampled-out ones included, grouped by trace id) so "
          "a retention decision made AFTER a trace ends — root error, "
          "root over trace_tail_slow_ms, or an incident naming the "
          "trace — can still promote the whole trace to the span log. "
          "0 disables the ring and restores pre-forensics behavior "
          "(sampled-out spans emit headerless frames)")
_register("trace_tail_slow_ms", float, 0.0,
          "tail-retention slow threshold: a ROOT span whose duration "
          "reaches this many milliseconds is retroactively promoted "
          "to the span log with reason 'slow' (derive it from the SLO "
          "latency objective). <=0 disables the slow rule; error and "
          "incident-offender promotion stay on")
_register("forensics_dir", str, "",
          "directory monitor.forensics writes incident bundles into "
          "(black-box DUMP captures assembled into a CRC-manifested "
          "bundle when a signals incident OPENs). Empty = "
          "forensics_bundles under the cwd")
_register("rpc_retry", bool, True,
          "run idempotent RPC verbs (GET/PRFT/PUT, tagged SEND/BARR, "
          "master GETT/DONE/FAIL/PING) under the resilience retry "
          "policy: transparent reconnect + bounded exponential backoff "
          "on socket errors instead of dying with the first broken "
          "connection")
_register("rpc_retry_deadline", float, 6.0,
          "total wall-clock budget (seconds) for one verb's retry loop "
          "— sized to ride out a pserver replacement (membership lease "
          "expiry + checkpoint recovery), after which the error "
          "propagates. The backoff schedule fills the whole budget "
          "(attempts are not the limiter)")
_register("feed_plan_cache", bool, True,
          "cache _normalize_feeds derivations per feed signature and "
          "reuse committed device feed buffers across Executor.run calls "
          "(the PERF.md round-5 in-process serving re-marshal fix); "
          "0 restores the per-call full normalization")
_register("serving_prefill_chunk", int, 16,
          "serving.Engine prompt-prefill chunk length: an admitted "
          "prompt is written into its slot's KV cache this many tokens "
          "per engine iteration, so one long prompt cannot stall the "
          "running decode batch")
_register("serving_admission_wait", float, 0.0,
          "serving.Engine wait-for-batch admission window (seconds): an "
          "IDLE engine holds admissions up to this long for the queue "
          "to fill to the slot count before starting a sparse batch. "
          "0 = greedy fill (admit at the next step boundary)")
_register("serving_megastep", int, 1,
          "serving.Engine decode iterations fused into ONE device "
          "dispatch (lax.scan over the slot step) when no admissions "
          "or prefills are pending — attacks the measured bs1 "
          "per-step dispatch floor (PERF.md round 5). Admissions and "
          "retirement bookkeeping land at megastep boundaries; output "
          "stays token-identical to the K=1 engine. 1 = one dispatch "
          "per decode step (the PR-5 behavior)")
_register("serving_paged", bool, True,
          "serving.Engine KV layout: paged block pool + per-slot "
          "block tables (the vLLM design — short requests stop "
          "reserving max_len worth of cache, shared prefixes share "
          "blocks). 0 restores the PR-5 dense [slots, ...] cache; "
          "greedy output is token-identical either way")
_register("serving_block_size", int, 16,
          "paged-KV block length (cache positions per block): the "
          "allocation granule, the prefix-cache match granule (only "
          "full-block prompt prefixes are cached/matched), and the "
          "COW copy unit")
_register("serving_kv_blocks", int, 0,
          "paged-KV pool size in blocks. 0 = auto: slots * "
          "ceil(max_len / block_size), dense-capacity parity — size "
          "it below that to trade concurrency headroom for memory "
          "(the engine preempts the lowest-priority request when the "
          "pool runs dry)")
_register("serving_block_kernel", bool, True,
          "block-native paged attention (ISSUE 20): walk each slot's "
          "allocated block chain with online softmax — compute and "
          "bandwidth scale with tokens held, not pool capacity "
          "(Pallas kernel on TPU, blocked lax fallback on CPU). 0 = "
          "the PR-10 dense-gather escape hatch; fp32 outputs are "
          "token-identical either way. Requires serving_paged")
_register("serving_kv_quant", str, "",
          "paged-KV pool quantization: '' (off, dense pool dtype), "
          "'int8' (symmetric per-(position,head)-vector scales stored "
          "beside the pool; ~0.4%/element error budget, serving "
          "outputs rtol-pinned at 2e-2), or 'fp8' (float8_e4m3fn, "
          "where the runtime provides it). Quantize on cache write, "
          "dequantize inside the kernel block loop; bytes_per_block "
          "and the autoparallel HBM filter price the smaller pool. "
          "Requires serving_paged + serving_block_kernel")
_register("serving_attn_unroll", int, 1,
          "block-kernel chain-walk group size: blocks gathered and "
          "scored per online-softmax update on the CPU/lax path "
          "(fewer, fatter iterations; the Pallas path grids over "
          "single blocks regardless). Numerics-neutral at any value")
_register("serving_prefix_cache", bool, True,
          "radix prefix cache over prompt blocks: an admission whose "
          "prompt shares a cached full-block prefix skips those "
          "prefill chunks entirely (refcounted chains, LRU eviction "
          "under pool pressure). Requires serving_paged")
_register("serving_speculative", bool, False,
          "serving.Engine speculative decode (ISSUE 13): a cheap "
          "drafter proposes up to serving_spec_gamma tokens per live "
          "slot and ONE paged-attention scoring dispatch verifies all "
          "of them — every dispatch emits 1..gamma+1 tokens, breaking "
          "the bs1 per-dispatch floor. Temp-0 output stays bitwise "
          "the non-speculative engine's (accept-longest-prefix "
          "against the model's own tokens); requires serving_paged")
_register("serving_spec_gamma", int, 4,
          "speculative draft length gamma: tokens proposed per live "
          "slot per iteration. A STATIC shape constant of the scoring "
          "program (one compile per gamma; Engine.warmup pre-pays "
          "it). 0 disables speculation outright — the engine runs "
          "the existing programs cost-for-cost")
_register("serving_spec_drafter", str, "ngram",
          "speculative drafter tier: 'ngram' (host-side prompt/n-gram "
          "lookup over the request's own token chain + the radix "
          "prefix cache's published chains — zero device cost) or "
          "'truncated' (a serving_spec_layers-deep pass over the same "
          "weights, one extra fused dispatch per drafted iteration)")
_register("serving_spec_ngram", int, 3,
          "longest suffix n-gram the ngram drafter matches (falls "
          "back to shorter suffixes down to serving_spec_ngram_min)")
_register("serving_spec_ngram_min", int, 2,
          "shortest suffix n-gram the ngram drafter accepts as "
          "evidence. 2 (default) skips weak single-token matches — "
          "measured: mostly-rejected drafts whose scoring dispatches "
          "cost more than they return; 3 drafts only on the "
          "strongest evidence (highest acceptance rate, fewest "
          "drafted iterations)")
_register("serving_spec_layers", int, 0,
          "transformer layers the 'truncated' drafter runs (0 = "
          "n_layer // 2). Draft quality only moves the acceptance "
          "rate, never the output")
_register("serving_fleet_window", int, 8,
          "serving.fleet Router per-replica in-flight window "
          "(backpressure): at most this many journaled requests are "
          "dispatched to one replica at a time; the rest queue "
          "router-side")
_register("serving_fleet_queue", int, 64,
          "serving.fleet Router global queue bound (load shedding): "
          "once this many requests await dispatch, submit() fast-fails "
          "with the typed Overloaded error, counted against the SLO "
          "error budget")
_register("serving_sparse_staleness_s", float, 5.0,
          "serving.sparse hot-ID cache bounded-staleness window "
          "(seconds): a cached embedding row older than this "
          "re-fetches from its pserver shard on next touch — the "
          "upper bound on how long an online update can stay "
          "invisible through the cache (an observed version bump or "
          "incarnation change invalidates sooner)")
_register("serving_sparse_cache_rows", int, 65536,
          "serving.sparse hot-ID cache capacity in ROWS (LRU): the "
          "per-process bound on cached embedding rows across tables")
_register("serving_scoring_batch", int, 8,
          "serving.sparse ScoringEngine batch capacity: requests "
          "scored per compiled dispatch (short batches pad to this "
          "shape, so the compiled program never re-traces)")
_register("serving_mirror_fraction", float, 0.25,
          "serving.fleet shadow mirroring: deterministic fraction of "
          "accepted decode requests duplicated to CANDIDATE replicas "
          "while a shadow mirror is armed (scored against the "
          "incumbent's result, never served, excluded from the "
          "incumbent's SLO histograms)")
_register("serving_canary_weight", float, 0.1,
          "serving.fleet canary split: deterministic fraction of "
          "accepted requests served FOR REAL by candidate replicas "
          "while a canary is armed (version stamped on row/span/"
          "lease; candidates at their window fall back to incumbents "
          "— the split never sheds)")
_register("serving_fleet_stall_timeout", float, 2.0,
          "serving.fleet Router response-deadline watchdog: a replica "
          "that answers no verb for this long (retry deadline "
          "included) is evicted from dispatch, its registry slot "
          "tombstoned for the supervisor, and its unfinished requests "
          "re-submitted to a survivor")
_register("megastep_inflight", int, 2,
          "Executor.run_steps async dispatch window: how many "
          "un-fetched megastep dispatches may be in flight before the "
          "next run_steps(return_numpy=False) call blocks on the "
          "oldest. 2 = double buffering (host feed of megastep N+1 "
          "overlaps device compute of megastep N); 1 restores "
          "serialized dispatch")
_register("telemetry", bool, False,
          "arm the scrape-only monitor.collector.TelemetryServer at "
          "import: any trainer/engine process becomes METR/HLTH "
          "scrapeable by a fleet collector even without hosting a "
          "pserver/master/replica dispatch loop")
_register("telemetry_port", int, 0,
          "TelemetryServer listen port (0 = ephemeral; the endpoint "
          "self-registers when telemetry_kv is set)")
_register("telemetry_kv", str, "",
          "membership KV endpoint (host:port) the armed "
          "TelemetryServer registers its endpoint with (role "
          "'telemetry', TTL lease) so collectors discover this "
          "process without configuration; empty = serve unregistered")
_register("telemetry_slots", int, 16,
          "how many 'telemetry' role slots the lease registry offers "
          "(register_endpoint desired count for flag-armed "
          "TelemetryServers)")
_register("signals_spec", str, "",
          "default SLO/signals spec JSON for python -m "
          "paddle_tpu.monitor alerts: error-budget objectives arm "
          "burn-rate rules, the spec's 'rules' object overrides the "
          "sustained-condition defaults (monitor/signals.py). Empty "
          "= fall back to slo_spec, then defaults-only rules")
_register("slo_spec", str, "",
          "default SLO spec JSON path: python -m paddle_tpu.slo uses "
          "it when no spec argument is given, and python -m "
          "paddle_tpu.monitor watch renders a live verdict line "
          "against it (see paddle_tpu/slo.py for the spec schema)")
_register("transform", bool, False,
          "arm paddle_tpu.transform at the executors' compile path: "
          "every compile-cache MISS runs the optimizing pass pipeline "
          "(see transform_passes) over the program and builds the "
          "transformed clone — the cache key stays the caller's "
          "program+version, and passes are semantics-preserving "
          "(bitwise-identical fetches, pinned in tests/test_transform)")
_register("transform_passes", str, "all",
          "which optimizing passes the armed transform (and the "
          "python -m paddle_tpu.transform CLI default) runs: 'all', "
          "'none', or a comma list from {constant_fold, cse, dead_op, "
          "fusion, bf16_cast} in application order ('all' excludes "
          "the opt-in, non-bitwise bf16_cast)")
_register("autoparallel_devices", int, 0,
          "default device count for the automatic parallelism planner "
          "(python -m paddle_tpu.transform --plan / "
          "transform.recommend); 0 = jax.device_count() at call time")
_register("autoparallel_calib", str, "",
          "path to a transform.calibrate calibration record "
          "(python -m paddle_tpu.transform --calibrate); when set, "
          "plan_cost prices candidates with the MEASURED per-chip "
          "matmul FLOP/s and ring-collective bandwidth instead of the "
          "documented placeholders. Empty / unreadable = placeholders "
          "(rankings stay ordinal, one stderr warning per bad path)")
_register("autoparallel_hbm_gb", float, 0.0,
          "per-chip HBM capacity (GB) the autoparallel planner "
          "filters against: candidates whose modeled per-chip bytes "
          "(param shard + optimizer state + paged-KV pool, "
          "transform.autoparallel.plan_hbm_bytes) exceed it are "
          "REJECTED, not ranked. 0 = no capacity filter")


def get_flag(name):
    return _FLAGS[name].value()


def set_flag(name, value):
    """Programmatic override (wins over the environment). Values coerce
    through the flag's type with the same gflags parsing env vars get, so
    set_flag('lod_bucketing', 'off') really turns it off."""
    f = _FLAGS[name]
    if value is not None and not isinstance(value, f.type):
        if f.type is bool:
            value = str(value).strip().lower() not in _TRUTHY_OFF
        else:
            value = f.type(value)
    f._override = value
    if name == "debug_nans":
        _apply_debug_nans()


def overrides():
    """{name: current value} of every flag whose value differs from
    its default (env var or set_flag) — the active-configuration stamp
    a forensics DUMP capture carries, so a bundle records how each
    process was actually configured at the incident."""
    out = {}
    for f in _FLAGS.values():
        v = f.value()
        if v != f.default:
            out[f.name] = v
    return out


def flags_help():
    return "\n".join(
        "%-16s %-5s default=%r env=%s\n    %s"
        % (f.name, f.type.__name__, f.default, f.env, f.help)
        for f in _FLAGS.values())


def _apply_debug_nans():
    import jax
    jax.config.update("jax_debug_nans", bool(get_flag("debug_nans")))


def __bootstrap__():
    """Read env-driven flags that must take effect at import (the
    reference's __bootstrap__ shape)."""
    if get_flag("debug_nans"):
        _apply_debug_nans()


__bootstrap__()
