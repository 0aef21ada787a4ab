"""Driver benchmark entry: prints ONE JSON line with the headline metric.

Flagship: ResNet-50 ImageNet training throughput, bf16, one TPU chip
(BASELINE.json north star metric #1: ResNet-50 images/sec/chip). The same
line carries the second north-star metric — Transformer LM tokens/sec/chip
(flash-attention fused path) — as extra fields.

vs_baseline anchor: the reference's only in-tree ResNet-50 *training*
number — 81.69 imgs/sec (Intel MKL-DNN, 2×Xeon 6148, bs=64,
benchmark/IntelOptimizedPaddle.md; BASELINE.md). The reference has no
single-GPU ResNet-50 number; its closest GPU figure is AlexNet at 383
imgs/sec on a K40m.

MFU methodology and the measured per-op ceilings backing these numbers:
PERF.md.

Failure contract: every config runs under ``guarded`` — a failure is
stamped into the JSON's "errors" map and that config reports null, so
the one JSON line still prints with every loss named; the process then
exits non-zero. A chip config on a host with no accelerator fails in
``TPUPlace.jax_device()`` and is stamped like any other failure.
"""

import json
import os
import sys
import time

# ResNet-50 train step ~3x fwd FLOPs (fwd 4.1 GFLOP/img @224); v5e peak
# 197 bf16 TFLOP/s — MFU printed alongside throughput.
FLOPS_PER_IMG_TRAIN = 3 * 4.1e9
PEAK_BF16 = 197e12


def flops_per_token(L, D, FFN, T, V):
    """Train-step FLOPs per token of a decoder-only LM (3x forward)."""
    return 3 * (L * (8 * D * D + 4 * D * FFN + 4 * T * D) + 2 * D * V)


def guarded(label, fn, errors):
    """Run one bench config to completion or to a STAMPED error —
    never an aborted JSON. A failure APPENDS to ``errors[label]`` (a
    list — a config may fail on some of the K interleaved repeats and
    succeed on others, and the record must keep every loss) and that
    run reports None."""
    try:
        return fn()
    except Exception as e:
        errors.setdefault(label, []).append(repr(e))
        print("%s bench failed: %r" % (label, e), file=sys.stderr)
        return None


def _run(argv):
    sys.argv = [sys.argv[0]] + argv


def main():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    # median-of-5 timing windows: the median over >=5 windows carries
    # its own error bar.
    os.environ.setdefault("PADDLE_TPU_BENCH_WINDOWS", "5")

    errors = {}

    from paddle_tpu import compile_cache
    compile_cache.configure()

    # every config (the headline included) builds into the default
    # program, so every config starts from the one reset recipe
    import paddle_tpu as fluid
    from paddle_tpu.core import scope as scope_mod

    def _fresh():
        fluid.switch_main_program(fluid.Program())
        fluid.switch_startup_program(fluid.Program())
        scope_mod._global_scope = scope_mod.Scope()
        fluid.amp.enable_amp(False)

    def _resnet_first():
        _fresh()
        _run(["--batch_size", "256", "--iterations", "20",
              "--skip_batch_num", "3", "--device", "TPU",
              "--dtype", "bfloat16"])
        from resnet import main as resnet_main
        return float(resnet_main())

    ips = guarded("resnet", _resnet_first, errors)
    baseline = 81.69
    if ips is not None:
        mfu = ips * FLOPS_PER_IMG_TRAIN / PEAK_BF16
        print("ResNet-50 MFU %.1f%% (%.1f img/s)" % (mfu * 100, ips),
              file=sys.stderr)

    import importlib

    def transformer_bench(label, bs, L=4, D=512, FFN=2048, T=256,
                          V=8192, heads=None):
        """One transformer config through benchmarks/transformer.py;
        returns tok/s or None (via guarded) — ResNet stays the
        headline even if a transformer config fails."""
        def _one():
            _fresh()
            argv = ["--batch_size", str(bs), "--iterations", "10",
                    "--skip_batch_num", "3", "--device", "TPU",
                    "--dtype", "bfloat16", "--n_layer", str(L),
                    "--d_model", str(D), "--d_inner", str(FFN),
                    "--max_len", str(T), "--vocab", str(V)]
            if heads:
                argv += ["--n_head", str(heads)]
            _run(argv)
            import transformer as tmod
            tps = float(importlib.reload(tmod).main())
            mfu = tps * flops_per_token(L, D, FFN, T, V) / PEAK_BF16
            print("%s MFU %.1f%% (%.0f tok/s)"
                  % (label, mfu * 100, tps), file=sys.stderr)
            return tps

        return guarded(label, _one, errors)

    def resnet_repeat():
        def _one():
            _fresh()
            _run(["--batch_size", "256", "--iterations", "20",
                  "--skip_batch_num", "3", "--device", "TPU",
                  "--dtype", "bfloat16"])
            import resnet as rmod
            return float(importlib.reload(rmod).main())

        return guarded("resnet-repeat", _one, errors)

    def lstm_repeat():
        """The reference's strongest published training line: stacked
        dynamic LSTM (benchmark/README.md 184 ms/batch, h=512 bs=64 on
        a K40m) — the LoD/bucketing path under perf, not just
        correctness. Returns ms/batch (lower is better)."""
        def _one():
            _fresh()
            _run(["--batch_size", "64", "--hidden_dim", "512",
                  "--iterations", "12", "--skip_batch_num", "2",
                  "--device", "TPU"])
            import stacked_dynamic_lstm as lmod
            return float(importlib.reload(lmod).main())

        return guarded("lstm", _one, errors)

    # INTERLEAVED repeats: each config is measured K times spread across
    # the whole invocation and reported as median + spread — a
    # round-over-round delta smaller than the spread is noise.
    K = max(1, int(os.environ.get("PADDLE_TPU_BENCH_REPEATS", "3")))
    res_s, large_s, xl_s, lstm_s = [ips], [], [], []
    tps_small = None
    for r in range(K):
        if r > 0:
            res_s.append(resnet_repeat())
        if r == 0:
            # bs256: the throughput-saturating batch for the 4L/d512
            # config — bs32 is dispatch-latency-bound (PERF.md batch
            # sweep); one sample (secondary metric)
            tps_small = transformer_bench("Transformer-small", bs=256)
        # the LARGE config (8L d1024 ffn4096 T1024): kept unchanged for
        # round-over-round comparability
        large_s.append(transformer_bench(
            "Transformer-large", bs=8, L=8, D=1024, FFN=4096, T=1024))
        # the XL config — the best honest MFU this chip reaches (width
        # sweep, PERF.md round 4): 8L d2048 ffn8192 T1024, head dim 128
        xl_s.append(transformer_bench(
            "Transformer-XL", bs=8, L=8, D=2048, FFN=8192, T=1024,
            heads=16))
        lstm_s.append(lstm_repeat())

    def monitor_probe():
        """One short MONITORED window (benchmarks/mnist.py shrunk):
        paddle_tpu.monitor armed with flight recorder + cost model, the
        summary stamped into the bench JSON. Kept separate from the
        headline timing windows because the monitor syncs every step
        for honest latency, which the throughput protocol (one sync
        per window) does not."""
        from paddle_tpu import monitor as mon
        _fresh()
        log = "/tmp/ptpu_bench_monitor.jsonl"
        try:
            os.remove(log)
        except OSError:
            pass
        # monitor.session(): respects an env-armed ambient config and
        # reports the PROBE's own counts as deltas, so the stamp never
        # aggregates the headline windows' steps
        import contextlib
        with mon.session(log_path=log) as sess:
            _run(["--batch_size", "128", "--iterations", "10",
                  "--skip_batch_num", "2", "--device", "TPU"])
            import mnist as mmod
            # the mnist driver prints its own result line to STDOUT;
            # bench.py's contract is ONE JSON line there — reroute
            with contextlib.redirect_stdout(sys.stderr):
                importlib.reload(mmod).main()
        s = sess.summary()
        probe = {
            "steps": s["steps"],
            "p50_ms": round(1000 * s["p50_s"], 3) if s["p50_s"] else None,
            "p95_ms": round(1000 * s["p95_s"], 3) if s["p95_s"] else None,
            "recompiles": s["recompiles"],
            "tokens_per_sec": round(s["tokens_per_sec"], 1)
            if s["tokens_per_sec"] else None,
            "mfu_pct": round(100 * s["mfu"], 2) if s["mfu"] else None,
            "log": log,
        }
        print("monitor probe: %s" % probe, file=sys.stderr)
        return probe

    monitor_summary = guarded("monitor-probe", monitor_probe, errors)

    def serving_probe():
        """Continuous-batching serving smoke (benchmarks/serving_bench
        fast CPU mode): engine-vs-sequential aggregate tokens/s on a
        mixed-length request set, with token identity verified and the
        request-level SLO percentiles (TTFT/TPOT p50/p95) stamped.
        Runs on the CPU backend (chip_smoke.py is the engine's run on
        the chip) and is stamped into the bench JSON like the monitor
        probe."""
        import jax
        prev = jax.config.jax_default_device
        try:
            _fresh()
            # --megastep 8: the ISSUE-7 fused-K decode pass rides the
            # same probe, stamped as megastep_* fields in the block.
            # --prefix_share 32: the ISSUE-10 shared-system-prompt A/B
            # (paged+prefix vs PR-5 dense, interleaved windows) rides
            # it too, stamped as prefix_* fields alongside the paged
            # pool occupancy (kv_*).
            # --speculative 4: the ISSUE-13 speculative-decode A/B
            # (γ=4 drafts verified per scoring dispatch; shared-prefix
            # + natural-text regimes + the bs1 dispatch-floor probe on
            # the dispatch-bound shape), stamped as spec_* fields +
            # the accepted_tokens_per_dispatch figure perfgate gates
            # --block_probe: the ISSUE-20 block-kernel vs gather-path
            # A/B (paged decode step at fixed tokens held across two
            # pool capacities; int8 arm separate), stamped as block_*
            # fields perfgate gates
            _run(["--device", "CPU", "--fast", "--megastep", "8",
                  "--prefix_share", "32", "--speculative", "4",
                  "--block_probe"])
            import serving_bench as smod
            return importlib.reload(smod).main()
        finally:
            # serving_bench pins the PROCESS default device to CPU for
            # its engine thread and restores it itself; verify here
            # too — a leaked CPU pin would silently steer every later
            # config off the chip
            if jax.config.jax_default_device is not prev:
                print("serving probe leaked jax_default_device=%r — "
                      "restoring %r"
                      % (jax.config.jax_default_device, prev),
                      file=sys.stderr)
                jax.config.update("jax_default_device", prev)

    serving_summary = guarded("serving-probe", serving_probe, errors)

    import statistics

    def agg(samples, nd=1):
        """median + max-min spread (% of median) + rounded sorted
        samples — the one reducer every stamp in this JSON uses."""
        vals = sorted(v for v in samples if v)
        if not vals:
            return None, None, []
        med = statistics.median(vals)
        spread = 100.0 * (vals[-1] - vals[0]) / med if med else 0.0
        return med, round(spread, 1), [round(v, nd or None)
                                       for v in vals]

    def megastep_probe():
        """ISSUE-7 K-sweep on the dispatch-bound shape: interleaved
        A/B windows of K=1 (one exe.run dispatch per step) vs K=8
        (exe.run_steps, ONE fused dispatch per 8 steps) on a
        scaled-down small-transformer train step, CPU-pinned like the
        serving probe (the per-step host-dispatch tax is the quantity
        under test).
        Round-5 protocol: the arms alternate inside one invocation and
        report median + spread."""
        import jax
        import numpy as np
        from paddle_tpu.models import transformer as T
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        try:
            _fresh()
            avg_cost, _ = T.transformer_lm(
                vocab_size=256, max_len=16, n_layer=2, n_head=2,
                d_model=64, d_inner=256, packed=True)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            rng = np.random.RandomState(0)
            feed = T.make_lm_batch(rng, 4, 16, 256)
            feed["mask"] = np.ones_like(feed["mask"])
            toks = int(feed["mask"].sum())
            steps, k, wins = 64, 8, 5

            def sync(out):
                jax.block_until_ready(out)   # pytree of device fetches

            def win_k1():
                t0 = time.perf_counter()
                last = None
                for _ in range(steps):
                    last = exe.run(feed=feed, fetch_list=[avg_cost],
                                   return_numpy=False)
                sync(last)
                return steps * toks / (time.perf_counter() - t0)

            def win_k8():
                t0 = time.perf_counter()
                out = None
                for _ in range(steps // k):
                    out = exe.run_steps(feeds=[feed] * k,
                                        fetch_list=[avg_cost],
                                        return_numpy=False)
                sync(out)
                return steps * toks / (time.perf_counter() - t0)

            win_k1(), win_k8()          # warm both compiles
            a, b = [], []
            for _ in range(wins):       # interleaved A/B
                a.append(win_k1())
                b.append(win_k8())

            m1, sp1, s1 = agg(a, nd=0)
            m8, sp8, s8 = agg(b, nd=0)
            probe = {
                "config": "transformer_lm 2L/d64 bs4 T16 (CPU pin)",
                "steps_per_window": steps, "windows": wins,
                "k1_tok_s": round(m1), "k1_spread_pct": sp1,
                "k1_samples": s1,
                "k8_tok_s": round(m8), "k8_spread_pct": sp8,
                "k8_samples": s8,
                "speedup": round(m8 / m1, 2),
            }
            print("megastep probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            jax.config.update("jax_default_device", prev)

    megastep_summary = guarded("megastep-probe", megastep_probe, errors)

    def fleet_probe():
        """ISSUE-8 serving-fleet probe, CPU-pinned like the serving
        probe: (a) DISARMED router overhead — direct single-Engine
        generate_many vs the same mixed request set through KV-registry
        + Router + replica RPC, interleaved A/B windows (PR-4
        protocol), per-request p50/p95 added latency stamped; (b) a
        small ARMED pass (seeded replica kill mid-traffic + supervisor
        respawn) stamping resubmission counts and the exactly-once/
        token-identity verdict."""
        import jax
        import numpy as np
        from paddle_tpu import serving
        from paddle_tpu.distributed.membership import KVServer, KVClient
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.transformer_infer import TransformerLMInfer
        from paddle_tpu.resilience import faults
        from paddle_tpu.serving import fleet
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        try:
            _fresh()
            scope = fluid.global_scope()
            # decode-bound shape: the router's per-request cost (SUBM
            # round trip + delivery ack) must be measured against real
            # decode work, the production ratio — on a dispatch-bound
            # toy model the host RPC chatter IS the bottleneck and the
            # figure measures core contention, not the front door
            T.transformer_lm(vocab_size=256, max_len=224, n_layer=4,
                             n_head=4, d_model=256, d_inner=1024)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            lm = TransformerLMInfer(fluid.default_main_program(), scope,
                                    4, 4, 256, 224)
            rng = np.random.RandomState(0)
            reqs = []
            for _ in range(16):
                plen = int(rng.randint(1, 9))
                prompt = [1] + rng.randint(3, 256, plen - 1).tolist()
                reqs.append((prompt, int(rng.randint(64, 129))))
            prompts = [p for p, _ in reqs]
            news = [m for _, m in reqs]

            eng = serving.Engine(lm, slots=4, prefill_chunk=8,
                                 name="fleet-direct")
            kvs = KVServer(sweep_interval=0.05).start()
            kv = KVClient(kvs.endpoint)
            cells = [fleet.Replica(kv, lm, desired=1, slots=4,
                                   prefill_chunk=8, ttl=0.5)]
            router = fleet.Router(kvs.endpoint, window=8,
                                  refresh_interval=0.05)
            router.wait_for_replicas(1)

            def win_direct():
                t0 = time.perf_counter()
                handles = [eng.submit(p, m)
                           for p, m in zip(prompts, news)]
                out = [h.result(timeout=120) for h in handles]
                dt = time.perf_counter() - t0
                lats = sorted(h.t_retire - h.t_enqueue
                              for h in handles)
                return dt, lats, out

            def win_routed():
                t0 = time.perf_counter()
                handles = [router.submit(p, m)
                           for p, m in zip(prompts, news)]
                out = [h.result(timeout=120) for h in handles]
                dt = time.perf_counter() - t0
                lats = sorted(h.latency() for h in handles)
                return dt, lats, out

            win_direct(), win_routed()        # warm every compile
            wins, a_dt, b_dt, a_lat, b_lat = 3, [], [], [], []
            base, identical = None, True
            for _ in range(wins):             # interleaved A/B
                dt, lats, out = win_direct()
                a_dt.append(dt)
                a_lat.append(lats)
                base = out
                dt, lats, out = win_routed()
                b_dt.append(dt)
                b_lat.append(lats)
                # accumulated across EVERY window — a divergence in an
                # early window must not be masked by a clean last one
                identical = identical and all(
                    bt == rt for (bt, _), (rt, _) in zip(base, out))
            ma, spa, _ = agg(a_dt, nd=4)
            mb, spb, _ = agg(b_dt, nd=4)

            def pct(ls, q):
                import statistics
                per = [s[min(len(s) - 1, int(round(q * (len(s) - 1))))]
                       for s in ls]
                return statistics.median(per)

            # armed pass: seeded kill mid-traffic + respawn; every
            # accepted request completes exactly once, token-identical
            def spawn():
                return fleet.Replica(kv, lm, desired=2, slots=4,
                                     prefill_chunk=8, ttl=0.4)
            cells.append(spawn())             # 2nd replica for the kill
            # threshold relative to the warm-up traffic already
            # accepted, so the kill fires mid-way through the ARMED
            # pass (the fault counts SUBM admissions)
            plan = faults.arm(
                {"kill": [{"target": "replica:0",
                           "after": cells[0].server._accepted + 4}]},
                seed=1301)
            sup = fleet.Supervisor(kv, spawn, desired=2,
                                   interval=0.1).start()
            chaos = router.generate_many(prompts, news, timeout=120)
            chaos_ok = all(bt == ct for (bt, _), (ct, _)
                           in zip(base, chaos))
            faults.disarm()
            probe = {
                "config": "transformer_lm 4L/d256, 16 mixed reqs "
                          "(64-128 new tokens), slots=4 (CPU pin)",
                "windows": wins,
                "direct_s": round(ma, 4), "direct_spread_pct": spa,
                "routed_s": round(mb, 4), "routed_spread_pct": spb,
                "router_overhead_pct": round(100 * (mb - ma) / ma, 2),
                "direct_p50_ms": round(1000 * pct(a_lat, 0.5), 2),
                "routed_p50_ms": round(1000 * pct(b_lat, 0.5), 2),
                "added_p50_ms": round(1000 * (pct(b_lat, 0.5)
                                              - pct(a_lat, 0.5)), 2),
                "added_p95_ms": round(1000 * (pct(b_lat, 0.95)
                                              - pct(a_lat, 0.95)), 2),
                "identical": bool(identical),
                "chaos_identical": bool(chaos_ok),
                "chaos_resubmissions": router.stats["resubmissions"],
                "chaos_evictions": dict(router.stats["evictions"]),
                "chaos_respawns": sup.respawns,
                "kill_fired": ("kill", "replica:0") in plan.trips,
            }
            sup.stop()
            router.close()
            for c in cells + sup.cells:
                try:
                    c.shutdown()
                except Exception:
                    pass
            eng.close()
            kv.shutdown_server()
            kv.close()
            print("fleet probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            faults.disarm()
            jax.config.update("jax_default_device", prev)

    fleet_summary = guarded("fleet-probe", fleet_probe, errors)

    def autoscale_probe():
        """ISSUE-18 elastic-fleet probe, CPU-pinned like the fleet
        probe: (a) DISARMED autoscaler overhead — the same mixed
        request set through a plain 2-replica fleet vs an
        Autoscaler-managed fleet of identical shape (both cold-booted
        from the SAME v1 artifact), interleaved A/B windows: the
        control loop's tick must be invisible to the serving path;
        (b) a v1 -> v2 rolling weight update under live traffic —
        bursts keep flowing through the router while the controller
        replaces replicas one at a time — stamping the shed count
        (contract: 0), the roll wall clock, and the p95 TTFT
        inflation during the roll vs a steady window (delta-histogram
        over ptpu_serving_ttft_seconds)."""
        import shutil
        import tempfile
        import jax
        import numpy as np
        from paddle_tpu import serving
        from paddle_tpu.distributed.membership import KVServer, KVClient
        from paddle_tpu.models import transformer as T
        from paddle_tpu.monitor.metrics import bucket_percentile
        from paddle_tpu.monitor.runtime import SERVING_TTFT
        from paddle_tpu.serving import fleet
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        art_root = None
        auto = router_a = router_b = None
        cells_a, kvss = [], []
        try:
            _fresh()
            scope = fluid.global_scope()
            _, logits = T.transformer_lm(vocab_size=64, max_len=96,
                                         n_layer=2, n_head=2,
                                         d_model=64, d_inner=128)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            main = fluid.default_main_program()
            art_root = tempfile.mkdtemp(prefix="ptpu_autoscale_")
            v1 = os.path.join(art_root, "v1")
            v2 = os.path.join(art_root, "v2")
            # same weights under two version labels: token identity
            # across the roll IS the acceptance contract, so v2 must
            # decode exactly like v1
            serving.save_lm_artifact(v1, main, scope, [logits],
                                     2, 2, 64, 96)
            serving.save_lm_artifact(v2, main, scope, [logits],
                                     2, 2, 64, 96)
            rng = np.random.RandomState(0)
            reqs = []
            for _ in range(12):
                plen = int(rng.randint(1, 9))
                prompt = [1] + rng.randint(3, 64, plen - 1).tolist()
                reqs.append((prompt, int(rng.randint(16, 33))))
            prompts = [p for p, _ in reqs]
            news = [m for _, m in reqs]

            # fleet A: two plain replicas, no controller
            kva = KVServer(sweep_interval=0.05).start()
            kvss.append(kva)
            kvc = KVClient(kva.endpoint)
            cells_a = [fleet.Replica(kvc, v1, desired=2, slots=4,
                                     prefill_chunk=8, ttl=0.5)
                       for _ in range(2)]
            router_a = fleet.Router(kva.endpoint, window=8,
                                    refresh_interval=0.05)
            router_a.wait_for_replicas(2)
            # fleet B: the SAME shape under the autoscale control loop
            kvb = KVServer(sweep_interval=0.05).start()
            kvss.append(kvb)
            auto = serving.Autoscaler(
                kvb.endpoint, v1, desired=2, min_replicas=1,
                max_replicas=4, slots=4, ttl=0.5, interval=0.05,
                prefill_chunk=8).start()
            auto.wait_steady(timeout=60)
            router_b = fleet.Router(kvb.endpoint, window=8,
                                    refresh_interval=0.05)
            router_b.wait_for_replicas(2)

            def win(router):
                t0 = time.perf_counter()
                handles = [router.submit(p, m)
                           for p, m in zip(prompts, news)]
                out = [h.result(timeout=120) for h in handles]
                return time.perf_counter() - t0, out

            win(router_a), win(router_b)      # warm every compile
            wins, a_dt, b_dt = 3, [], []
            base, identical = None, True
            for _ in range(wins):             # interleaved A/B
                dt, out = win(router_a)
                a_dt.append(dt)
                base = out
                dt, out = win(router_b)
                b_dt.append(dt)
                identical = identical and all(
                    bt == rt for (bt, _), (rt, _) in zip(base, out))
            ma, spa, _ = agg(a_dt, nd=4)
            mb, spb, _ = agg(b_dt, nd=4)

            nb = len(SERVING_TTFT.buckets) + 1

            def ttft_counts():
                return {k: list(v["counts"])
                        for k, v in SERVING_TTFT.snapshot().items()}

            def ttft_p95(before, after):
                # windowed delta-histogram p95, merged across every
                # engine label (the roll's v2 engines included)
                delta = [0] * nb
                for k, counts in after.items():
                    b4 = before.get(k, [0] * nb)
                    for i in range(min(nb, len(counts))):
                        delta[i] += counts[i] - b4[i]
                if sum(delta) <= 0:
                    return None
                return bucket_percentile(SERVING_TTFT.buckets,
                                         delta, 0.95)

            snap0 = ttft_counts()
            win(router_b)                     # steady TTFT window
            steady_p95 = ttft_p95(snap0, ttft_counts())
            shed0 = router_b.stats["shed"]
            snap1 = ttft_counts()
            t0 = time.perf_counter()
            auto.roll(v2)
            roll_identical, bursts = True, 0
            while auto.roll_status() is not None and bursts < 40:
                _, out = win(router_b)
                bursts += 1
                roll_identical = roll_identical and all(
                    bt == rt for (bt, _), (rt, _) in zip(base, out))
            info = auto.wait_roll(timeout=120)
            roll_wall_s = time.perf_counter() - t0
            roll_p95 = ttft_p95(snap1, ttft_counts())
            st = auto.wait_steady(timeout=60)
            probe = {
                "config": "transformer_lm 2L/d64 T96 artifacts, "
                          "12 mixed reqs (16-32 new), 2 replicas "
                          "x slots=4 (CPU pin)",
                "windows": wins,
                "plain_s": round(ma, 4), "plain_spread_pct": spa,
                "managed_s": round(mb, 4), "managed_spread_pct": spb,
                "overhead_pct": round(100 * (mb - ma) / ma, 2),
                "identical": bool(identical),
                "roll_s": round(info.get("convergence_s")
                                or roll_wall_s, 3),
                "roll_bursts": bursts,
                "roll_shed": router_b.stats["shed"] - shed0,
                "roll_aborted": bool(info.get("aborted")),
                "roll_identical": bool(roll_identical),
                "roll_replaced": info.get("replaced"),
                "final_version_mix": st["version_mix"],
            }
            if steady_p95 is not None:
                probe["steady_ttft_p95_ms"] = round(
                    1000 * steady_p95, 2)
            if roll_p95 is not None:
                probe["roll_ttft_p95_ms"] = round(1000 * roll_p95, 2)
            if steady_p95 and roll_p95 is not None:
                probe["roll_ttft_inflation_pct"] = round(
                    100 * (roll_p95 - steady_p95) / steady_p95, 1)
            print("autoscale probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            for r in (router_a, router_b):
                if r is not None:
                    r.close()
            if auto is not None:
                auto.close()
            for c in cells_a:
                try:
                    c.shutdown()
                except Exception:
                    pass
            for s in kvss:
                try:
                    s.stop()
                except Exception:
                    pass
            if art_root is not None:
                shutil.rmtree(art_root, ignore_errors=True)
            jax.config.update("jax_default_device", prev)

    autoscale_summary = guarded("autoscale-probe", autoscale_probe,
                                errors)

    def rollout_probe():
        """ISSUE-19 canary-rollout probe, CPU-pinned like the fleet
        probes: (a) mirror-path overhead — the same mixed request set
        through a plain 2-replica fleet vs an autoscaler-managed
        fleet whose router carries the (DISARMED) mirror machinery,
        interleaved A/B windows: the per-submit mirror check and the
        idle mirror thread must be invisible to the serving path
        (<1%% budget); (b) a full shadow -> canary -> promote rollout
        under live traffic — bursts keep flowing through the router
        while candidates score mirrored copies, serve the canary
        split, and the autoscaler rolls the fleet to v2 — stamping
        the verdicts, the shed count (contract: 0), token identity
        across the whole pipeline, and the p95 TTFT inflation during
        the rollout vs a steady window (delta-histogram over
        ptpu_serving_ttft_seconds)."""
        import shutil
        import tempfile
        import threading
        import jax
        import numpy as np
        from paddle_tpu import monitor, serving
        from paddle_tpu.distributed.membership import KVServer, KVClient
        from paddle_tpu.models import transformer as T
        from paddle_tpu.monitor.metrics import bucket_percentile
        from paddle_tpu.monitor.runtime import SERVING_TTFT
        from paddle_tpu.serving import fleet
        from paddle_tpu.serving.rollout import RolloutController
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        art_root = None
        auto = ctl = router_a = router_b = None
        cells_a, kvss = [], []
        try:
            _fresh()
            scope = fluid.global_scope()
            _, logits = T.transformer_lm(vocab_size=64, max_len=96,
                                         n_layer=2, n_head=2,
                                         d_model=64, d_inner=128)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            main = fluid.default_main_program()
            art_root = tempfile.mkdtemp(prefix="ptpu_rollout_")
            v1 = os.path.join(art_root, "v1")
            v2 = os.path.join(art_root, "v2")
            # same weights under two labels: the PASS verdict and
            # token identity across the promotion ARE the contract
            serving.save_lm_artifact(v1, main, scope, [logits],
                                     2, 2, 64, 96)
            serving.save_lm_artifact(v2, main, scope, [logits],
                                     2, 2, 64, 96)
            rng = np.random.RandomState(7)
            reqs = []
            for _ in range(12):
                plen = int(rng.randint(1, 9))
                prompt = [1] + rng.randint(3, 64, plen - 1).tolist()
                reqs.append((prompt, int(rng.randint(16, 33))))
            prompts = [p for p, _ in reqs]
            news = [m for _, m in reqs]

            # fleet A: plain replicas, no controller, no mirror ever
            kva = KVServer(sweep_interval=0.05).start()
            kvss.append(kva)
            kvc = KVClient(kva.endpoint)
            cells_a = [fleet.Replica(kvc, v1, desired=2, slots=4,
                                     prefill_chunk=8, ttl=0.5)
                       for _ in range(2)]
            router_a = fleet.Router(kva.endpoint, window=8,
                                    refresh_interval=0.05)
            router_a.wait_for_replicas(2)
            # fleet B: autoscaler-managed (the promotion path), same
            # shape; its router's mirror machinery stays DISARMED for
            # the A/B overhead windows
            kvb = KVServer(sweep_interval=0.05).start()
            kvss.append(kvb)
            auto = serving.Autoscaler(
                kvb.endpoint, v1, desired=2, min_replicas=1,
                max_replicas=4, slots=4, ttl=0.5, interval=0.05,
                prefill_chunk=8).start()
            auto.wait_steady(timeout=60)
            router_b = fleet.Router(kvb.endpoint, window=8,
                                    refresh_interval=0.05)
            router_b.wait_for_replicas(2)

            def win(router):
                t0 = time.perf_counter()
                handles = [router.submit(p, m)
                           for p, m in zip(prompts, news)]
                out = [h.result(timeout=120) for h in handles]
                return time.perf_counter() - t0, out

            win(router_a), win(router_b)      # warm every compile
            wins, a_dt, b_dt = 3, [], []
            base, identical = None, True
            for _ in range(wins):             # interleaved A/B
                dt, out = win(router_a)
                a_dt.append(dt)
                base = out
                dt, out = win(router_b)
                b_dt.append(dt)
                identical = identical and all(
                    bt == rt for (bt, _), (rt, _) in zip(base, out))
            ma, spa, _ = agg(a_dt, nd=4)
            mb, spb, _ = agg(b_dt, nd=4)

            nb = len(SERVING_TTFT.buckets) + 1

            def ttft_counts():
                return {k: list(v["counts"])
                        for k, v in SERVING_TTFT.snapshot().items()}

            def ttft_p95(before, after):
                delta = [0] * nb
                for k, counts in after.items():
                    b4 = before.get(k, [0] * nb)
                    for i in range(min(nb, len(counts))):
                        delta[i] += counts[i] - b4[i]
                if sum(delta) <= 0:
                    return None
                return bucket_percentile(SERVING_TTFT.buckets,
                                         delta, 0.95)

            snap0 = ttft_counts()
            win(router_b)                     # steady TTFT window
            steady_p95 = ttft_p95(snap0, ttft_counts())

            # (b) the full verdict-gated pipeline under live traffic.
            # The delta evaluator reads flight-recorder rows, so the
            # probe arms a recorder session for the rollout phase.
            # inflation bound 50x like the chaos-gated e2e test — a
            # shadow copy's TTFT includes its queue wait at the ONE
            # candidate carrying a sampled slice of a 2-replica
            # fleet's traffic — plus the absolute floor: on a toy
            # model the incumbent baseline is single-digit ms, and a
            # ratio over a near-zero baseline reads milliseconds of
            # structural queueing as a huge regression
            spec = {"delta": {
                "window_s": 300.0, "min_pairs": 6, "min_requests": 6,
                "objectives": [
                    {"metric": "delta_ttft", "percentile": 0.95,
                     "max_inflation": 50.0, "min_floor_s": 0.25},
                    {"metric": "delta_error_rate", "max_delta": 0.5},
                    {"metric": "token_agreement", "min_ratio": 0.95},
                ]}}
            shed0 = router_b.stats["shed"]
            snap1 = ttft_counts()
            t0 = time.perf_counter()
            roll_identical, bursts = True, 0
            with monitor.session(log_path=os.path.join(
                    art_root, "rollout.jsonl")):
                ctl = RolloutController(
                    kvb.endpoint, router_b, auto, v2, spec,
                    # fraction < 1: one 4-slot candidate cannot absorb
                    # a FULL mirror of 12-wide bursts without queueing
                    # every copy behind the window cap
                    candidates=1, shadow_fraction=0.6,
                    canary_weight=0.3, verdict_timeout=90.0,
                    slots=4, ttl=0.5, prefill_chunk=8)
                done = {}
                th = threading.Thread(
                    target=lambda: done.update(st=ctl.run()),
                    daemon=True)
                th.start()
                while th.is_alive() and bursts < 200:
                    _, out = win(router_b)
                    bursts += 1
                    roll_identical = roll_identical and all(
                        bt == rt
                        for (bt, _), (rt, _) in zip(base, out))
                th.join(timeout=240)
                st = done.get("st") or ctl.status()
            rollout_wall_s = time.perf_counter() - t0
            rollout_p95 = ttft_p95(snap1, ttft_counts())
            probe = {
                "config": "transformer_lm 2L/d64 T96 artifacts, "
                          "12 mixed reqs (16-32 new), 2 replicas "
                          "x slots=4 + 1 candidate (CPU pin)",
                "windows": wins,
                "plain_s": round(ma, 4), "plain_spread_pct": spa,
                "mirror_disarmed_s": round(mb, 4),
                "mirror_disarmed_spread_pct": spb,
                "mirror_overhead_pct": round(
                    100 * (mb - ma) / ma, 2),
                "identical": bool(identical),
                "rollout_phase": st["phase"],
                "rollout_verdicts": {
                    p: v.get("verdict")
                    for p, v in st["verdicts"].items()},
                "rollout_s": round(st.get("convergence_s")
                                   or rollout_wall_s, 3),
                "rollout_bursts": bursts,
                "rollout_shed": router_b.stats["shed"] - shed0,
                "rollout_identical": bool(roll_identical),
                "mirror_pairs": router_b.stats["mirror_pairs"],
                "canary_served": router_b.stats["canary_served"],
            }
            if steady_p95 is not None:
                probe["steady_ttft_p95_ms"] = round(
                    1000 * steady_p95, 2)
            if rollout_p95 is not None:
                probe["rollout_ttft_p95_ms"] = round(
                    1000 * rollout_p95, 2)
            if steady_p95 and rollout_p95 is not None:
                probe["rollout_ttft_inflation_pct"] = round(
                    100 * (rollout_p95 - steady_p95) / steady_p95, 1)
            print("rollout probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            if ctl is not None:
                try:
                    ctl.close()
                except Exception:
                    pass
            for r in (router_a, router_b):
                if r is not None:
                    r.close()
            if auto is not None:
                auto.close()
            for c in cells_a:
                try:
                    c.shutdown()
                except Exception:
                    pass
            for s in kvss:
                try:
                    s.stop()
                except Exception:
                    pass
            if art_root is not None:
                shutil.rmtree(art_root, ignore_errors=True)
            jax.config.update("jax_default_device", prev)

    rollout_summary = guarded("rollout-probe", rollout_probe, errors)

    def recsys_probe():
        """ISSUE-12 sparse-serving probe, CPU-pinned like the serving
        probe: DeepFM scoring against live pserver row shards through
        the serving.sparse tier. (a) COLD vs WARM hot-ID cache
        scoring throughput, interleaved A/B windows (cold = cache
        cleared before the window, every row over the PRFT wire; warm
        = the zipf-hot id set served cacheside) + the final cache hit
        rate; (b) routed-vs-direct overhead — the same request set
        through KV registry + Router + scoring replica vs the direct
        engine — with bitwise score identity verified at the pinned
        cache version."""
        import jax
        import numpy as np
        from paddle_tpu.distributed.membership import KVServer, KVClient
        from paddle_tpu.distributed.rpc import VariableServer
        from paddle_tpu.models import deepfm as dfm
        from paddle_tpu.serving import fleet
        from paddle_tpu.serving.sparse import (HotIDCache, SparseClient,
                                               ScoringEngine)
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        VOCAB, DIM, F, NSHARD = 20000, 16, 8, 2
        servers, eps = [], []
        closers = []
        try:
            _fresh()
            rng = np.random.RandomState(0)
            tables = {
                "fm_first_w": rng.rand(VOCAB, 1).astype(np.float32),
                "fm_second_w": rng.rand(VOCAB, DIM).astype(np.float32)}
            for shard in range(NSHARD):
                meta = {t: {"shard": shard, "num_shards": NSHARD,
                            "height": VOCAB} for t in tables}
                srv = VariableServer(fan_in=1, sparse_tables=meta)
                for t, full in tables.items():
                    srv.store[t] = full[shard::NSHARD].copy()
                srv.start()
                servers.append(srv)
                eps.append("127.0.0.1:%d" % srv.port)

            scope = fluid.global_scope()
            prob, _ = dfm.build_scoring_net(F, DIM, dnn_dims=(32, 32))
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            main = fluid.default_main_program()

            def make_engine(name):
                cache = HotIDCache(capacity=65536, staleness_s=60.0)
                c1 = SparseClient("fm_first_w", eps, cache=cache)
                c2 = SparseClient("fm_second_w", eps, cache=cache)
                feat = dfm.make_featurizer(c1, c2, F, DIM)
                eng = ScoringEngine(main, scope, prob.name, feat,
                                    clients=[c1, c2], batch=8,
                                    name=name)
                closers.append(eng)
                return eng

            eng = make_engine("recsys-direct")
            eng.warmup()
            # zipf-hot traffic: the hot-ID cache's natural shape — a
            # small head of ids dominates every batch
            nreq = 64
            hot = rng.randint(0, 256, (nreq, F))
            tail = rng.randint(0, VOCAB, (nreq, F))
            pick = rng.rand(nreq, F) < 0.9
            ids = np.where(pick, hot, tail)
            feats = [{"f%d" % f: [int(ids[r, f])] for f in range(F)}
                     for r in range(nreq)]

            def win_cold():
                for c in eng._clients:
                    c.cache.clear()
                t0 = time.perf_counter()
                eng.score_many(feats, timeout=120)
                return nreq / (time.perf_counter() - t0)

            def win_warm():
                t0 = time.perf_counter()
                eng.score_many(feats, timeout=120)
                return nreq / (time.perf_counter() - t0)

            win_cold(), win_warm()          # warm the compile + cache
            cold, warm = [], []
            for _ in range(3):              # interleaved A/B
                cold.append(win_cold())
                warm.append(win_warm())
            mc, spc, _ = agg(cold, nd=0)
            mw, spw, _ = agg(warm, nd=0)
            cs = eng.cache_stats()
            hit_rate = cs["hits"] / max(1, cs["hits"] + cs["misses"])

            # routed-vs-direct at a pinned cache version (no online
            # updates land during the A/B -> versions equal -> scores
            # bitwise): interleaved windows, PR-8 protocol
            kvs = KVServer(sweep_interval=0.05).start()
            kv = KVClient(kvs.endpoint)
            cell = fleet.Replica(kv, None, desired=1, ttl=0.5,
                                 engine_factory=lambda name:
                                 make_engine("recsys-replica"))
            router = fleet.Router(kvs.endpoint, refresh_interval=0.05)
            router.wait_for_replicas(1)

            def win_direct():
                t0 = time.perf_counter()
                out = eng.score_many(feats, timeout=120)
                return time.perf_counter() - t0, out

            def win_routed():
                t0 = time.perf_counter()
                hs = [router.submit(features=f) for f in feats]
                out = [h.result(timeout=120)[1] for h in hs]
                return time.perf_counter() - t0, out

            win_direct(), win_routed()      # warm the replica's cache
            a_dt, b_dt, identical = [], [], True
            for _ in range(3):
                dt, base = win_direct()
                a_dt.append(dt)
                dt, routed = win_routed()
                b_dt.append(dt)
                identical = identical and routed == base
            ma, spa, _ = agg(a_dt, nd=4)
            mb, spb, _ = agg(b_dt, nd=4)
            probe = {
                "config": "deepfm F8 D16 V20k, 2 pserver shards, 64 "
                          "zipf-hot reqs, batch=8 (CPU pin)",
                "windows": 3,
                "cold_rps": round(mc), "cold_spread_pct": spc,
                "warm_rps": round(mw), "warm_spread_pct": spw,
                "warm_over_cold": round(mw / mc, 2),
                "cache_hit_rate": round(hit_rate, 3),
                "wire_rows": sum(c.stats["wire_rows"]
                                 for c in eng._clients),
                "miss_row_us": round(1e6 * (
                    eng._clients[0].miss_row_seconds() or 0), 1),
                "direct_s": round(ma, 4), "direct_spread_pct": spa,
                "routed_s": round(mb, 4), "routed_spread_pct": spb,
                "router_overhead_pct": round(100 * (mb - ma) / ma, 2),
                "identical": bool(identical),
            }
            router.close()
            cell.shutdown()
            kv.shutdown_server()
            kv.close()
            print("recsys probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            for eng in closers:
                try:
                    eng.close()
                    for c in eng._clients:
                        c.close()
                except Exception:
                    pass
            for srv in servers:
                try:
                    srv.stop()
                except Exception:
                    pass
            jax.config.update("jax_default_device", prev)

    recsys_summary = guarded("recsys-probe", recsys_probe, errors)

    def transform_probe():
        """ISSUE-9 transform probe, CPU-pinned like the serving probe:
        (a) the optimizing pass pipeline over the Program zoo (rewrite
        only — the bitwise verification gate lives in tier-1), stamping
        per-model ops-removed; (b) interleaved A/B step-time delta of
        the TRANSFORMED vs untransformed program on the dispatch-bound
        train shape (megastep-probe protocol: alternating windows,
        median + spread); (c) the autoparallel planner's top-3 ranking
        for the transformer zoo model at 8 virtual devices."""
        import jax
        import numpy as np
        from paddle_tpu import flags as _flags
        from paddle_tpu.models import (TRANSFORM_ZOO,
                                       transform_zoo_entry)
        from paddle_tpu.models import transformer as T
        from paddle_tpu.transform import PassManager, recommend
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        # pin the armed-transform flag OFF for the A/B: with
        # PADDLE_TPU_TRANSFORM=1 in the environment the "untransformed"
        # arm would silently compile the transformed clone too and the
        # stamped delta would measure transformed-vs-transformed
        _flags.set_flag("transform", False)
        try:
            removed = {}
            for name in sorted(TRANSFORM_ZOO):
                main, _, _, fetch_names = transform_zoo_entry(name)
                removed[name] = PassManager().run(
                    main, keep=fetch_names).ops_removed

            _fresh()
            avg_cost, _ = T.transformer_lm(
                vocab_size=256, max_len=16, n_layer=2, n_head=2,
                d_model=64, d_inner=256, packed=True)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
            main = fluid.default_main_program()
            transformed = PassManager().run(
                main, keep=[avg_cost.name]).program
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            rng = np.random.RandomState(0)
            feed = T.make_lm_batch(rng, 4, 16, 256)
            feed["mask"] = np.ones_like(feed["mask"])
            toks = int(feed["mask"].sum())
            steps, wins = 64, 5

            def win(prog):
                t0 = time.perf_counter()
                last = None
                for _ in range(steps):
                    last = exe.run(prog, feed=feed,
                                   fetch_list=[avg_cost.name],
                                   return_numpy=False)
                jax.block_until_ready(last)
                return steps * toks / (time.perf_counter() - t0)

            win(main), win(transformed)     # warm both compiles
            a, b = [], []
            for _ in range(wins):           # interleaved A/B
                a.append(win(main))
                b.append(win(transformed))
            m0, sp0, s0 = agg(a, nd=0)
            m1, sp1, s1 = agg(b, nd=0)

            plans = recommend("transformer", 8, top=3)
            probe = {
                "zoo_ops_removed": removed,
                "config": "transformer_lm 2L/d64 bs4 T16 (CPU pin)",
                "steps_per_window": steps, "windows": wins,
                "untransformed_tok_s": round(m0),
                "untransformed_spread_pct": sp0,
                "untransformed_samples": s0,
                "transformed_tok_s": round(m1),
                "transformed_spread_pct": sp1,
                "transformed_samples": s1,
                "delta_pct": round(100.0 * (m1 - m0) / m0, 1),
                "planner_top3_transformer_8dev": [
                    {"plan": p.describe(),
                     "cost_s": float("%.3e" % p.cost)}
                    for p in plans],
            }
            print("transform probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            _flags.set_flag("transform", None)   # back to env-driven
            jax.config.update("jax_default_device", prev)

    transform_summary = guarded("transform-probe", transform_probe,
                                errors)

    def specialize_probe():
        """ISSUE-15 specialize probe, CPU-pinned (process-level pin —
        the engine decode loop is a background thread): (a) per-zoo-
        model fusion-pattern hits from the full optimizing pipeline;
        (b) artifact cold-boot wall — save_inference_model ->
        fresh-scope load -> parameter-stream replay into the decode
        model; (c) interleaved A/B serving tok/s of the artifact-booted
        engine vs the source-model engine, with the token-identity
        verdict (the ISSUE acceptance A/B: specialization must not
        regress serving)."""
        import shutil
        import tempfile
        import jax
        import numpy as np
        from paddle_tpu import serving
        from paddle_tpu.models import TRANSFORM_ZOO, transform_zoo_entry
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.transformer_infer import TransformerLMInfer
        from paddle_tpu.transform import PassManager, default_passes
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        eng_src = eng_art = None
        art = None
        try:
            fused = {}
            for name in sorted(TRANSFORM_ZOO):
                main, _, _, fetch_names = transform_zoo_entry(name)
                res = PassManager(default_passes()).run(
                    main, keep=fetch_names)
                fused[name] = sum(v for v in res.patterns.values())
            zoo_fused_total = sum(fused.values())

            _fresh()
            main, startup = (fluid.default_main_program(),
                             fluid.default_startup_program())
            scope = fluid.global_scope()
            avg_cost, logits = T.transformer_lm(
                vocab_size=64, max_len=96, n_layer=2, n_head=2,
                d_model=64, d_inner=128)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            lm = TransformerLMInfer(main, scope, 2, 2, 64, 96)
            art = tempfile.mkdtemp(prefix="ptpu_artifact_")
            serving.save_lm_artifact(art, main, scope, [logits],
                                     2, 2, 64, 96)
            t0 = time.perf_counter()
            model2 = serving.model_from_artifact(art)
            boot_s = time.perf_counter() - t0

            eng_src = serving.Engine(lm, slots=4, prefill_chunk=8,
                                     name="spec-src")
            eng_art = serving.Engine(model2, slots=4, prefill_chunk=8,
                                     name="spec-art")
            rng = np.random.RandomState(0)
            prompts = [[1] + rng.randint(3, 64,
                                         int(rng.randint(1, 10))).tolist()
                       for _ in range(12)]

            def win(e):
                t0 = time.perf_counter()
                outs = e.generate_many(prompts, 24)
                toks = sum(len(t) for t, _ in outs)
                return (toks / (time.perf_counter() - t0),
                        [t for t, _ in outs])

            win(eng_src), win(eng_art)          # warm both compiles
            a, b, identical = [], [], True
            for _ in range(3):                  # interleaved A/B
                sa, ta = win(eng_src)
                sb, tb = win(eng_art)
                a.append(sa)
                b.append(sb)
                identical = identical and (ta == tb)
            m0, sp0, s0 = agg(a, nd=0)
            m1, sp1, s1 = agg(b, nd=0)
            probe = {
                "zoo_fused_ops": fused,
                "zoo_fused_total": zoo_fused_total,
                "config": "transformer_lm 2L/d64 T96, 12 mixed reqs "
                          "x24 new, slots=4 (CPU pin)",
                "artifact_boot_s": round(boot_s, 3),
                "source_tok_s": round(m0),
                "source_spread_pct": sp0,
                "artifact_tok_s": round(m1),
                "artifact_spread_pct": sp1,
                "serving_delta_pct": round(100.0 * (m1 - m0) / m0, 1),
                "identical": identical,
            }
            print("specialize probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            for e in (eng_src, eng_art):
                if e is not None:
                    e.close()
            if art is not None:
                shutil.rmtree(art, ignore_errors=True)
            jax.config.update("jax_default_device", prev)

    specialize_summary = guarded("specialize-probe", specialize_probe,
                                 errors)

    def alerts_probe():
        """ISSUE-14 signal-plane probe: an ARMED mini-fleet (private
        registry behind a real TelemetryServer, scraped by a real
        Collector over RPC) driven on a synthetic clock — a clean
        interleaved window first (healthy traffic + benign queue
        wiggle; any transition is a FALSE POSITIVE), then an injected
        error burst + queue pressure, stamping detection latency in
        scrape rounds from the injected fault to the page-severity
        FIRING. Synthetic-clock rounds make the window math exact and
        the probe sub-second — no sleeping on scrape intervals."""
        from paddle_tpu.monitor import metrics as mm
        from paddle_tpu.monitor import signals as sg
        from paddle_tpu.monitor.collector import (Collector,
                                                  TelemetryServer)
        reg = mm.Registry()
        ret = reg.counter("ptpu_serving_retirements_total", "")
        fail = reg.counter("ptpu_serving_request_failures_total", "")
        qd = reg.gauge("ptpu_serving_queue_depth", "")
        srv = TelemetryServer(registry=reg, role="replica").start()
        col = Collector(static=[("replica", srv.endpoint)])
        try:
            sig = sg.Signals(spec={"objectives": [
                {"metric": "error_rate", "target": 0.95,
                 "windows": [{"short_s": 4.0, "long_s": 16.0,
                              "burn_rate": 2.0,
                              "severity": "page"}]}]})
            t0 = 1_000_000.0
            clean_rounds, false_pos = 12, 0
            for r in range(clean_rounds):
                ret.inc(20)
                qd.set(r % 3)
                col.scrape_once()
                false_pos += len(sig.observe(
                    snapshot=col.fleet_snapshot(), now=t0 + r))
            detect = None
            for r in range(clean_rounds, clean_rounds + 12):
                fail.inc(20)             # full outage: every request
                qd.set(64)               # fails + the queue backs up
                col.scrape_once()
                trs = sig.observe(snapshot=col.fleet_snapshot(),
                                  now=t0 + r)
                if any(t["state"] == "FIRING"
                       and t["severity"] == "page" for t in trs):
                    detect = r - clean_rounds + 1
                    break
            hint = sig.scale_hint()
            probe = {
                "clean_rounds": clean_rounds,
                "false_positives": false_pos,
                "detection_rounds": detect,
                "scale_hint": hint.direction,
                "scale_magnitude": hint.magnitude,
            }
            print("alerts probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            col.close()
            srv.stop()

    alerts_summary = guarded("alerts-probe", alerts_probe, errors)

    def forensics_probe():
        """ISSUE-17 incident-forensics probe, CPU-pinned like the
        fleet probe: (a) DISARMED overhead of the tail span ring — the
        same mixed request set through a 3-replica fleet with tracing
        at 1/64 head sampling, interleaved A/B windows with the ring ON
        (the new default) vs OFF (``tail_window=0``, the historical
        behavior); (b) the ARMED path — wall clock of one full fleet
        DUMP capture (lease-discovered KV + 3 replicas assembled into a
        CRC-manifested bundle) plus the bundle's verify verdict."""
        import shutil
        import tempfile

        import jax
        import numpy as np
        from paddle_tpu import trace
        from paddle_tpu.distributed.membership import KVServer, KVClient
        from paddle_tpu.models import transformer as T
        from paddle_tpu.models.transformer_infer import TransformerLMInfer
        from paddle_tpu.monitor import forensics as fx
        from paddle_tpu.serving import fleet
        prev = jax.config.jax_default_device
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        tdir = tempfile.mkdtemp(prefix="ptpu-bench-fx-")
        try:
            _fresh()
            scope = fluid.global_scope()
            # decode-bound shape (fleet-probe rationale): the ring's
            # per-span cost must be measured against real decode work,
            # not a dispatch-bound toy
            T.transformer_lm(vocab_size=256, max_len=160, n_layer=2,
                             n_head=4, d_model=256, d_inner=1024)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            lm = TransformerLMInfer(fluid.default_main_program(), scope,
                                    2, 4, 256, 160)
            rng = np.random.RandomState(0)
            prompts, news = [], []
            for _ in range(12):
                plen = int(rng.randint(1, 9))
                prompts.append([1] + rng.randint(3, 256,
                                                 plen - 1).tolist())
                news.append(int(rng.randint(32, 65)))
            kvs = KVServer(sweep_interval=0.05).start()
            kv = KVClient(kvs.endpoint)
            cells = [fleet.Replica(kv, lm, desired=3, slots=2,
                                   prefill_chunk=8, ttl=0.5)
                     for _ in range(3)]
            router = fleet.Router(kvs.endpoint, window=4,
                                  refresh_interval=0.05)
            router.wait_for_replicas(3)

            def win(tail_window, tag):
                trace.enable(
                    log_path=os.path.join(
                        tdir, "spans-%s.jsonl" % tag),
                    sample_rate=1.0 / 64, tail_window=tail_window)
                t0 = time.perf_counter()
                out = router.generate_many(prompts, news, timeout=120)
                dt = time.perf_counter() - t0
                trace.disable()
                return sum(len(t) for t, _ in out) / dt

            win(256, "w1"), win(0, "w2")      # warm every compile
            a_tps, b_tps = [], []
            for w in range(3):                # interleaved A/B
                a_tps.append(win(256, "on%d" % w))
                b_tps.append(win(0, "off%d" % w))
            ma, spa, _ = agg(a_tps, nd=1)
            mb, spb, _ = agg(b_tps, nd=1)

            # armed pass: populate the rings, then time one full
            # lease-discovered fleet capture
            trace.enable(log_path=os.path.join(tdir, "spans-arm.jsonl"),
                         sample_rate=1.0 / 64, tail_window=256)
            router.generate_many(prompts, news, timeout=120)
            t0 = time.perf_counter()
            bundle = fx.capture(kv_endpoint=kvs.endpoint,
                                deadline_s=2.0, out_dir=tdir)
            cap_ms = 1000 * (time.perf_counter() - t0)
            man = fx.load_manifest(bundle)
            probe = {
                "config": "transformer_lm 2L/d256, 12 mixed reqs "
                          "(32-64 new tokens), 3 replicas, sampling "
                          "1/64 (CPU pin)",
                "ring_on_tokens_per_s": round(ma, 1),
                "ring_off_tokens_per_s": round(mb, 1),
                "ring_on_spread_pct": spa,
                "ring_off_spread_pct": spb,
                "ring_overhead_pct": round(100 * (mb - ma) / mb, 2),
                "capture_ms": round(cap_ms, 1),
                "bundle_parts": len(man["parts"]),
                "bundle_missing": len(man["missing"]),
                "bundle_crc_ok": fx.verify(bundle) == [],
            }
            trace.disable()
            router.close()
            for c in cells:
                try:
                    c.shutdown()
                except Exception:
                    pass
            kv.shutdown_server()
            kv.close()
            print("forensics probe: %s" % probe, file=sys.stderr)
            return probe
        finally:
            from paddle_tpu import trace as _trace
            _trace.disable()
            shutil.rmtree(tdir, ignore_errors=True)
            jax.config.update("jax_default_device", prev)

    forensics_summary = guarded("forensics-probe", forensics_probe,
                                errors)

    ips, res_spread, res_samples = agg(res_s)
    large_flops_tok = flops_per_token(L=8, D=1024, FFN=4096, T=1024,
                                      V=8192)
    xl_flops_tok = flops_per_token(L=8, D=2048, FFN=8192, T=1024, V=8192)
    tps_large, large_spread, large_samples = agg(large_s)
    tps_xl, xl_spread, xl_samples = agg(xl_s)
    lstm_ms, lstm_spread, lstm_samples = agg(lstm_s)

    # the JSON stamps even when the headline failed every repeat: a
    # null value + per-config errors beats an aborted, empty record
    out = {
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(float(ips), 1) if ips is not None else None,
        "unit": "imgs/sec",
        "vs_baseline": round(float(ips) / baseline, 2)
        if ips is not None else None,
        "mfu_pct": round(ips * FLOPS_PER_IMG_TRAIN / PEAK_BF16 * 100, 1)
        if ips is not None else None,
        "repeats": K,
        "spread_pct": res_spread,
        "samples": res_samples,
    }
    if tps_small is not None:
        out["transformer_tokens_per_sec_per_chip"] = round(tps_small, 0)
    if tps_large is not None:
        out["transformer_large_tokens_per_sec_per_chip"] = round(tps_large, 0)
        out["transformer_large_mfu_pct"] = round(
            tps_large * large_flops_tok / PEAK_BF16 * 100, 1)
        out["transformer_large_spread_pct"] = large_spread
        out["transformer_large_samples"] = large_samples
    if tps_xl is not None:
        out["transformer_xl_tokens_per_sec_per_chip"] = round(tps_xl, 0)
        out["transformer_xl_mfu_pct"] = round(
            tps_xl * xl_flops_tok / PEAK_BF16 * 100, 1)
        out["transformer_xl_spread_pct"] = xl_spread
        out["transformer_xl_samples"] = xl_samples
    if lstm_ms is not None:
        # reference anchor: 184 ms/batch (K40m, h=512 bs=64) — LOWER is
        # better, so vs_baseline > 1 means faster than the reference
        out["lstm_ms_per_batch"] = round(lstm_ms, 1)
        out["lstm_vs_baseline"] = round(184.0 / lstm_ms, 2)
        out["lstm_spread_pct"] = lstm_spread
        out["lstm_samples"] = lstm_samples
    if monitor_summary is not None:
        # runtime-telemetry stamp (paddle_tpu.monitor): per-step p50/p95,
        # recompile count and cost-model MFU of the monitored probe
        out["monitor"] = monitor_summary
    if serving_summary is not None:
        # continuous-batching stamp (paddle_tpu.serving): engine vs
        # sequential tokens/s, speedup, occupancy, token identity,
        # request-level SLO percentiles (TTFT/TPOT p50/p95) + the
        # fused-K megastep engine pass (megastep_* fields) + the
        # ISSUE-13 speculative-decode A/B (spec_* fields incl. the
        # perfgate-gated accepted_tokens_per_dispatch)
        out["serving"] = serving_summary
    if megastep_summary is not None:
        # megastep K-sweep stamp (ISSUE 7): K=1 vs K=8 interleaved
        # A/B medians on the dispatch-bound train shape
        out["megastep"] = megastep_summary
    if transform_summary is not None:
        # program-transform stamp (ISSUE 9): per-zoo-model ops removed
        # by the pass pipeline, transformed-vs-untransformed interleaved
        # A/B on the dispatch-bound train shape, and the autoparallel
        # planner's top-3 for the transformer zoo model at 8 devices
        out["transform"] = transform_summary
    if specialize_summary is not None:
        # inference-specialization stamp (ISSUE 15): per-zoo-model
        # fusion-pattern hits, artifact cold-boot wall, and the
        # artifact-vs-source serving A/B with token identity — the
        # perfgate-gated non-regression contract of the specialize
        # pipeline
        out["specialize"] = specialize_summary
    if fleet_summary is not None:
        # serving-fleet stamp (ISSUE 8): disarmed router overhead
        # (interleaved A/B vs direct engine, per-request p50/p95 added
        # latency) + the armed kill pass's resubmission/exactly-once
        # verdict
        out["fleet"] = fleet_summary
    if autoscale_summary is not None:
        # elastic-fleet stamp (ISSUE 18): disarmed autoscaler overhead
        # (plain vs managed fleet, interleaved A/B) + the
        # roll-under-traffic pass — shed count (contract: 0), roll
        # wall clock, p95 TTFT inflation during the roll, and the
        # token-identity verdict across the v1 -> v2 weight update
        out["autoscale"] = autoscale_summary
    if rollout_summary is not None:
        # canary-rollout stamp (ISSUE 19): disarmed mirror-path
        # overhead (plain vs managed fleet, interleaved A/B, <1%
        # budget) + the full shadow -> canary -> promote pipeline
        # under live traffic — per-phase delta verdicts, shed count
        # (contract: 0), joined mirror pairs, p95 TTFT inflation
        # during the rollout, and the token-identity verdict across
        # the promotion
        out["rollout"] = rollout_summary
    if alerts_summary is not None:
        # signal-plane stamp (ISSUE 14): armed mini-fleet alerting
        # probe — detection latency in scrape rounds from injected
        # fault to page-severity FIRING, zero-false-positive verdict
        # over the clean interleaved window, and the scale hint the
        # direction-2 supervisor would have consumed
        out["alerts"] = alerts_summary
    if forensics_summary is not None:
        # incident-forensics stamp (ISSUE 17): tail span ring on/off
        # interleaved A/B tokens/s through a 3-replica fleet (the
        # disarmed-overhead contract) + one armed fleet DUMP capture's
        # wall clock and the bundle's CRC verdict
        out["forensics"] = forensics_summary
    if recsys_summary is not None:
        # sparse-serving stamp (ISSUE 12): cold-vs-warm hot-ID cache
        # scoring throughput A/B, final cache hit rate, measured
        # miss-path cost, and routed-vs-direct overhead with the
        # bitwise score-identity verdict at a pinned cache version
        out["recsys"] = recsys_summary
    try:
        # platform stamp: a chipless (CPU-pinned) rehearsal round must
        # never be read as a chip round's throughput record
        import jax
        dev = jax.devices()[0]
        out["platform"] = dev.platform
        out["device_kind"] = getattr(dev, "device_kind", "")
    except Exception:
        pass
    try:
        # perf regression verdict vs the previous checked-in round
        # (ISSUE 11): the paddle_tpu.perfgate probe comparison with
        # explicit per-probe noise bands — platform-mismatched rounds
        # skip rather than scream. Advisory here (the round always
        # stamps); the CLI is the exit-code gate.
        from paddle_tpu import perfgate
        base = perfgate.latest_baseline(
            os.path.dirname(os.path.abspath(__file__)))
        if base is not None:
            v = perfgate.compare(out, base)
            out["perfgate"] = {
                "baseline": os.path.basename(base),
                "pass": v["pass"],
                "compared": v["compared"],
                "regressions": v["regressions"],
                "improvements": v["improvements"],
            }
            print(perfgate.render(v), file=sys.stderr)
    except Exception as e:
        errors.setdefault("perfgate", []).append(repr(e))
    if errors:
        # per-config failures: the record names what was lost instead
        # of the whole round vanishing — and the exit code says so
        out["errors"] = errors
    print(json.dumps(out))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
