"""chipbench: one cell, once.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

A new process that fails without a TPU (or with fewer chips than the
cell asks for), makes its weights and its traffic from ``--seed``,
warms only this cell's shapes, measures for ``--seconds`` and prints
ONE JSON object as the last line of its standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``. Everything else it says goes on
earlier lines.

``--rehearse`` is for the CPU only (``JAX_PLATFORMS=cpu``): the
configuration's and the mix's ``rehearse`` overrides cut the cell to a
tiny size and Pallas kernels run in interpret mode. Its last line says
``"platform": "cpu"`` and can never pass for a chip run; the driver
never passes the option.
"""

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import sys                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import cells, peaks, tracing   # noqa: E402

CACHE_DIR = os.path.join(HERE, ".cache")   # in .gitignore; never moves


def log(msg):
    print("[chipbench] " + msg, flush=True)


def device_or_exit(chips, rehearse):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(
            "chipbench: no TPU: JAX reports platform %r (%s). There is "
            "no CPU fallback; a CPU rehearsal is `JAX_PLATFORMS=cpu "
            "python3 chipbench/run.py --rehearse ...`."
            % (dev.platform, dev.device_kind))
    if len(devs) < chips:
        raise SystemExit("chipbench: the cell asks for %d chip(s) and "
                         "JAX reports %d" % (chips, len(devs)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}, devs[:chips]


def kernels_in_interpret_mode():
    """Rehearsal only, as chip_smoke.py does it: steer the attention
    dispatchers to their Pallas kernels in interpret mode."""
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import paged_attention as pa
    fa_resolve = fa._resolve_path
    fa._resolve_path = lambda q, scale, bq, bk, force: fa_resolve(
        q, scale, bq, bk, force or "interpret")
    pa._resolve_path = lambda q, force: force or "interpret"


def load_cell(workload, rehearse):
    """The cell's files; for a rehearsal, cut to the tiny size its
    configuration and mix state, with the kernels in interpret mode."""
    cell = cells.load_cell(ROOT, workload)
    if rehearse:
        for part in ("config_file", "traffic_file"):
            cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
        kernels_in_interpret_mode()
    return cell


def memory_peak(devices):
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    # the one compile cache, inside the checkout, whatever the
    # environment names: the program's own entry points are bypassed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    import paddle_tpu  # noqa: F401  (absent -> ImportError, exit != 0)

    cell = load_cell(args.workload, args.rehearse)
    device, devices = device_or_exit(cell["chips"], args.rehearse)
    chip = None if args.rehearse else peaks.peaks_for(device["kind"])
    log("cell %s: config %s, traffic %s, %d chip(s) of %s, seed %d, "
        "%.0f s%s" % (cell["name"], cell["config"], cell["traffic"],
                      cell["chips"], device["kind"], args.seed,
                      args.seconds, ", REHEARSAL (tiny, CPU)"
                      if args.rehearse else ""))

    driver = cells.load_driver(cell["traffic_file"]["driver"])
    trace_dir = os.path.join(CACHE_DIR, "trace") if args.trace else None
    entered_s = time.perf_counter() - T_START
    run = driver.run(cell, args.seed, args.seconds, devices,
                     t_start=T_START, trace_dir=trace_dir, log=log)
    # a driver that stamps no phases of its own still says when it began
    run.setdefault("setup_phases", {"entered": entered_s})
    run.update(cell=cell, config=cell["config_file"],
               traffic=cell["traffic_file"], chips=cell["chips"],
               peaks=chip, seconds=args.seconds)
    device["memory_peak_bytes"] = memory_peak(devices)
    log("peak HBM on the fullest chip: %.3f GiB"
        % (device["memory_peak_bytes"] / 2 ** 30))
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if args.trace:
        rows = tracing.load_rows(trace_dir)
        run["trace"] = tracing.reduce_rows(rows, cell["chips"])
        result["metrics"] = cells.read_metrics(cell, "per_layer", run)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"][:10],
            "idle_gaps": run["trace"]["idle_gaps"][:10]}
    else:
        result["metrics"] = cells.read_metrics(cell, "end_to_end", run)
    result["device"] = device
    for name, m in sorted(result["metrics"].items()):
        log("%s = %r %s" % (name, m["value"], m["unit"]))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
