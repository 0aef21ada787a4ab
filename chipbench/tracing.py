"""From the profiler's trace to numbers: chipbench's own reduction.

``start`` / ``stop`` wrap ``jax.profiler`` (Python tracer off: it
slows the very host loop whose gaps are being measured). ``load_rows``
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into plain
rows, and ``reduce_rows`` turns rows into what the metric readers use.
The reduction works on rows, so the tests check it on a small recorded
trace kept as JSON.

A row: ``{"plane", "line", "name", "start" (s), "dur" (s)}``; a device
op's ``name`` is its HLO name alone.
Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line has
one event per run of a jitted program, ``XLA Ops`` one per HLO op.
"""

import glob
import os
import re
import shutil

import numpy as np

MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def start(trace_dir):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop():
    import jax
    jax.profiler.stop_trace()


def load_rows(trace_dir):
    import jax
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                row = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start": ev.start_ns * 1e-9,
                       "dur": ev.duration_ns * 1e-9}
                if device and line.name == OP_LINE:
                    # the event's name is the op's whole HLO text
                    row["name"] = ev.name.split(" = ")[0].lstrip("%")
                rows.append(row)
    return rows


def module_name(event_name):
    """``jit__step_impl(123456789)`` -> ``_step_impl``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return re.sub(r"^jit_", "", name)


def op_name(name):
    """``fusion.123`` -> ``fusion``, ``jvp_sp_attention.35_.1`` ->
    ``jvp_sp_attention``: an op's kind, with the numbering XLA gives
    its instances taken off."""
    name = name.lstrip("%").split(" ")[0]
    return re.sub(r"\.\d+", "", name).rstrip("_") or name


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_rows(rows, chips=1, gap_floor_s=20e-6):
    """What the readers use, from one traced window:

    ``window_s``   first device event's start to the last one's end
    ``busy_s``     union of device-op intervals, mean over the chips
    ``modules``    {program: [device seconds of each run]} on chip 0
    ``ops``        {"program/op": device seconds} on chip 0
    ``device_ops`` the same as a list, longest first
    ``idle_gaps``  [[what the host was doing, seconds]], longest first:
                   chip 0's idle gaps over ``gap_floor_s``, each named
                   by the innermost host runtime call open at its middle
    """
    planes = sorted({r["plane"] for r in rows
                     if r["plane"].startswith("/device:")})[:chips]
    if not planes:
        raise SystemExit("chipbench: the trace holds no device plane: "
                         "no operation ran on the device")
    dev = [r for r in rows if r["plane"] in planes]
    ops = [r for r in dev if r["line"] == OP_LINE]
    if not ops:
        raise SystemExit("chipbench: the trace holds no device "
                         "operation")
    t0 = min(r["start"] for r in ops)
    t1 = max(r["start"] + r["dur"] for r in ops)
    busy = [sum(e - s for s, e in _union(
        (r["start"], r["start"] + r["dur"]) for r in ops
        if r["plane"] == p)) for p in planes]

    first = planes[0]
    mods = sorted((r for r in dev if r["plane"] == first
                   and r["line"] == MODULE_LINE),
                  key=lambda r: r["start"])
    modules = {}
    for m in mods:
        modules.setdefault(module_name(m["name"]), []).append(m["dur"])
    ops0 = sorted((r for r in ops if r["plane"] == first),
                  key=lambda r: r["start"])
    op_time, mi = {}, 0
    for r in ops0:                  # both lists are in start order
        while mi + 1 < len(mods) and mods[mi + 1]["start"] <= r["start"]:
            mi += 1
        inside = mods and mods[mi]["start"] <= r["start"] \
            <= mods[mi]["start"] + mods[mi]["dur"]
        module = module_name(mods[mi]["name"]) if inside else "?"
        key = module + "/" + op_name(r["name"])
        op_time[key] = op_time.get(key, 0.0) + r["dur"]

    merged = _union((r["start"], r["start"] + r["dur"]) for r in ops0)
    host = [r for r in rows if r["plane"].startswith("/host:")
            and r["dur"] > 0]
    h_start = np.array([h["start"] for h in host])
    h_end = np.array([h["start"] + h["dur"] for h in host])
    gaps = {}
    for (_, a), (b, _) in zip(merged, merged[1:]):
        if b - a < gap_floor_s:
            continue
        mid = 0.5 * (a + b)
        live = np.flatnonzero((h_start <= mid) & (h_end >= mid))
        # the innermost of the runtime calls open at the gap's middle
        cause = "host: " + host[live[np.argmax(h_start[live])]]["name"] \
            if len(live) else "host: no runtime call open (Python code)"
        gaps[cause] = gaps.get(cause, 0.0) + (b - a)

    by_time = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])
    return {"window_s": t1 - t0, "busy_s": sum(busy) / len(busy),
            "modules": modules, "ops": op_time,
            "device_ops": by_time(op_time), "idle_gaps": by_time(gaps)}
