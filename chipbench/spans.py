"""The traced window once more, with what ``tracing.load_rows`` drops.

``tracing.py`` reduces the profiler's trace to device time by HLO op
kind and names idle gaps. This file reads the same ``.xplane.pb`` (from
the directory ``run.py`` traces into: ``run`` carries no path) and keeps
what that reduction throws away, so that readers can ask for device
time by the PROGRAM's layers and host time by the program's phases:

``host``     the program's host spans (``jax.profiler.TraceAnnotation``
             events opened through ``paddle_tpu.trace``: ``exe.*``,
             ``pexe.*``, ``engine.*``): ``{"name", "start", "dur"
             (seconds), "thread", "args"}``. ``self_time`` gives a
             span's duration less what its children cover.
``ops``      chip 0's device ops: ``{"name" (HLO instruction), "kind"
             (numbering taken off: a Pallas kernel's ``name=``),
             "start", "dur", "program" (the jitted function), "scope"
             (``mul.226``, ``adam.7``, ``kv.read`` ...), "direction"
             (``fwd`` for ``jvp(...)``, ``bwd`` for
             ``transpose(jvp(...))``, None), "op_name" (the whole
             ``tf_op``), "kernel" (bool)}``.
``modules``  chip 0's runs of jitted programs: ``{"program", "start",
             "dur"}``.
``compiles`` the program's compile log
             (``paddle_tpu.monitor.runtime.compile_log()``; ``end`` on
             ``time.perf_counter()``, the clock ``setup_s`` counts on).

Where the scope was found (one real trace of ``opt350m_train`` on a
TPU v5e, PR 24, looked at by hand): NOT in the event's name, which on
the TPU is the instruction's HLO text without its ``metadata={...}``,
and not among the event's own stats (offset and duration only), but in
the stats of the event's METADATA: ``tf_op`` holds the ``op_name``,
e.g. ``jit(step)/transpose(jvp(mul.226))/dot_general``.
``jax.profiler.ProfileData`` does not show metadata stats, so this file
decodes the protobuf's wire format itself (the six message types of
``xplane.proto``; no package beyond the standard library). The device
plane has no name-scope line. XLA gives a fusion the ``op_name`` of
each instruction it merged, joined by ``;``, the fusion's root first as
far as the trace shows (a matmul fusion starts with its
``dot_general``): an op is attributed to the FIRST of them that names a
Program op or a serving scope, so a fused op's whole time goes to one
scope.

A program that opens no spans (the parent of the PR that added them)
gives an empty ``host`` list and no ``compiles``; readers then return
None and their metric is left out of the line.
"""

import glob
import os
import re
import struct

from chipbench import arith, records, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, ".cache", "trace")   # run.py's, fixed
PROGRAM_SPANS = ("exe.", "pexe.", "engine.")
SERVING_SCOPES = ("kv.read", "kv.write", "attn", "mlp", "head", "sample")
_SCOPE = re.compile(
    r"^(?:(transpose)\()?(?:(jvp)\()?([A-Za-z_]\w*\.\d+|%s)\)*$"
    % "|".join(re.escape(s) for s in SERVING_SCOPES))


def say(msg):
    print("[chipbench] " + msg, flush=True)


# -- the protobuf wire format, as far as xplane.proto needs it -------------

def _fields(buf):
    """(field number, wire type, value) of one message: varints as
    ints, 64-bit fields as 8 bytes, length-delimited fields as a
    memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError("xplane: wire type %d" % wire)
        yield key >> 3, wire, val


def _stat(buf, stat_names):
    """One XStat -> (name, value)."""
    name = value = None
    for field, _, val in _fields(buf):
        if field == 1:
            name = stat_names.get(val, str(val))
        elif field == 2:
            value = struct.unpack("<d", val)[0]
        elif field in (3, 4):
            if field == 4 and val >= 1 << 63:
                val -= 1 << 64
            value = val
        elif field in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif field == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _plane_name(buf):
    return next((bytes(v).decode() for f, _, v in _fields(buf)
                 if f == 2), "")


def _plane(buf, wanted_lines):
    """One XPlane -> [{"line", "name", "start", "dur", "stats",
    "meta"}]: the events of the lines ``wanted_lines(line name)``
    accepts, in seconds, with the event's own stats and its
    metadata's."""
    lines, metas, stat_names = [], {}, {}
    for field, _, val in _fields(buf):
        if field == 3:
            lines.append(val)
        elif field in (4, 5):                   # map entries
            key = entry = None
            for f, _, v in _fields(val):
                if f == 1:
                    key = v
                elif f == 2:
                    entry = v
            if field == 4:
                metas[key] = entry
            else:
                stat_names[key] = next(
                    (bytes(v).decode() for f, _, v in _fields(entry)
                     if f == 2), "")
    decoded = {}

    def meta(mid):
        if mid not in decoded:
            mname, stats = "", {}
            for f, _, v in _fields(metas.get(mid, b"")):
                if f == 2:
                    mname = bytes(v).decode("utf-8", "replace")
                elif f == 5:
                    k, x = _stat(v, stat_names)
                    stats[k] = x
            decoded[mid] = (mname, stats)
        return decoded[mid]

    events = []
    for line in lines:
        lname, t0_ns, raw = "", 0, []
        for field, _, val in _fields(line):
            if field == 2:
                lname = bytes(val).decode()
            elif field == 3:
                t0_ns = val
            elif field == 4:
                raw.append(val)
        if not wanted_lines(lname):
            continue
        for ev in raw:
            mid = off_ps = dur_ps = 0
            stats = {}
            for field, _, val in _fields(ev):
                if field == 1:
                    mid = val
                elif field == 2:
                    off_ps = val
                elif field == 3:
                    dur_ps = val
                elif field == 4:
                    k, x = _stat(val, stat_names)
                    stats[k] = x
            mname, mstats = meta(mid)
            events.append({"line": lname, "name": mname,
                           "start": t0_ns * 1e-9 + off_ps * 1e-12,
                           "dur": dur_ps * 1e-12, "stats": stats,
                           "meta": mstats})
    return events


# -- names ------------------------------------------------------------------

def parse_op_name(op_name):
    """``jit(step)/transpose(jvp(mul.226))/dot_general`` ->
    ``("step", "mul.226", "bwd")``. The program is the outermost
    ``jit(...)``; the scope is the first path component that is a
    Program op (``<op_type>.<seq>``) or a serving scope, bare or inside
    ``jvp(...)`` (forward of a differentiated step: ``"fwd"``) or
    ``transpose(jvp(...))`` (backward: ``"bwd"``); bare gives direction
    None. Where XLA joined several names with ``;`` the first that has
    a scope decides. No scope: ``(program, None, None)``."""
    program = None
    for one in (op_name or "").split(";"):
        parts = one.strip().split("/")
        m = re.match(r"^jit\((.*)\)$", parts[0])
        if m and program is None:
            program = m.group(1)
        for part in parts[1:] if m else parts:
            s = _SCOPE.match(part)
            if s:
                return (program, s.group(3),
                        "bwd" if s.group(1) else
                        "fwd" if s.group(2) else None)
    return program, None, None


def scope_type(scope):
    """``mul.226`` -> ``mul``; a serving scope (``kv.read``) and None
    are their own type."""
    if scope is None or scope in SERVING_SCOPES:
        return scope
    return scope.rsplit(".", 1)[0]


# -- the window -------------------------------------------------------------

def load(trace_dir=TRACE_DIR):
    """The traced window's ``host`` / ``ops`` / ``modules`` /
    ``compiles`` (see the module's docstring), or None where there is
    no trace to read."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        return None
    with open(paths[0], "rb") as f:
        space = memoryview(f.read())
    planes = [val for field, _, val in _fields(space) if field == 1]
    host, ops, modules = [], [], []
    # chip 0 is, as in tracing.reduce_rows, the first device plane by
    # name that holds ops (other "/device:" planes hold none)
    for name, buf in sorted(((_plane_name(p), p) for p in planes),
                            key=lambda named: named[0]):
        if name.startswith("/host:CPU"):
            events = _plane(buf, lambda line: True)
            host += [{"name": e["name"], "start": e["start"],
                      "dur": e["dur"], "thread": e["line"],
                      "args": e["stats"]} for e in events
                     if e["name"].startswith(PROGRAM_SPANS)]
        elif name.startswith("/device:") and not ops:
            events = _plane(buf, lambda line: line in (
                tracing.MODULE_LINE, tracing.OP_LINE))
            for e in events:
                if e["line"] == tracing.MODULE_LINE:
                    modules.append({
                        "program": tracing.module_name(e["name"]),
                        "start": e["start"], "dur": e["dur"]})
                else:
                    ops.append(device_op(e["name"], e["start"], e["dur"],
                                         e["meta"].get("tf_op")))
    return {"host": sorted(host, key=lambda s: s["start"]),
            "ops": sorted(ops, key=lambda o: o["start"]),
            "modules": sorted(modules, key=lambda m: m["start"]),
            "compiles": compile_log()}


def device_op(text, start, dur, op_name):
    """One device op row from its event: ``text`` is the event's name
    (the instruction's HLO text, or its name alone), ``op_name`` its
    metadata's ``tf_op``."""
    name = text.split(" = ")[0].lstrip("%")
    program, scope, direction = parse_op_name(op_name)
    return {"name": name, "kind": tracing.op_name(name), "start": start,
            "dur": dur, "program": program, "scope": scope,
            "direction": direction, "op_name": op_name,
            "kernel": "tpu_custom_call" in text}


def compile_log():
    """The program's compile log, or None where the program keeps
    none."""
    try:
        from paddle_tpu.monitor import runtime
    except ImportError:
        return None
    log = getattr(runtime, "compile_log", None)
    return log() if log else None


def of(run):
    """The window of a traced run, loaded once and kept on ``run``;
    None for a run that was not traced."""
    if "spans" not in run:
        run["spans"] = load() if run.get("trace") else None
    return run["spans"]


# -- what the readers share -------------------------------------------------

def _covered(intervals, lo, hi):
    """Length of [lo, hi] that the intervals cover."""
    return sum(e - s for s, e in tracing._union(
        (max(s, lo), min(e, hi)) for s, e in intervals
        if e > lo and s < hi))


def self_time(span, spans):
    """A span's duration less the part of it that its children cover:
    the other spans of ``spans`` on its thread that lie inside it
    (children of children cover nothing more; overlapping children
    count once)."""
    kids = [(c["start"], c["start"] + c["dur"])
            for c in children(span, spans)]
    return span["dur"] - _covered(kids, span["start"],
                                  span["start"] + span["dur"])


def children(span, spans, name=None):
    """The spans inside ``span`` on its thread, optionally by name."""
    lo, hi = span["start"], span["start"] + span["dur"]
    return [s for s in spans if s is not span
            and s["thread"] == span["thread"] and s["start"] >= lo
            and s["start"] + s["dur"] <= hi
            and (name is None or s["name"] == name)]


def device_time(window, program=None, **want):
    """Device seconds of chip 0's ops whose fields equal ``want``
    (``kind="flash_fwd"``, ``scope_type="mul"``, ``scope=None`` ...),
    in the runs of ``program`` (every program if None)."""
    total = 0.0
    for op in window["ops"]:
        if program is not None and op["program"] != program:
            continue
        if all((scope_type(op["scope"]) if k == "scope_type" else op[k])
               == v for k, v in want.items()):
            total += op["dur"]
    return total


def roof_pct(run, kinds, flops_share):
    """Share of the compute roofline of the Pallas kernels ``kinds`` in
    the traced train steps: ``flops_share`` of
    ``arith.flash_flops_per_step`` (the cell's chips share them) over
    the peak bf16 rate over the kernels' device time on chip 0. Also
    gives each kernel's seconds, for the reader's log line."""
    window = of(run)
    if not window:
        return None, {}
    _, steps = step_program(window)
    seconds = {k: device_time(window, kind=k, kernel=True)
               for k in kinds}
    if not steps or not all(seconds.values()):
        return None, seconds
    t = run["train"]
    flops = flops_share * steps * arith.flash_flops_per_step(
        run["config"], t["batch"], t["seq_len"]) / run["chips"]
    return (100.0 * flops / run["peaks"]["flops_bf16"]
            / sum(seconds.values())), seconds


def busy_share_pct(run, seconds):
    """``seconds`` of chip 0's device time over the traced window's
    busy time (``tracing.reduce_rows``' union of device-op
    intervals)."""
    return 100.0 * seconds / run["trace"]["busy_s"]


def ops_total(window):
    """The sum of chip 0's device-op times (ops can overlap, so this is
    the sum ``breakdown.device_ops`` is built from and not the union
    that ``busy_s`` is)."""
    return sum(op["dur"] for op in window["ops"])


def step_program(window):
    """The jitted program that holds the most device time, and how
    many runs of it were traced: the train step."""
    total = {}
    for m in window["modules"]:
        total[m["program"]] = total.get(m["program"], 0.0) + m["dur"]
    if not total:
        return None, 0
    program = max(total, key=total.get)
    return program, sum(m["program"] == program
                        for m in window["modules"])


def compile_seconds(run, whats):
    """From the compile log, for the phases ``whats``: (seconds before
    the measured window, their split by ``fun_name``, the count of such
    phases that ended inside the window or after it). Phases nest (a
    jitted function traced inside another; a cache retrieval inside a
    ``backend_compile_duration``), so seconds are the union of the
    phases' intervals, and a function is charged what its own phases
    cover. "Before the window" is ``run.py``'s ``T_START`` plus
    ``run["setup_s"]``; where there is no ``__main__.T_START`` (a test
    calling a reader on a dict) the whole log counts. None where the
    program keeps no log."""
    window = of(run)
    log = window and window.get("compiles")
    if log is None:
        return None
    import __main__
    t_start = getattr(__main__, "T_START", None)
    cut = float("inf") if t_start is None else t_start + run["setup_s"]
    rows = [r for r in log if r["what"] in whats]
    before = [(r["end"] - r["seconds"], r["end"], r["fun_name"])
              for r in rows if r["end"] <= cut]
    by_fun = {}
    for fun in {b[2] for b in before}:
        by_fun[fun] = _covered([b[:2] for b in before if b[2] == fun],
                               -float("inf"), float("inf"))
    total = _covered([b[:2] for b in before], -float("inf"),
                     float("inf"))
    return total, by_fun, sum(r["end"] > cut for r in rows)


def setup_seconds(run, metric, whats):
    """``compile_seconds`` for a set-up reader: the seconds before the
    window, with the reader's log line (the split by function, largest
    first, and the count of phases that ended inside the window)."""
    got = compile_seconds(run, whats)
    if got is None:
        return None
    total, by_fun, late = got
    top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:6]
    say("%s: %.3f s before the window (%s); %d such phase(s) ended "
        "inside the window" % (metric, total, ", ".join(
            "%s %.3f" % (fun or "cache retrieval", s)
            for fun, s in top), late))
    return total


def self_ms(run, root, less):
    """Median over the traced ``root`` spans of the root's duration
    less its children named in ``less``, in ms; roots with no such
    child (a step that compiled, an iteration that decoded nothing) are
    left out. None where the program opens no such span."""
    window = of(run)
    if not window:
        return None
    host = window["host"]
    own = []
    for span in host:
        if span["name"] != root:
            continue
        inside = [c for name in less for c in children(span, host, name)]
        if inside:
            own.append(1e3 * (span["dur"] - sum(c["dur"]
                                                for c in inside)))
    return records.percentile(own, 50)
