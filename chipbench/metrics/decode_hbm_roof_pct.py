"""The decode step's share of the HBM roofline: the bytes a step must
read (``arith.decode_step_bytes``: the weights once and the K/V of the
tokens its rows attend to, means over the window from the engine's
counters and the requests' lengths) over the peak bandwidth, over the
step's median device time. Memory bounds this step: its FLOPs over the
peak rate take far less time."""
from chipbench import arith, records

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "itl_mean_ms"
PROGRAM = "_step_impl"


def read(run):
    step_s = records.percentile(records.module_runs(run, PROGRAM), 50)
    s = run["engine"]["stats"]
    if not step_s or not s["decode_steps"]:
        return None
    # context a request's decode steps attend to: P, P+1, .. P+n-1
    kv_tokens = sum(r["tokens"] * r["prompt_len"]
                    + r["tokens"] * (r["tokens"] - 1) / 2
                    for r in records.done(run))
    steps = s["decode_steps"]
    need = arith.decode_step_bytes(
        run["config"], 2, kv_tokens / steps,
        s["active_slot_steps"] / steps)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / step_s
