"""The readings a limit of ``correct`` is set from, and its control.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13,...

For a training cell, in ONE process and with no timed window: for each
seed the cell's program as ``drivers/train_steps.py`` builds it (its
``trainer``: the same seed folding, Adam, bf16 AMP), its parameters as
initialised, and the driver's own comparison
(``train_steps.forward_against_reference``: the float32 reference's
logits on the first sequence's last ``check_rows`` rows, handed the
program's choices where the model makes any), and against that
reference the error of

* the program's own bf16-AMP forward: the number ``correct`` compares,
  which has to stay under the architecture's ``TRAIN_LOGITS_RTOL``;
* the CONTROL (``arch.control_logits_at``): the reference put in the
  program's place and computed in the nearest precision below the one
  the configuration states. It has to come out as NOT correct. For a
  model that chooses it is handed what the reference was handed and
  routes as the float32 reference does, so that fp8 arithmetic alone
  separates the two (``README.md``, "an architecture", says why).

Every number is printed beside the limit. The last line is one JSON
object: the largest sound reading, the smallest control reading, the
limit, ``routed`` (whether the reference was handed choices) and
``separates``: whether the control's smallest is at least three times
the program's largest, without which no limit holds. Exit
code 0 only if every program reading is under the limit, every control
reading over it and they separate. The benchmark's own runs never call
this; ``tests/chipbench/test_chipbench_control.py`` runs it at tiny
size on the CPU (``--rehearse``), and ``PERF.md`` has the chip's
readings at the cell's own size.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import cells, run as entry, traffic  # noqa: E402
from chipbench.drivers import train_steps            # noqa: E402
from chipbench.reference import compare              # noqa: E402


def readings(cell, seed, on_tpu):
    """(the program's logits error, the control's, whether the
    reference was handed the program's choices) for one seed."""
    import jax
    import numpy as np
    cfg, mix = cell["config_file"], cell["traffic_file"]
    seq, rows = int(mix["seq_len"]), int(mix["check_rows"])
    with train_steps.trainer(cell, seed, on_tpu) as t:
        (first,) = train_steps.declared_feeds(t.main, traffic.lm_batches(
            seed, 1, 1, seq, cfg["vocab_size"]))
        # on the host: the forward's run donates the scope's arrays,
        # and the control is read after it
        params = jax.tree.map(np.asarray, t.arch.params_of_program(
            t.main, t.scope, cfg))
        got, ref, choices = train_steps.forward_against_reference(
            t, params, first, rows)
        control = train_steps.reference_rows(
            t.arch.control_logits_at, cfg, params, first, rows, choices)
    return (compare.logits_error(got, ref),
            compare.logits_error(control, ref), choices is not None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, comma separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_compilation_cache_dir", entry.CACHE_DIR)
    cell = entry.load_cell(args.workload, args.rehearse)
    _, devices = entry.device_or_exit(cell["chips"], args.rehearse)
    limit = cells.load_arch(cell["config_file"]["arch"]).TRAIN_LOGITS_RTOL
    got = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        program, control, routed = readings(
            cell, seed, devices[0].platform == "tpu")
        got.append((program, control))
        entry.log("seed %d: the program's logits error %.3e, the "
                  "control's %.3e (limit %.0e)" % (seed, program, control,
                                                   limit))
    program_max = max(p for p, _ in got)
    control_min = min(c for _, c in got)   # NaN: the control gave no number
    result = {"program_max": program_max, "control_min": control_min,
              "limit": limit, "seeds": len(got), "routed": routed,
              "separates": bool(control_min >= 3 * program_max)}
    print(json.dumps(result), flush=True)
    return 0 if (program_max <= limit < control_min
                 and result["separates"]) else 1


if __name__ == "__main__":
    sys.exit(main())
