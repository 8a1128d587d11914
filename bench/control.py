#!/usr/bin/env python3
"""Run one cell once, as ``run.py`` does, and also read the control:

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

After the window, on the same prompts and served tokens, the reference is
computed again in the program's place: the control, with the weights one
step below the stated ones (int4 for int8); ``control_act``, with the
stated weights and the activations one step below the stated compute
dtype (float8 for bfloat16); and the witness of the program's own
rounding, with the stated weights and compute dtype.  Each is held to
the cell's limits; the control has to come out not correct.  The result
line carries them beside the run's own keys, and every per-token reading
is saved under ``bench/.cache/control/``.  The benchmark's own runs do
not read them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    try:
        cell = harness.Cell(harness.ROOT, args.workload)
        out = harness.run(cell, args.seed, args.seconds, False, T_START,
                          control=True)
    except harness.RunError as e:
        harness._fail(str(e))
    print(json.dumps(out), flush=True)
