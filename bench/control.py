"""Read a cell's numbers compared under its control, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 1]

The control is the step a later change would be tempted to take:

* serving cells: the plain reference computed in fp8 (e4m3, every linear
  layer scaled per tensor: the precision below the bfloat16 the
  configuration states) in the program's place; the numbers read are the
  logit gaps of the tokens it puts first at each served position;
* DSE cells: the program's own sampled characterization
  (``behav_metrics_sampled``) switched on for validation, breaking the
  configuration's guarantee of exhaustive BEHAV.

With ``--fault`` the serving cells read, in the control's place, a fault
planted in the timed path (``fault_driver``): the numbers a limit must
stay below where the control cannot separate them.

With ``--program`` it reads the sound program itself, the lower readings
a limit is set above.

Each seed is one run of the cell (set-up, a short window, the check) in
this one process; one JSON line per seed gives its numbers and limits.
The control must come out not correct on every seed.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import types

import numpy as np

import run as harness


def sampled_characterize(spec, behav_key: str, ppa_key: str):
    """Validation's characterization on the program's sampled BEHAV path."""
    from repro.core.fastchar import behav_metrics_sampled
    from repro.core.ppa import ppa_metrics

    def fn(configs):
        behav, _ = behav_metrics_sampled(spec, configs)
        return np.stack([behav[behav_key], ppa_metrics(spec, configs)[ppa_key]],
                        axis=-1)

    return fn


def control_driver(cell):
    """The cell's driver with its control switched on."""
    drv = harness.load_module(cell.driver)
    if cell.traffic["kind"] == "serve":
        return types.SimpleNamespace(run=functools.partial(drv.run, control="fp8"))
    from repro.core.operator_model import spec_for

    op, obj = cell.config["operator"], cell.config["objectives"]
    spec = spec_for(int(op["n_bits"]), op["op"], bool(op["signed"]))
    fn = sampled_characterize(spec, obj["behav"], obj["ppa"])
    return types.SimpleNamespace(run=functools.partial(drv.run, characterize_fn=fn))


def fault_driver(cell, fault: str):
    """The serving cell's driver with ``fault`` planted where the timed path
    produces its state or its tokens: ``state_unchanged`` (each decode step
    hands back the cache it was given) or ``token_altered`` (every token one
    id off the step's argmax)."""
    drv = harness.load_module(cell.driver)

    class Broken(drv.Server):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            decode, argmax = self.decode, self.argmax
            if fault == "state_unchanged":
                self.decode = lambda p, cache, *x: (decode(p, cache, *x)[0], cache)
            elif fault == "token_altered":
                self.argmax = lambda lg: (argmax(lg) + 1) % lg.shape[-1]
            else:
                raise ValueError(f"no fault {fault!r}")

    drv.Server = Broken
    return drv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    which = ap.add_mutually_exclusive_group()
    which.add_argument("--fault", choices=("state_unchanged", "token_altered"))
    which.add_argument("--program", action="store_true",
                       help="read the sound program, not the control")
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    why = harness.check_devices(cell.chips)
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 2
    harness.enable_cache()
    for seed in args.seeds:
        driver = (fault_driver(cell, args.fault) if args.fault
                  else None if args.program else control_driver(cell))
        res = harness.run_cell(cell, seed, args.seconds, False, driver=driver)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "fault": args.fault, "program": args.program,
                          "correct": res["correct"], "checks": res["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
